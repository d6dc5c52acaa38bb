"""Offline trace/metrics report: sorted-key table + cache efficiency.

Loads a Chrome trace_event JSON (written by paddle_tpu.profiler /
observability.tracing, or the legacy record-list format) and/or a
metrics dump (observability MetricsRegistry.to_json()) and prints:

- a fluid-style sorted-key table (Calls/Total/Min/Max/Ave/Ratio per
  event name), and
- a cache-efficiency summary (jit/meta cache hit rates, compile count
  and total compile time) from the executor metrics.

Usage:
    python tools/trace_report.py TRACE.json [--metrics METRICS.json]
        [--sorted-key total] [--limit 30]
    python tools/trace_report.py --demo [--out-dir DIR]

--demo runs a tiny cached 3-step training loop on CPU, writes
`trace_sample.timeline.json` + `metrics_sample.json` into --out-dir,
then reports on them — the zero-to-trace smoke path.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# mirror of paddle_tpu.observability.report.SORT_KEYS, duplicated so
# `--help` never pays the full framework import; a drift guard in
# tests/api/test_observability.py keeps them identical
SORT_KEYS = ("calls", "total", "max", "min", "ave")


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load_trace_events(path):
    """-> [(name, dur_ms, cat)] from any of the three on-disk shapes:
    {"traceEvents": [...]}, a bare event list, or the legacy profiler
    record list [{"name","start_s","dur_s","tid"}]."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        events = data.get("traceEvents")
        if events is None:
            raise ValueError(f"{path}: no 'traceEvents' key")
    elif isinstance(data, list):
        events = data
    else:
        raise ValueError(f"{path}: expected JSON object or array")
    out = []
    for e in events:
        if not isinstance(e, dict):
            continue
        if "dur_s" in e:                      # legacy record format
            out.append((e["name"], float(e["dur_s"]) * 1e3, "host"))
        elif e.get("ph") == "X":
            out.append((e["name"], float(e.get("dur", 0.0)) / 1e3,
                        e.get("cat", "")))
    return out


def load_metrics(path):
    """-> {name: snapshot} from MetricsRegistry.to_dict() JSON."""
    with open(path) as f:
        data = json.load(f)
    metrics = data.get("metrics", []) if isinstance(data, dict) else []
    out = {m["name"]: m for m in metrics if isinstance(m, dict)}
    if isinstance(data, dict) and isinstance(
            data.get("signals_sample"), dict):
        # the demo's dump_signals() payload rides the sample dump —
        # the alert-timeline lines read the latched lifecycle records
        out["signals_sample"] = data["signals_sample"]
    return out


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def print_event_table(events, sorted_key="total", limit=30, file=None):
    file = file if file is not None else sys.stdout
    # shared formatter with paddle_tpu.profiler (imported lazily so
    # `--help` stays instant; a report run imports the framework anyway)
    from paddle_tpu.observability.report import (aggregate_events,
                                                 format_event_table)
    agg = aggregate_events((name, dur_ms) for name, dur_ms, _cat in events)
    for line in format_event_table(
            agg, sorted_key, title="Trace Report",
            subtitle=f"Events: {len(events)}    "
                     f"Sorted by: {sorted_key or 'order'}", limit=limit):
        print(line, file=file)


def _counter_total(metrics, name):
    m = metrics.get(name)
    if not m:
        return 0
    return sum(v.get("value", 0) for v in m.get("values", []))


def _hist_totals(metrics, name):
    m = metrics.get(name)
    if not m:
        return 0, 0.0
    count = sum(v.get("count", 0) for v in m.get("values", []))
    total = sum(v.get("sum", 0.0) for v in m.get("values", []))
    return count, total


def print_cache_summary(metrics, file=None):
    file = file if file is not None else sys.stdout
    print("--------------------->    Cache Efficiency    <---------------------",
          file=file)
    for cache in ("jit_cache", "meta_cache"):
        hits = _counter_total(metrics, f"executor.{cache}.hits")
        misses = _counter_total(metrics, f"executor.{cache}.misses")
        evict = _counter_total(metrics, f"executor.{cache}.evictions")
        lookups = hits + misses
        rate = hits / lookups if lookups else 0.0
        print(f"{cache:<12} hits={hits:<8} misses={misses:<8} "
              f"evictions={evict:<6} hit-rate={rate:.1%}", file=file)
    compiles = _counter_total(metrics, "executor.compiles")
    ccount, ctotal = _hist_totals(metrics, "executor.compile_ms")
    bcount, btotal = _hist_totals(metrics, "executor.backend_compile_ms")
    steps = _counter_total(metrics, "executor.steps")
    scount, stotal = _hist_totals(metrics, "executor.step_ms")
    print(f"compiles={compiles} compile_time={ctotal / 1e3:.2f}s "
          f"(xla backend events: {bcount}, {btotal / 1e3:.2f}s)", file=file)
    if steps:
        print(f"steps={steps} avg_step={stotal / max(scount, 1):.3f}ms",
              file=file)
    if steps and compiles:
        amort = ctotal / steps
        print(f"amortized compile cost: {amort:.3f}ms/step over this run",
              file=file)
    disp = _counter_total(metrics, "executor.async.dispatches")
    if disp:
        waits = _counter_total(metrics, "executor.async.window_waits")
        _wc, wtotal = _hist_totals(metrics, "executor.async.host_sync_wait_ms")
        print(f"async: dispatches={disp} window_waits={waits} "
              f"host_sync_wait={wtotal / 1e3:.2f}s "
              f"errors={_counter_total(metrics, 'executor.async.errors')}",
              file=file)
    bb = _counter_total(metrics, "executor.bucket.batches")
    if bb:
        waste = _counter_total(metrics, "executor.bucket.pad_waste_elems")
        print(f"bucketing: batches={bb} pad_waste_elems={waste}", file=file)


def print_fault_summary(metrics, file=None):
    """Fault/recovery summary (robustness layer): printed only when a
    guarded executor / CheckpointManager left metrics behind."""
    file = file if file is not None else sys.stdout
    guard_steps = _counter_total(metrics, "executor.fault.guard_steps")
    saves = _counter_total(metrics, "checkpoint.saves")
    if not guard_steps and not saves:
        return
    nonfinite = _counter_total(metrics, "executor.fault.nonfinite")
    rollbacks = _counter_total(metrics, "executor.fault.rollbacks")
    preempt = _counter_total(metrics, "executor.fault.preemptions")
    print(f"faults: guard_steps={guard_steps} nonfinite={nonfinite} "
          f"rollbacks={rollbacks} preemptions={preempt}", file=file)
    scount, stotal = _hist_totals(metrics, "checkpoint.save_ms")
    rcount, rtotal = _hist_totals(metrics, "checkpoint.restore_ms")
    wfail = _counter_total(metrics, "checkpoint.write_failures")
    crc = _counter_total(metrics, "checkpoint.crc_failures")
    fb = _counter_total(metrics, "checkpoint.fallbacks")
    print(f"checkpoints: saves={saves} "
          f"(avg {stotal / max(scount, 1):.2f}ms) restores={rcount} "
          f"(avg {rtotal / max(rcount, 1):.2f}ms) write_failures={wfail} "
          f"crc_failures={crc} fallbacks={fb}", file=file)


def print_serving_summary(metrics, file=None):
    """Continuous-batching serving summary: printed only when a
    GenerationServer left serving.* metrics behind."""
    file = file if file is not None else sys.stdout
    reqs = _counter_total(metrics, "serving.requests")
    if not reqs:
        return
    toks = _counter_total(metrics, "serving.generated_tokens")
    iters = _counter_total(metrics, "serving.iterations")
    retired = _counter_total(metrics, "serving.retired")
    cancelled = _counter_total(metrics, "serving.cancelled")
    deadline = _counter_total(metrics, "serving.deadline_cancels")
    prefill = _counter_total(metrics, "serving.prefill_tokens")
    tc, tt = _hist_totals(metrics, "serving.ttft_ms")
    ic, it = _hist_totals(metrics, "serving.itl_ms")
    sc, stot = _hist_totals(metrics, "serving.step_ms")
    print(f"serving: requests={reqs} retired={retired} "
          f"cancelled={cancelled} deadline_cancels={deadline} "
          f"iterations={iters}", file=file)
    print(f"serving: generated_tokens={toks} prefill_tokens={prefill} "
          f"avg_step={stot / max(sc, 1):.2f}ms "
          f"ttft_avg={tt / max(tc, 1):.2f}ms "
          f"itl_avg={it / max(ic, 1):.2f}ms", file=file)
    ker = _counter_total(metrics, "serving.kernel.traced")
    fb = _counter_total(metrics, "serving.kernel.fallback")
    if ker or fb:
        interp = metrics.get("serving.kernel.interpret", {})
        ivals = interp.get("values", [])
        imode = ivals[0].get("value") if ivals else None
        print(f"serving: paged_kernel traced={ker} fallback={fb} "
              f"interpret={imode}", file=file)
    # request-level telemetry (ISSUE 7): queue-wait/e2e, SLO window
    # gauges, lifecycle-trace sampling, and flight-recorder activity
    qc, qt = _hist_totals(metrics, "serving.queue_wait_ms")
    ec, et = _hist_totals(metrics, "serving.e2e_ms")
    traced_reqs = _counter_total(metrics, "serving.requests_traced")
    faults = _counter_total(metrics, "serving.faults")
    dumps = _counter_total(metrics, "flight.dumps")
    windows = _counter_total(metrics, "serving.slo.windows")
    if qc or ec or windows or faults or dumps:
        print(f"serving: queue_wait_avg={qt / max(qc, 1):.2f}ms "
              f"e2e_avg={et / max(ec, 1):.2f}ms "
              f"requests_traced={traced_reqs} faults={faults} "
              f"flight_dumps={dumps}", file=file)
    # prefix cache + speculative decoding (ISSUE 10)
    ph = _counter_total(metrics, "serving.prefix.hits")
    pm = _counter_total(metrics, "serving.prefix.misses")
    if ph or pm:
        pe = _counter_total(metrics, "serving.prefix.evictions")
        pc = _counter_total(metrics, "serving.prefix.cow_copies")
        sh = metrics.get("serving.prefix.shared_blocks", {})
        svals = sh.get("values", [])
        shared_now = svals[0].get("value") if svals else 0
        print(f"serving: prefix hits={ph} misses={pm} "
              f"hit-rate={ph / max(ph + pm, 1):.1%} evictions={pe} "
              f"cow_copies={pc} shared_blocks_now={shared_now}",
              file=file)
    sp = _counter_total(metrics, "serving.spec.proposed")
    if sp:
        sa = _counter_total(metrics, "serving.spec.accepted")
        print(f"serving: spec proposed={sp} accepted={sa} "
              f"accept-rate={sa / max(sp, 1):.1%}", file=file)
    # forked generation (ISSUE 20): fork groups (submit(n=K) /
    # BeamParams) sharing the prompt's blocks, COW divergence traffic,
    # beam-lane reorders, and the guided-decoding mask counters
    gr = _counter_total(metrics, "serving.group.requests")
    if gr:
        gl = _counter_total(metrics, "serving.group.lanes")
        gf = _counter_total(metrics, "serving.group.forks")
        gc = _counter_total(metrics, "serving.group.cow_copies")
        br = _counter_total(metrics, "serving.beam.reorders")
        print(f"serving: fork-groups requests={int(gr)} "
              f"lanes={int(gl)} forks={int(gf)} cow_copies={int(gc)} "
              f"beam_reorders={int(br)}", file=file)
    gm = _counter_total(metrics, "serving.guided.masked_steps")
    gv = _counter_total(metrics, "serving.guided.violations")
    if gm or gv:
        print(f"serving: guided masked_steps={int(gm)} "
              f"violations={int(gv)}", file=file)
    # tiered KV cache (ISSUE 18): host-RAM spill-pool traffic — chains
    # that left HBM alive, came back via swap-in, and the re-prefills
    # the host tier absorbed, plus preempt/resume churn
    thb = _counter_total(metrics, "serving.kv.tier.host_blocks")
    tsp = _counter_total(metrics, "serving.kv.tier.spills")
    tsw = _counter_total(metrics, "serving.kv.tier.swap_ins")
    if thb or tsp or tsw:
        tra = _counter_total(metrics,
                             "serving.kv.tier.reprefills_avoided")
        tpr = _counter_total(metrics, "serving.kv.tier.preempts")
        tre = _counter_total(metrics, "serving.kv.tier.resumes")
        print(f"serving: kv-tier host_blocks={int(thb)} "
              f"spills={int(tsp)} swap_ins={int(tsw)} "
              f"reprefills_avoided={int(tra)} preempts={int(tpr)} "
              f"resumes={int(tre)}", file=file)
    # fleet router (ISSUE 11): routed-by-policy, shedding, failover,
    # and disaggregated handoff traffic
    routed_vals = metrics.get("serving.fleet.routed", {}).get(
        "values", [])
    # the unlabeled child is the aggregate; policy= children break it
    # down (summing every child would double-count)
    routed = sum(v.get("value", 0) for v in routed_vals
                 if not v.get("labels"))
    if routed:
        by_policy = {}
        for v in routed_vals:
            pol = v.get("labels", {}).get("policy")
            if pol:
                by_policy[pol] = by_policy.get(pol, 0) + v.get(
                    "value", 0)
        sheds = sum(v.get("value", 0) for v in metrics.get(
            "serving.fleet.sheds", {}).get("values", [])
            if not v.get("labels"))
        fo = _counter_total(metrics, "serving.fleet.failovers")
        ho = _counter_total(metrics, "serving.fleet.handoffs")
        hb = _counter_total(metrics, "serving.fleet.handoff_blocks")
        pol_s = " ".join(f"{k}={v}" for k, v in sorted(
            by_policy.items()))
        print(f"serving: fleet routed={routed} ({pol_s}) sheds={sheds} "
              f"failovers={fo} handoffs={ho} handoff_blocks={hb}",
              file=file)
    # fleet health (ISSUE 13): the self-healing loop's scoreboard —
    # what the fleet survived, not just what it routed
    hangs = _counter_total(metrics, "serving.fleet.hangs")
    resur = _counter_total(metrics, "serving.fleet.resurrections")
    loops = _counter_total(metrics, "serving.fleet.crash_loops")
    quar = _counter_total(metrics, "serving.fleet.quarantines")
    if hangs or resur or loops or quar:
        print(f"serving: fleet-health hangs={hangs} "
              f"resurrections={resur} crash_loops={loops} "
              f"quarantines={quar}", file=file)
    # fleet-wide distributed tracing (ISSUE 15): sampled contexts
    # minted, completed traces in the /trace ring, merged dumps, and
    # ring drops (a nonzero drop count means captures were partial)
    tr_req = _counter_total(metrics, "serving.fleet.trace.requests")
    tr_done = _counter_total(metrics, "serving.fleet.trace.completed")
    tr_dumps = _counter_total(metrics, "serving.fleet.trace.dumps")
    if tr_req or tr_done or tr_dumps:
        dropped = _counter_total(metrics, "tracing.dropped_events")
        print(f"serving: fleet-trace requests={tr_req} "
              f"completed={tr_done} dumps={tr_dumps} "
              f"dropped_events={dropped}", file=file)
    # fleet health signals (ISSUE 17): series volume, the alert
    # timeline (one line per rule that ever fired — the latched
    # lifecycle record), and top tenants by attributed cost
    spts = _counter_total(metrics, "serving.series.points")
    af = _counter_total(metrics, "serving.alerts.fired")
    ar = _counter_total(metrics, "serving.alerts.resolved")
    if spts or af or ar:
        sdrop = _counter_total(metrics, "serving.series.dropped_points")
        print(f"serving: signals series_points={spts} "
              f"dropped={sdrop} alerts fired={af} resolved={ar}",
              file=file)
    sig = metrics.get("signals_sample") or {}
    for a in (sig.get("alerts") or {}).get("alerts", []):
        if not a.get("fired_count"):
            continue
        res = (f" resolved_at={a['resolved_at']:.3f}s"
               if a.get("resolved_at") is not None else "")
        print(f"serving: alert[{a['name']}] state={a['state']} "
              f"fired_at={a['fired_at']:.3f}s{res} "
              f"fired_count={a['fired_count']} "
              f"series={a['rule']['series']}", file=file)
    tenant_toks = {}
    for v in metrics.get("serving.tenant.generated_tokens", {}).get(
            "values", []):
        ten = v.get("labels", {}).get("tenant")
        if ten:
            tenant_toks[ten] = tenant_toks.get(ten, 0) + v.get(
                "value", 0)
    if tenant_toks:
        tenant_reqs = {}
        for v in metrics.get("serving.tenant.requests", {}).get(
                "values", []):
            ten = v.get("labels", {}).get("tenant")
            if ten:
                tenant_reqs[ten] = tenant_reqs.get(ten, 0) + v.get(
                    "value", 0)
        top = sorted(tenant_toks.items(),
                     key=lambda kv: (-kv[1], kv[0]))[:5]
        print("serving: top-tenants "
              + " ".join(f"{k}={int(v)}tok/"
                         f"{int(tenant_reqs.get(k, 0))}req"
                         for k, v in top), file=file)
    quant = metrics.get("serving.slo.quantile_ms")
    if windows and quant:
        # key on (server, metric): two live GenerationServers publish
        # under distinct server= labels and must not be merged into one
        # last-write-wins row
        by_key = {}
        for v in quant.get("values", []):
            lbl = v.get("labels", {})
            if "metric" in lbl and "q" in lbl:
                key = (lbl.get("server", ""), lbl["metric"])
                by_key.setdefault(key, {})[lbl["q"]] = v.get("value")
        servers = {srv for srv, _ in by_key}
        for srv, m in sorted(by_key):
            qs = by_key[(srv, m)]
            tag = f"{srv}:{m}" if len(servers) > 1 else m
            print(f"serving: slo[{tag}] (last window, {windows} windows) "
                  + " ".join(f"{q}={qs[q]:.2f}ms"
                             for q in ("p50", "p90", "p99") if q in qs),
                  file=file)


# ---------------------------------------------------------------------------
# --demo: generate a sample trace + metrics dump from a tiny cached loop
# ---------------------------------------------------------------------------

def run_demo(out_dir):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers, profiler
    from paddle_tpu.observability.metrics import global_registry

    os.makedirs(out_dir, exist_ok=True)
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square_error_cost(layers.fc(x, size=8), y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.reset_stats()

    trace_base = os.path.join(out_dir, "trace_sample")
    rng = np.random.RandomState(0)
    with profiler.profiler(state="CPU", sorted_key="total",
                           profile_path=trace_base):
        for _ in range(3):      # 1 compile + 2 jit-cache hits
            with profiler.record_event("demo_step"):
                exe.run(feed={"x": rng.randn(8, 4).astype(np.float32),
                              "y": rng.randn(8, 1).astype(np.float32)},
                        fetch_list=[loss])

    # async + bucketed demo loop: a second tiny program driven through
    # run_pipelined with a FeedBucketer, so executor.async.* and
    # executor.bucket.* series land in the sample dump
    from paddle_tpu.core import framework
    from paddle_tpu.core.bucketing import FeedBucketer
    amain, astart = framework.Program(), framework.Program()
    with framework.program_guard(amain, astart):
        ax = layers.data("x", shape=[4], dtype="float32")
        ay = layers.data("y", shape=[1], dtype="float32")
        am = layers.data("batch_mask", shape=[1], dtype="float32")
        per = layers.square_error_cost(layers.fc(ax, size=8), ay)
        aloss = layers.reduce_sum(per * am) / layers.reduce_sum(am)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(aloss)
    ascope = fluid.Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(ascope):
        exe2.run(astart)
        bucketer = FeedBucketer(mask_name="batch_mask")
        feeds = [{"x": rng.randn(n, 4).astype(np.float32),
                  "y": rng.randn(n, 1).astype(np.float32)}
                 for n in (3, 5, 6, 7)]       # buckets {4, 8}: 2 compiles
        for _ in exe2.run_pipelined(amain, feeds, fetch_list=[aloss],
                                    bucketer=bucketer, window=2):
            pass

    # guarded-recovery demo loop: chaos poisons one grad, the sentinel
    # trips, GuardedTrainer rolls back to its checkpoint and replays —
    # so executor.fault.* / checkpoint.* series land in the committed
    # sample dump (and the fault summary line below has data)
    import tempfile
    from paddle_tpu.robustness import ChaosInjector, GuardedTrainer
    gmain, gstart = framework.Program(), framework.Program()
    with framework.program_guard(gmain, gstart):
        gx = layers.data("x", shape=[4], dtype="float32")
        gy = layers.data("y", shape=[1], dtype="float32")
        gloss = layers.mean(layers.square_error_cost(
            layers.fc(gx, size=8), gy))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(gloss)
    gscope = fluid.Scope()
    exe3 = fluid.Executor(fluid.CPUPlace(), guard=True)
    with fluid.scope_guard(gscope):
        exe3.run(gstart)
    gfeeds = [{"x": rng.randn(8, 4).astype(np.float32),
               "y": rng.randn(8, 1).astype(np.float32)} for _ in range(6)]
    with tempfile.TemporaryDirectory() as ckdir:
        # fixed-name subdir: the CheckpointManager gauge label is
        # basename(root), and a random tempdir name would put a
        # different label into metrics_sample.json on every run
        trainer = GuardedTrainer(
            exe3, gmain, fetch_list=[gloss], scope=gscope,
            checkpoint_dir=os.path.join(ckdir, "demo_ckpts"),
            checkpoint_every=2,
            chaos=ChaosInjector().poison_grad_at(3), window=2)
        guard_result = trainer.train(gfeeds)

    # continuous-batching serving demo: a short mixed-length greedy run
    # through the paged-KV GenerationServer (manual pump, no threads) so
    # serving.* series land in the committed sample dump — one request
    # cancels mid-stream via the deterministic chaos path. The chaos
    # clock ticks 20 ms per iteration and the SLO window is 100 ms, so
    # request-level telemetry (queue-wait/e2e histograms, SLO quantile
    # gauges, completed windows) lands in the sample too (ISSUE 7).
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                    SpecDecodeConfig)
    scfg = gpt.gpt_tiny()
    smain, sstart = framework.Program(), framework.Program()
    smain.random_seed = sstart.random_seed = 7
    with framework.program_guard(smain, sstart):
        gpt.build_lm_net(scfg, seq_len=8)
    sscope = fluid.Scope()
    exe4 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(sscope):
        exe4.run(sstart)
        sparams = gpt.load_params(sscope, scfg)
    schaos = ChaosInjector().cancel_request_at(4, index=0)
    for sit in range(1, 90):
        schaos.advance_clock_at(sit, ms=20)
    # prefix cache + speculative decoding on (ISSUE 10): the demo
    # drives a shared-prefix stream below so serving.prefix.* and
    # serving.spec.* series land in the committed sample (the draft is
    # the target itself — a perfect-acceptance sample)
    # num_slots=4: the fork-group wave below needs room for its n=4
    # lanes (groups admit atomically)
    server = GenerationServer(
        GPTServingModel(sparams, scfg), num_slots=4, block_size=8,
        max_context=64, chunk=4, start=False, chaos=schaos,
        slo_window_s=0.1, prefix_cache=True, host_kv_blocks=16,
        spec=SpecDecodeConfig(GPTServingModel(sparams, scfg), k=3))
    victim = server.submit(np.arange(3, 15, dtype=np.int32),
                           max_new_tokens=30)
    survivors = [server.submit([5 + i, 9, 11], max_new_tokens=4 + i)
                 for i in range(3)]
    server.run_until_idle()
    assert victim.cancelled() or victim.exception(timeout=1) is not None
    for f in survivors:
        f.result(timeout=5)
    # shared-prefix wave: the repeat matches both chunks (prefix hits)
    # and, being fully covered, exercises the copy-on-write path too
    shared_p = np.arange(3, 19, dtype=np.int32)     # 2 full blocks
    w1 = server.submit(shared_p, max_new_tokens=6)
    server.run_until_idle()
    # tiered KV (ISSUE 18): spill the now-idle shared chain to the
    # host pool before the repeat — the second wave's prefix hit
    # re-adopts both blocks via swap-in, so serving.kv.tier.* series
    # land in the sample with real spills/swap-ins behind them
    schaos.spill_chain_at(server._sched.iteration + 1, 2)
    w2 = server.submit(shared_p, max_new_tokens=6)
    server.run_until_idle()
    for f in (w1, w2):
        f.result(timeout=5)
    assert server.get_stats()["kv_tier"]["swap_ins"] >= 2

    # forked generation (ISSUE 20): an n=4 sampled fork group, a paged
    # beam request, and a guided regex decode ride the SAME server —
    # and the same compiled fused-step signature (mask/rng/ctl are
    # data, never shape) — so serving.group.* / serving.beam.reorders /
    # serving.guided.* series land in the committed sample with real
    # forks, COW copies, and masked steps behind them
    from paddle_tpu.serving import (BeamParams, RegexConstraint,
                                    SamplingParams)
    gfut = server.submit(np.arange(3, 19, dtype=np.int32),
                         max_new_tokens=5, n=4,
                         sampling=SamplingParams(seed=7))
    server.run_until_idle()
    assert len(gfut.result(timeout=5).lanes) == 4
    bfut = server.submit(np.arange(3, 11, dtype=np.int32),
                         max_new_tokens=5, eos_id=2,
                         beam=BeamParams(2))
    server.run_until_idle()
    assert len(bfut.result(timeout=5).hypotheses) == 2
    digits = {i: str(i - 3) for i in range(3, 13)}
    rcon = RegexConstraint("[0-9]+", [digits.get(i, chr(0x4E00 + i))
                                      for i in range(scfg.vocab_size)])
    qfut = server.submit(np.array([5, 9, 11], np.int32),
                         max_new_tokens=6, eos_id=1, guided=rcon)
    server.run_until_idle()
    assert all(3 <= t <= 12 for t in qfut.result(timeout=5).token_ids
               if t != 1)
    assert server.get_stats()["guided.violations"] == 0

    # fleet router demo (ISSUE 11): a 2-replica routed stream — the
    # second wave repeats the first wave's prompts so prefix-affinity
    # routing fires (serving.fleet.routed{policy=affinity} next to the
    # least_loaded cold routes in the committed sample)
    from paddle_tpu.robustness import ChaosInjector, SupervisorConfig
    from paddle_tpu.serving import FleetRouter

    def _spawn(_index):
        return GenerationServer(GPTServingModel(sparams, scfg),
                                num_slots=2, block_size=8,
                                max_context=64, chunk=4, start=False,
                                prefix_cache=True)

    freps = [_spawn(i) for i in range(2)]
    # self-healing demo (ISSUE 13): a chaos kill mid-stream, caught by
    # the supervisor — the replica resurrects (probe + prefix re-warm)
    # and the fleet-health counters land in the committed sample.
    # Fleet tracing on (ISSUE 15): every request rides one trace id
    # across the kill's failover, and the merged dump (fleet track +
    # both replica captures incl. the victim's death snapshot) is
    # produced so serving.fleet.trace.* series land in the sample too
    fchaos = ChaosInjector().kill_replica_at(3, 0)
    # fleet health signals (ISSUE 17): an alert storm rides the chaos
    # kill — "replica-down" (live replicas < 2) fires at the kill and
    # resolves when the supervisor's resurrection heals the fleet, so
    # serving.alerts.{fired,resolved,active} land in the committed
    # sample with a real firing→resolved lifecycle behind them; the
    # loose admission targets feed the slo.window_burn series the
    # "slo-burn" rule watches (quiet here — no shedding in the demo)
    from paddle_tpu.observability.alerts import AlertRule
    from paddle_tpu.serving.router import AdmissionPolicy
    frouter = FleetRouter(freps, start=False, chaos=fchaos,
                          spawn_fn=_spawn, trace=True, name="sig-demo",
                          signals_every=1,
                          admission=AdmissionPolicy(
                              {"ttft_ms": {"p99": 1e9}},
                              burn_threshold=1e9),
                          alert_rules=[
                              AlertRule.threshold_rule(
                                  "replica-down",
                                  "serving.fleet.replicas{router=sig-demo}",
                                  2.0, op="<"),
                              AlertRule.burn_rate(
                                  "slo-burn",
                                  "slo.window_burn.ttft_ms.p99",
                                  1.0, fast_s=0.5, slow_s=2.0)],
                          supervisor=SupervisorConfig(
                              backoff_heartbeats=1, warm_chains=2))
    fprompts = [np.arange(3 + i, 19 + i, dtype=np.int32)
                for i in range(2)]
    # per-tenant cost attribution: tagged and anonymous traffic mixed,
    # so serving.tenant.* series (incl. the <anon> row) land too
    ftenants = ("acme", "globex", None, "acme")
    waves = [frouter.submit(p, max_new_tokens=4, tenant=t)
             for p, t in zip(fprompts, ftenants)]
    frouter.run_until_idle()
    waves += [frouter.submit(p, max_new_tokens=4, tenant=t)
              for p, t in zip(fprompts, ftenants[2:])]
    frouter.run_until_idle()
    for f in waves:
        f.result(timeout=5)
    # drive calm waves until the supervisor's resurrection lands AND a
    # post-heal signal sample latches replica-down to resolved — the
    # heartbeat rides wall clock, so the number of waves needed varies
    # with machine load (outcome is deterministic, the count is not)
    for _ in range(40):
        down = next(a for a in frouter.dump_signals()["alerts"]["alerts"]
                    if a["name"] == "replica-down")
        if (frouter.get_stats()["live_replicas"] == 2
                and down["fired_count"] >= 1
                and down["state"] == "resolved"):
            break
        calm = [frouter.submit(np.arange(5 + i, 13 + i, dtype=np.int32),
                               max_new_tokens=2) for i in range(2)]
        frouter.run_until_idle()
        for f in calm:
            f.result(timeout=5)
        time.sleep(0.02)
    ftrace = frouter.dump_trace()
    assert len(ftrace["otherData"]["sources"]) >= 3     # fleet + 2 reps
    fleet_stats = frouter.get_stats()
    assert fleet_stats["live_replicas"] == 2    # healed after the kill
    signals_sample = frouter.dump_signals()
    down = next(a for a in signals_sample["alerts"]["alerts"]
                if a["name"] == "replica-down")
    assert down["fired_count"] >= 1 and down["state"] == "resolved"
    frouter.close()

    metrics_path = os.path.join(out_dir, "metrics_sample.json")
    dump = global_registry().to_dict()
    dump["executor_stats"] = exe.get_stats()
    dump["async_stats"] = exe2.get_stats()["async"]
    dump["bucket_stats"] = bucketer.get_stats()
    dump["fault_stats"] = dict(exe3.get_stats()["fault"],
                               rollbacks=guard_result.rollbacks,
                               steps=guard_result.steps)
    dump["serving_stats"] = server.get_stats()
    dump["fleet_stats"] = fleet_stats
    dump["signals_sample"] = signals_sample
    with open(metrics_path, "w") as f:
        # single line, keys sorted: two dumps diff line against line
        json.dump(dump, f, sort_keys=True)
        f.write("\n")
    return trace_base + ".timeline.json", metrics_path


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="sorted-key table + cache summary from a trace/metrics "
                    "dump")
    ap.add_argument("trace", nargs="?", help="Chrome trace JSON (or legacy "
                    "profiler records)")
    ap.add_argument("--metrics", help="metrics dump JSON "
                    "(MetricsRegistry.to_json())")
    ap.add_argument("--sorted-key", default="total",
                    choices=SORT_KEYS, help="table sort column")
    ap.add_argument("--limit", type=int, default=30,
                    help="max table rows")
    ap.add_argument("--demo", action="store_true",
                    help="generate sample trace+metrics from a tiny cached "
                    "loop, then report on them")
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_obs",
                    help="--demo output directory")
    args = ap.parse_args(argv)

    trace_path, metrics_path = args.trace, args.metrics
    if args.demo:
        trace_path, metrics_path = run_demo(args.out_dir)
        print(f"demo artifacts: {trace_path} {metrics_path}")
    if not trace_path and not metrics_path:
        ap.error("nothing to report: pass a trace file, --metrics, "
                 "or --demo")
    if trace_path:
        events = load_trace_events(trace_path)
        print_event_table(events, sorted_key=args.sorted_key,
                          limit=args.limit)
    if metrics_path:
        metrics = load_metrics(metrics_path)
        print_cache_summary(metrics)
        print_fault_summary(metrics)
        print_serving_summary(metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
