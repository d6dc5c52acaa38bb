"""Request-level serving telemetry (ISSUE 7): lifecycle span trees,
SLO digests, the fault flight recorder, and the /metrics endpoint.

The acceptance scenario lives in
test_acceptance_mixed_stream_cancel_and_poison: a mixed-length
staggered stream with a mid-stream cancel and a chaos-poisoned NaN
must produce (1) a Perfetto trace with complete per-request span trees
(queue -> prefill.chunk x N -> decode -> retire, plus one cancelled
tree), (2) get_stats()["slo"] TTFT/ITL quantiles within the sketch's
rank-error bound of exact offline quantiles, and (3) a flight-recorder
JSON whose LAST entry identifies the poisoned iteration.

Timing is exact everywhere: the chaos clock advances a known amount per
iteration, so TTFT/ITL values are deterministic multiples of the
advance — no sleeps, no tolerance-hiding.
"""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.observability.serving_telemetry import (
    FlightRecorder, ServingTelemetry, trace_request_mode)
from paddle_tpu.observability.tracing import TraceRecorder, get_recorder
from paddle_tpu.robustness import ChaosInjector
from paddle_tpu.robustness.guard import NonFiniteError
from paddle_tpu.serving import GenerationServer, GPTServingModel

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, scope, gpt.load_params(scope, cfg)


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _ticking_chaos(ms_of_iteration, n=200):
    """Chaos injector whose clock advances ms_of_iteration(it) ms at the
    START of each iteration — every latency becomes an exact sum of
    per-iteration advances."""
    chaos = ChaosInjector()
    for it in range(1, n):
        chaos.advance_clock_at(it, ms=ms_of_iteration(it))
    return chaos


# ---------------------------------------------------------------------------
# the ISSUE acceptance scenario
# ---------------------------------------------------------------------------

def test_acceptance_mixed_stream_cancel_and_poison(tiny_gpt, tmp_path):
    cfg, _scope, params = tiny_gpt
    # varying per-iteration clock advance -> non-trivial exact ITL/TTFT
    chaos = (_ticking_chaos(lambda it: 5.0 + (it % 7))
             .cancel_request_at(4, index=0)
             .poison_serving_at(14))
    tel = ServingTelemetry(clock=chaos.serving_clock, window_s=1e9,
                           flight_dir=str(tmp_path), flight_capacity=6)
    srv = _server(params, cfg, chaos=chaos, telemetry=tel)

    # exact offline record: (rid, clock at each token), via callbacks
    token_times = {}

    def stream(rid, _tok):
        token_times.setdefault(rid, []).append(chaos.serving_clock())

    submit_clock = {}

    def sub(*args, **kw):
        fut = srv.submit(*args, **kw)
        submit_clock[fut.request_id] = chaos.serving_clock()
        return fut

    rec = get_recorder()
    rec.start()
    try:
        victim = sub(np.arange(3, 15, dtype=np.int32),
                     max_new_tokens=30, stream=stream)
        staggered = [sub([5 + i] * (3 + 4 * i),
                         max_new_tokens=4 + 2 * i, stream=stream)
                     for i in range(2)]
        srv.step()
        srv.step()
        late = sub([9, 10, 11], max_new_tokens=20, stream=stream)
        with pytest.raises(NonFiniteError) as ei:
            srv.run_until_idle()
    finally:
        rec.stop()
    events = rec.events()
    rec.clear()

    # -- (1) complete per-request span trees -----------------------------
    by_rid = {}
    for e in events:
        if e.get("cat") != "serving.request":
            continue
        rid = e["args"]["rid"]
        by_rid.setdefault(rid, []).append(e)
    assert set(by_rid) == {f.request_id for f in
                           [victim, *staggered, late]}
    retired_rids = [f.request_id for f in staggered if f.done()
                    and not f.cancelled() and f.exception() is None]
    assert retired_rids, "at least one request must retire cleanly"
    for rid in retired_rids:
        names = [e["name"] for e in by_rid[rid]]
        root = next(e for e in by_rid[rid]
                    if e["name"] == f"request {rid}")
        assert root["args"]["outcome"] == "retire"
        assert root["args"]["finish_reason"] == "length"
        assert "queue" in names and "decode" in names
        assert "retire" in names
        chunks = [e for e in by_rid[rid] if e["name"] == "prefill.chunk"]
        prompt_len = root["args"]["prompt_len"]
        assert sum(c["args"]["tokens"] for c in chunks) == prompt_len
        assert len(chunks) == -(-prompt_len // 4)       # ceil(P/chunk)
        # correlation ids: chunk iterations strictly increase and the
        # span tree nests inside the root on one per-slot track
        its = [c["args"]["iteration"] for c in chunks]
        assert its == sorted(its)
        track = {e["tid"] for e in by_rid[rid]}
        assert track == {f"serving slot {root['args']['slot']}"}
        t0, t1 = root["ts"], root["ts"] + root["dur"]
        for e in by_rid[rid]:
            if e["ph"] == "X":
                assert e["ts"] >= t0 - 1e-3
                assert e["ts"] + e["dur"] <= t1 + 1e-3
    # one CANCELLED tree: the chaos mid-stream cancel at iteration 4
    vnames = [e["name"] for e in by_rid[victim.request_id]]
    vroot = next(e for e in by_rid[victim.request_id]
                 if e["name"].startswith("request"))
    assert vroot["args"]["outcome"] == "cancel"
    assert "cancel" in vnames
    assert victim.done() and victim.exception() is not None

    # -- (2) SLO digests vs exact offline quantiles ----------------------
    # ground truth is telemetry-independent: the stream callbacks
    # recorded every token's injected-clock stamp, and submit_clock the
    # stamp at submit — both exact, no sleeps anywhere
    slo = srv.get_stats()["slo"]["cumulative"]
    exact_ttft, exact_itl = [], []
    for fut in (victim, *staggered, late):
        times = token_times.get(fut.request_id)
        if not times:
            continue
        exact_ttft.append((times[0] - submit_clock[fut.request_id]) * 1e3)
        exact_itl.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
    assert slo["ttft_ms"]["count"] == len(exact_ttft)
    assert slo["itl_ms"]["count"] == len(exact_itl)
    tel_obj = srv.telemetry
    for metric, exact in (("ttft_ms", exact_ttft), ("itl_ms", exact_itl)):
        srt = np.sort(exact)
        d = tel_obj.slo.digest(metric)
        for q in (0.5, 0.99):
            est = d.quantile(q)
            lo = np.searchsorted(srt, est - 1e-6) / len(srt)
            hi = np.searchsorted(srt, est + 1e-6, side="right") / len(srt)
            bound = 2.0 / d.compression
            assert lo - bound <= q <= hi + bound, (metric, q, est)

    # -- (3) flight recorder identifies the poisoned iteration ----------
    dump_path = ei.value.flight_dump
    assert dump_path in srv.get_stats()["slo"]["flight"]["dumps"]
    dump = json.loads(open(dump_path).read().strip())
    assert dump["schema"] == "paddle_tpu.flight/1"
    assert dump["reason"] == "non_finite_logits"
    assert dump["step"] == 14 and ei.value.step == 14
    last = dump["entries"][-1]
    assert last["step"] == 14 and last["kind"] == "iteration"
    assert last["fault"]["kind"] == "non_finite_logits"
    assert last["fault"]["detail"]["bad_slots"]
    # ring capacity bounds the history, newest entry survives
    assert len(dump["entries"]) <= 6
    # the fault closed the server and failed every outstanding future
    assert srv.get_stats()["engine_fault"] is not None
    for f in (late, *staggered):
        assert f.done()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit([1, 2], max_new_tokens=2)
    assert chaos.fired["serving_poison"] == 1


# ---------------------------------------------------------------------------
# SLO windows, gauges, burn rates
# ---------------------------------------------------------------------------

def test_slo_windows_publish_quantile_gauges(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    chaos = _ticking_chaos(lambda it: 10.0)     # 10 ms per iteration
    tel = ServingTelemetry(clock=chaos.serving_clock, window_s=0.05)
    srv = _server(params, cfg, chaos=chaos, telemetry=tel)
    reg = global_registry()
    windows0 = reg.counter("serving.slo.windows").value()
    futs = [srv.submit([5 + i, 9, 11], max_new_tokens=6)
            for i in range(4)]
    srv.run_until_idle()
    for f in futs:
        f.result(timeout=5)
    slo = srv.get_stats()["slo"]
    assert slo["windows_completed"] >= 2
    assert reg.counter("serving.slo.windows").value() - windows0 == \
        slo["windows_completed"]
    last = slo["last_window"]
    assert last is not None and last["tokens"] >= 0
    assert last["elapsed_s"] >= 0.05
    # quantile gauges landed with (metric, q, server) labels — the
    # server label keeps concurrent servers from clobbering each other
    sid = slo["server"]
    labels = [lbl for lbl, _c in
              reg.gauge("serving.slo.quantile_ms").series()
              if lbl.get("server") == sid]
    assert labels and all(l["server"] == sid for l in labels)
    assert any(l["metric"] == "ttft" for l in labels)
    assert {l["q"] for l in labels} >= {"p50", "p90", "p99"}
    tps = [c.value() for lbl, c in
           reg.gauge("serving.slo.tokens_per_s").series()
           if lbl.get("server") == sid]
    assert len(tps) == 1 and tps[0] >= 0
    # cumulative throughput: 24 tokens over the total clock advance
    assert slo["cumulative"]["tokens"] == 24
    # close() retires this server's gauge series (no stale quantiles
    # from dead servers in a long-lived process)
    srv.close()
    assert not [lbl for lbl, _c in
                reg.gauge("serving.slo.quantile_ms").series()
                if lbl.get("server") == sid]
    assert not [lbl for lbl, _c in
                reg.gauge("serving.slo.tokens_per_s").series()
                if lbl.get("server") == sid]


def test_two_servers_do_not_alias_slo_stats(tiny_gpt):
    """Two telemetry-enabled servers in one process (a fleet of
    in-process replicas does exactly this) must keep distinct window
    gauges and per-server traced counts — the regression is one server reporting the other's
    requests."""
    cfg, _scope, params = tiny_gpt
    servers, chaoses = [], []
    for _ in range(2):
        chaos = _ticking_chaos(lambda it: 10.0)
        chaoses.append(chaos)
        servers.append(_server(
            params, cfg, chaos=chaos,
            telemetry=ServingTelemetry(clock=chaos.serving_clock,
                                       window_s=0.02)))
    rec = get_recorder()
    rec.start()
    try:
        futs = []
        for i, srv in enumerate(servers):
            futs.append(srv.submit([5 + i, 9], max_new_tokens=3 + i))
        for srv in servers:
            srv.run_until_idle()
        for f in futs:
            f.result(timeout=5)
    finally:
        rec.stop()
    rec.clear()
    slos = [srv.get_stats()["slo"] for srv in servers]
    assert slos[0]["server"] != slos[1]["server"]
    # per-server views, not process aggregates
    assert slos[0]["cumulative"]["tokens"] == 3
    assert slos[1]["cumulative"]["tokens"] == 4
    assert [s["trace_requests"]["traced"] for s in slos] == [1, 1]
    reg = global_registry()
    for slo in slos:
        own = [lbl for lbl, _c in
               reg.gauge("serving.slo.quantile_ms").series()
               if lbl.get("server") == slo["server"]]
        assert own, slo["server"]
    for srv in servers:
        srv.close()


def test_check_slo_burn_rates(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    tel = ServingTelemetry(clock=None, window_s=1e9)
    # synthetic, exact: 100 TTFT samples, 10 of them over 100 ms
    for i in range(90):
        tel.slo.observe("ttft_ms", 50.0)
    for i in range(10):
        tel.slo.observe("ttft_ms", 200.0)
    out = tel.check_slo({"ttft_ms": {"p50": 60.0, "p99": 100.0}})
    assert out["ok"] is False
    by_q = {c["quantile"]: c for c in out["checks"]}
    assert by_q["p50"]["met"] is True
    assert by_q["p50"]["observed_ms"] == pytest.approx(50.0)
    # p99 violated: 10% of mass over a 1% budget -> burn rate 10x
    assert by_q["p99"]["met"] is False
    assert by_q["p99"]["frac_over"] == pytest.approx(0.1, abs=0.02)
    assert by_q["p99"]["burn_rate"] == pytest.approx(10.0, abs=2.0)
    # unknown metric / malformed quantile raise instead of guessing
    with pytest.raises(ValueError):
        tel.check_slo({"nope_ms": {"p99": 1.0}})
    with pytest.raises(ValueError):
        tel.check_slo({"ttft_ms": {"q99": 1.0}})
    # engine surface: telemetry-less server refuses
    srv = _server(params, cfg, telemetry=False)
    with pytest.raises(RuntimeError, match="telemetry"):
        srv.check_slo({"ttft_ms": {"p99": 1.0}})
    assert srv.get_stats()["slo"] is None


# ---------------------------------------------------------------------------
# sampling knob
# ---------------------------------------------------------------------------

def test_trace_request_mode_parsing():
    assert trace_request_mode("all") == ("all", 1.0)
    assert trace_request_mode("off") == ("off", 0.0)
    assert trace_request_mode("sampled:0.25") == ("sampled", 0.25)
    assert trace_request_mode(None)[0] in ("all", "off", "sampled")
    for bad in ("sampled:2", "sampled:x", "sometimes"):
        with pytest.raises(ValueError):
            trace_request_mode(bad)


def test_trace_request_mode_env_typo_is_not_fatal(monkeypatch):
    # an operator typo in the env var must degrade with a warning, not
    # take down GenerationServer construction over a tracing knob
    monkeypatch.setenv("PADDLE_TPU_TRACE_REQUESTS", "sample:0.1")
    with pytest.warns(RuntimeWarning, match="PADDLE_TPU_TRACE_REQUESTS"):
        assert trace_request_mode() == ("all", 1.0)
    with pytest.warns(RuntimeWarning):
        tel = ServingTelemetry(window_s=1e9)   # constructor survives too
    assert tel.mode == "all"


def test_sampling_is_deterministic_and_off_suppresses_trees(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    tel = ServingTelemetry(sample="sampled:0.5", window_s=1e9)
    picks = [tel.sampled(rid) for rid in range(200)]
    assert picks == [tel.sampled(rid) for rid in range(200)]
    assert 40 < sum(picks) < 160        # hash spreads, not all-or-nothing
    # off: engine iteration spans still record, request trees do not
    srv = _server(params, cfg,
                  telemetry=ServingTelemetry(sample="off", window_s=1e9))
    rec = get_recorder()
    rec.start()
    try:
        srv.submit([5, 6, 7], max_new_tokens=3)
        srv.run_until_idle()
    finally:
        rec.stop()
    events = rec.events()
    rec.clear()
    assert any(e["name"] == "serving.iteration" for e in events)
    assert not any(e.get("cat") == "serving.request" for e in events)
    # SLO digests still fill while tracing is sampled out
    assert srv.get_stats()["slo"]["cumulative"]["ttft_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# trace-recorder ring bound (satellite)
# ---------------------------------------------------------------------------

def test_trace_recorder_ring_drops_oldest_and_counts():
    reg = global_registry()
    base = reg.counter("tracing.dropped_events").value()
    rec = TraceRecorder(max_events=10)
    rec.start()
    for i in range(25):
        rec.instant(f"e{i}")
    rec.stop()
    events = rec.events()
    assert len(events) == 10
    assert [e["name"] for e in events] == [f"e{i}" for i in range(15, 25)]
    assert rec.dropped == 15
    assert reg.counter("tracing.dropped_events").value() == base + 15
    chrome = rec.to_chrome()
    assert chrome["otherData"]["dropped_events"] == 15
    assert chrome["otherData"]["max_events"] == 10
    # start() resets the ring and the drop count
    rec.start()
    assert rec.dropped == 0 and rec.events() == []
    rec.stop()


def test_trace_recorder_env_buffer_knob(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE_BUFFER", "7")
    rec = TraceRecorder()
    assert rec.max_events == 7
    # nonsensical values warn (not silently shrink-to-1 / revert) and
    # keep the default
    for bad in ("not-a-number", "0", "-5"):
        monkeypatch.setenv("PADDLE_TPU_TRACE_BUFFER", bad)
        with pytest.warns(RuntimeWarning, match="PADDLE_TPU_TRACE_BUFFER"):
            assert TraceRecorder().max_events == 200_000


# ---------------------------------------------------------------------------
# flight recorder (unit level)
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_dump_and_annotation(tmp_path):
    fr = FlightRecorder(capacity=4, out_dir=str(tmp_path))
    for i in range(9):
        fr.record(i, kind="iteration", lanes=[0, 1], numpy_val=np.int32(i))
    assert len(fr) == 4
    fr.annotate_last(fault={"kind": "test"})
    path = fr.dump("test_reason", extra={"arr": np.arange(3)})
    assert path.endswith("flight-00000008.json")
    d = json.loads(open(path).read())
    assert d["reason"] == "test_reason" and d["step"] == 8
    assert d["recorded"] == 9 and d["capacity"] == 4
    assert [e["step"] for e in d["entries"]] == [5, 6, 7, 8]
    assert d["entries"][-1]["fault"] == {"kind": "test"}
    assert d["entries"][0]["numpy_val"] == 5       # numpy -> json ok
    assert d["extra"]["arr"] == [0, 1, 2]
    assert fr.dump_paths == [path]


# ---------------------------------------------------------------------------
# deadline storm -> flight dump
# ---------------------------------------------------------------------------

def test_deadline_storm_dumps_flight_recorder(tiny_gpt, tmp_path):
    cfg, _scope, params = tiny_gpt
    chaos = ChaosInjector()
    chaos.advance_clock_at(3, ms=10000)     # the storm: clock jumps 10s
    for it in (1, 2, 4, 5, 6, 7, 8):
        chaos.advance_clock_at(it, ms=1)
    tel = ServingTelemetry(clock=chaos.serving_clock, window_s=1e9,
                           flight_dir=str(tmp_path), deadline_storm=3)
    srv = _server(params, cfg, num_slots=2, chaos=chaos, telemetry=tel)
    reg = global_registry()
    faults0 = reg.counter("serving.faults").value()
    # 2 active + 2 queued, all with deadlines inside the jump
    futs = [srv.submit([5 + i, 9], max_new_tokens=20, deadline_ms=2000)
            for i in range(4)]
    srv.run_until_idle()
    failed = [f for f in futs if f.exception(timeout=1) is not None]
    assert len(failed) == 4
    assert srv.get_stats()["deadline_cancels"] == 4
    dumps = tel.flight.dump_paths
    assert len(dumps) == 1, "storm latched: one dump per burst"
    d = json.loads(open(dumps[0]).read())
    assert d["reason"] == "deadline_storm"
    assert d["extra"]["deadline_cancels"] >= 3
    assert reg.counter("serving.faults").value() == faults0 + 1


# ---------------------------------------------------------------------------
# GuardedTrainer flight dump (chaos-injected NaN stream)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_trainer_flight_dump_on_nan_rollback(tmp_path):
    from paddle_tpu import layers
    from paddle_tpu.robustness import GuardedTrainer

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(
            layers.fc(x, size=8), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace(), guard=True)
    with scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(8, 4).astype(np.float32),
              "y": rng.randn(8, 1).astype(np.float32)} for _ in range(6)]
    ckdir = str(tmp_path / "ck")
    trainer = GuardedTrainer(
        exe, main, fetch_list=[loss], scope=scope, checkpoint_dir=ckdir,
        checkpoint_every=2, window=2,
        chaos=ChaosInjector().poison_grad_at(3))
    res = trainer.train(feeds)
    assert res.steps == 6 and res.rollbacks == 1
    assert len(res.flight_dumps) == 1
    d = json.loads(open(res.flight_dumps[0]).read())
    assert d["reason"] == "nonfinite_rollback"
    assert d["step"] == 3
    last = d["entries"][-1]
    assert last["kind"] == "fault" and last["step"] == 3
    assert last["var"] and last["segment_base"] == 2
    # the ring shows the dispatch/resolve interleave leading to it
    kinds = {e["kind"] for e in d["entries"]}
    assert {"dispatch", "resolve", "fault"} <= kinds
    # dump landed inside the checkpoint root (next to the evidence)
    assert res.flight_dumps[0].startswith(ckdir)
    # flight=False disables cleanly
    t2 = GuardedTrainer(exe, main, fetch_list=[loss], scope=scope,
                        checkpoint_dir=str(tmp_path / "ck2"),
                        flight=False)
    assert t2.flight is None


# ---------------------------------------------------------------------------
# telemetry endpoint (engine + executor mounts)
# ---------------------------------------------------------------------------

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def test_engine_serve_metrics_endpoints(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg)
    exp = srv.serve_metrics(port=0)
    assert srv.serve_metrics() is exp          # idempotent mount
    assert srv.serve_metrics(port=exp.port) is exp   # same port: fine
    assert exp.host == "127.0.0.1"             # loopback by default
    # asking for a DIFFERENT explicit port/host than the live mount
    # must raise, not silently return the old endpoint
    with pytest.raises(ValueError, match="already mounted"):
        srv.serve_metrics(port=exp.port + 1)
    with pytest.raises(ValueError, match="already mounted"):
        srv.serve_metrics(host="0.0.0.0")
    fut = srv.submit([5, 6, 7], max_new_tokens=4)
    srv.run_until_idle()
    fut.result(timeout=5)
    code, prom = _get(f"{exp.url}/metrics")
    assert code == 200
    assert "# TYPE serving_requests counter" in prom
    assert "serving_generated_tokens" in prom
    code, health = _get(f"{exp.url}/healthz")
    h = json.loads(health)
    assert code == 200 and h["status"] == "ok" and h["pending"] == 0
    code, slo = _get(f"{exp.url}/slo")
    s = json.loads(slo)
    assert code == 200
    assert s["cumulative"]["ttft_ms"]["count"] == 1
    try:
        _get(f"{exp.url}/nope")
        assert False, "404 expected"
    except urllib.error.HTTPError as e:
        assert e.code == 404
        assert "/metrics" in e.read().decode()
    # scrape accounting landed (labeled by path + aggregate)
    reg = global_registry()
    series = {tuple(sorted(lbl.items())): c.value()
              for lbl, c in reg.counter("exporter.requests").series()}
    assert series[(("code", "200"), ("path", "/metrics"))] >= 1
    srv.close()
    assert srv._exporter is None               # endpoint died with it


def test_executor_serve_metrics_mount():
    from paddle_tpu import layers
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        out = layers.fc(x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[out])
        exp = exe.serve_metrics(port=0)
        assert exe.serve_metrics() is exp      # idempotent mount
        with pytest.raises(ValueError, match="already mounted"):
            exe.serve_metrics(port=exp.port + 1)
        code, health = _get(f"{exp.url}/healthz")
        h = json.loads(health)
        assert code == 200 and h["steps"] >= 1
        assert h["executor"] == exe._exe_id
        code, prom = _get(f"{exp.url}/metrics")
        assert "executor_steps" in prom
        exe.close()
    assert exe._telemetry_server is None


# ---------------------------------------------------------------------------
# telemetry-off parity
# ---------------------------------------------------------------------------

def test_telemetry_off_is_bitwise_equal_and_hookless(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    prompt = np.array([5, 9, 11, 2], np.int32)
    ids = {}
    for mode in (True, False):
        srv = _server(params, cfg, telemetry=mode)
        fut = srv.submit(prompt, max_new_tokens=8)
        srv.run_until_idle()
        ids[mode] = list(fut.result(timeout=5).token_ids)
        st = srv.get_stats()
        assert st["telemetry_enabled"] is mode
        assert st["fused_step_signatures"] == 1
    assert ids[True] == ids[False]


def test_prefill_chunk_spans_cover_prompt_exactly(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg, chunk=4,
                  telemetry=ServingTelemetry(window_s=1e9))
    rec = get_recorder()
    rec.start()
    try:
        fut = srv.submit(np.arange(2, 13, dtype=np.int32),  # 11 tokens
                         max_new_tokens=2)
        srv.run_until_idle()
    finally:
        rec.stop()
    fut.result(timeout=5)
    chunks = [e for e in rec.events() if e["name"] == "prefill.chunk"]
    rec.clear()
    assert [c["args"]["tokens"] for c in chunks] == [4, 4, 3]
    # chunks chain: each starts where the previous ended
    for a, b in zip(chunks, chunks[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1.0)


def test_fault_stopped_server_still_drops_slo_gauges(tiny_gpt, tmp_path):
    """_on_engine_fault marks the server closed without running the
    normal teardown; a later close() must still retire the dead
    server's published SLO gauge series via the early-return branch —
    otherwise a long-lived process keeps scraping the dead server's
    last-window quantiles forever."""
    cfg, _scope, params = tiny_gpt
    chaos = _ticking_chaos(lambda it: 10.0).poison_serving_at(8)
    tel = ServingTelemetry(clock=chaos.serving_clock, window_s=0.05,
                           flight_dir=str(tmp_path))
    srv = _server(params, cfg, chaos=chaos, telemetry=tel)
    for i in range(3):
        srv.submit(np.arange(3, 8 + i, dtype=np.int32), max_new_tokens=12)
    with pytest.raises(NonFiniteError):
        srv.run_until_idle()
    sid = tel.slo.labels["server"]
    reg = global_registry()
    # at least one window rolled before the fault, so per-server gauges
    # were published (the precondition the regression needs)
    assert [lbl for lbl, _c in
            reg.gauge("serving.slo.tokens_per_s").series()
            if lbl.get("server") == sid]
    srv.close()
    for gname in ("serving.slo.tokens_per_s", "serving.slo.quantile_ms"):
        assert not [lbl for lbl, _c in reg.gauge(gname).series()
                    if lbl.get("server") == sid]


def test_nonfinite_guard_fires_without_telemetry(tiny_gpt):
    """The non-finite-logits fail-stop is a safety feature, not an
    observability feature: a telemetry=False server must still refuse
    to stream NaN-derived garbage — only the flight-recorder artifact
    is telemetry-dependent (err.flight_dump is None here)."""
    cfg, _scope, params = tiny_gpt
    chaos = ChaosInjector().poison_serving_at(6)
    srv = _server(params, cfg, chaos=chaos, telemetry=False)
    futs = [srv.submit(np.arange(3, 9, dtype=np.int32), max_new_tokens=16)
            for _ in range(2)]
    with pytest.raises(NonFiniteError) as ei:
        srv.run_until_idle()
    assert ei.value.flight_dump is None
    assert chaos.fired["serving_poison"] == 1
    for f in futs:
        with pytest.raises(NonFiniteError):
            f.result(timeout=5)
    srv.close()


def test_poison_on_cancel_only_iteration_is_deferred(tiny_gpt, tmp_path):
    """A KV poison keyed to an iteration whose plan() comes back None
    (cancel-only: the cancel empties the last active slot) must be
    re-keyed to the next iteration, not silently lost — a fault-
    injection test must never believe it exercised the NaN path when
    the poison never fired."""
    cfg, _scope, params = tiny_gpt
    chaos = (ChaosInjector().cancel_request_at(3, index=0)
             .poison_serving_at(3))
    tel = ServingTelemetry(flight_dir=str(tmp_path))
    srv = _server(params, cfg, chaos=chaos, telemetry=tel)
    fa = srv.submit(np.arange(3, 8, dtype=np.int32), max_new_tokens=20)
    srv.run_until_idle()       # iteration 3 is cancel-only -> idle
    assert fa.done()            # the cancel retired request A
    assert chaos.fired["cancel"] == 1
    assert chaos.fired["serving_poison"] == 0   # deferred, not fired
    fb = srv.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=20)
    with pytest.raises(NonFiniteError):
        srv.run_until_idle()   # re-keyed poison lands once B is live
    assert chaos.fired["serving_poison"] == 1
    with pytest.raises(NonFiniteError):
        fb.result(timeout=5)
