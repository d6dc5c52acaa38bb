"""Runner `serve`: a configuration behind `GenerationServer`, worker
thread on, under the load its traffic file's generator plays
(`benchmark/generators/<generator>.py`), timed from the client's side.

The generator owns the clients and their clock readings
(`time.perf_counter` at `submit` and in the `stream` callback); nothing
is read from the program's telemetry. Its interface:
`Load(submit, params, seed, vocab_size)` with `start()`, `stop()`, the
event `warm`, `submit_errors` and `log`, a list of requests that carry
`first`, `prompt`, `want`, `t_submit`, `stamps`, `tokens`, `t_done`,
`result` and `error`.

The window opens when the generator says the load is warm. An event
counts where its own time falls in the window: a token and the gap
before it at the token's stamp, a time to first token at the first
token's stamp, a request at its resolution.
"""

import gc
import time

import numpy as np

from benchmark import harness, stats

CHIPS = (1,)
_clock = time.perf_counter


def _window_metrics(run, log, t0, t1):
    """The end-to-end metrics, from the request log alone."""
    tokens = sum(stats.in_window(r.stamps, t0, t1) for r in log)
    ttft = [(r.stamps[0] - r.t_submit) * 1e3 for r in log
            if r.stamps and t0 <= r.stamps[0] <= t1]
    gaps = [g for r in log for g in stats.gaps_ms(r.stamps, t0, t1)]
    run.e2e["output_tokens_per_s"] = stats.rate_per_s(tokens, t0, t1)
    if ttft:
        run.e2e["ttft_p50_ms"] = stats.median(ttft)
    if gaps:
        run.e2e["itl_p95_ms"] = stats.percentile(gaps, 95)
    run.facts.update(window_tokens=tokens, ttft_samples=len(ttft),
                     itl_samples=len(gaps),
                     ttft_p90_ms=(stats.percentile(ttft, 90) if ttft
                                  else None),
                     itl_p50_ms=stats.median(gaps) if gaps else None)


def _window_facts(run, log, t0, t1):
    """Why a window read as it did, from the request log alone (no clock
    of its own): where deliveries stopped, and how the work lay along
    the window. A stall shows as one long no-token interval, a slow
    stretch as low tenths with no long interval, another phase of the
    closed loop as prompt tokens bunched in some tenths."""
    gaps = stats.no_token_gaps([r.stamps for r in log], t0, t1)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    stalls = stats.stalls(gaps)
    tokens = stats.by_tenth([(t, 1) for r in log for t in r.stamps],
                            t0, t1)
    prompts = stats.by_tenth([(r.t_submit, len(r.prompt)) for r in log],
                             t0, t1)
    run.facts.update(
        no_token_gap_p50_ms=stats.weighted_median_s(gaps) * 1e3,
        longest_no_token_gap_ms=(longest[1] - longest[0]) * 1e3,
        stall_share=stats.stall_share(gaps, t0, t1),
        stalls=len(stalls),
        # "seconds into the window:ms", the first dozen
        stalls_at_s_ms=" ".join(f"{a - t0:.2f}:{(b - a) * 1e3:.0f}"
                                for a, b in stalls[:12]),
        tokens_by_tenth=" ".join(str(n) for n in tokens),
        prompt_tokens_by_tenth=" ".join(str(n) for n in prompts))


def _host_reading():
    """What the interpreter counts for this process, read before and
    after the window and never inside it. (The chip machine's kernel
    keeps no `getrusage` context switches or page faults, no
    `/proc/stat` and no load average: all read 0 there, PR 33.)"""
    return {"cpu_s": time.process_time(),
            "gc_gen2_collections": gc.get_stats()[2]["collections"]}


def _count_requests(run, log, t0, t1):
    """attempted: requests that resolved inside the window. failed:
    those that raised, returned another number of tokens than asked, or
    streamed other tokens than they returned."""
    done = [r for r in log if r.t_done is not None
            and t0 <= r.t_done <= t1]
    bad = 0
    for r in done:
        if r.error is not None:
            bad += 1
        elif len(r.result.token_ids) != r.want or \
                [int(t) for t in r.result.token_ids] != r.tokens:
            bad += 1
    run.attempted, run.failed = len(done), bad
    return [r for r in done if r.error is None]


def _reference_check(run, ref, model, cfg, c, log, finished):
    """The engine against the plain reference, over each client's first
    request and up to `requests_checked` finished requests of the
    window, from the shortest to the longest. The engine returns a
    request's score, the sum of its tokens' log-probs, and no log-prob
    per token, so:

    * a request of ONE token (a first request) is a log-prob per token:
      it agrees with the reference's to `token_logp_tol_nats`;
    * a longer request agrees in the mean over its tokens, to
      `mean_logp_tol_nats_per_token` (opposite errors cancel there,
      which is why the first rule exists);
    * every token the engine chose is, by the reference, within
      `token_regret_tol_nats` of the reference's best token."""
    tol = c["correct"]
    n_check = int(tol["requests_checked"])
    firsts = [r for r in log if r.first and r.result is not None]
    n_first = sum(1 for r in log if r.first)
    by_len = sorted(finished, key=lambda r: (len(r.prompt) + r.want,
                                             r.t_submit))
    spread = sorted({int(round(i)) for i in
                     np.linspace(0, len(by_len) - 1, n_check)}) \
        if by_len else []
    picked = [by_len[i] for i in spread]
    longest = max((len(r.prompt) + r.want for r in firsts + picked),
                  default=0)
    ok = run.check(len(picked) == min(n_check, len(by_len)) > 0
                   and len(firsts) == n_first > 0,
                   f"{len(firsts)} of {n_first} first requests and "
                   f"{len(picked)} of {len(by_len)} finished requests to "
                   f"check (up to {n_check}); the longest context "
                   f"{longest}")
    worst = {"token": 0.0, "mean": 0.0, "regret": 0.0}
    for r in firsts + picked:
        toks = np.asarray(r.result.token_ids, np.int32)
        p, n = len(r.prompt), len(toks)
        rows = ref.forward_logprobs(
            model.params, cfg, np.concatenate([r.prompt, toks]),
            pad_to=model.max_position, first_row=p - 1, n_rows=n)
        chosen = rows[np.arange(n), toks]       # row t predicts ids[t+1]
        gap = abs(float(chosen.sum()) - float(r.result.score)) / n
        kind = "token" if n == 1 else "mean"
        worst[kind] = max(worst[kind], gap)
        worst["regret"] = max(worst["regret"],
                              float((rows.max(-1) - chosen).max()))
    run.facts.update(token_logp_gap_nats=worst["token"],
                     mean_logp_gap_nats_per_token=worst["mean"],
                     token_regret_nats=worst["regret"])
    for kind, key, what in (
            ("token", "token_logp_tol_nats",
             "log-prob of a one-token request vs reference, worst, nats"),
            ("mean", "mean_logp_tol_nats_per_token",
             "score per token of a longer request vs reference, worst, "
             "nats"),
            ("regret", "token_regret_tol_nats",
             "reference's best token over the engine's token, worst, "
             "nats")):
        ok &= run.within(key.replace("_tol", "_gap"), worst[kind],
                         float(tol[key]), what)
    return ok


def run(ctx):
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.serving import GenerationServer

    c, params = ctx.config, ctx.traffic
    family = harness.by_name("models", c["family"])
    generator = harness.by_name("generators", params["generator"])
    ref = harness.by_name("reference", c["correct"]["reference"])
    if ctx.chips not in CHIPS:
        raise SystemExit(
            f"runner 'serve' drives one replica on one chip; a cell on "
            f"{ctx.chips} chips (PERF.md Open questions, row "
            f"gpt2-xl.fleet4) needs a runner of its own")
    out = harness.Run(ctx)
    compiles = harness.CompileCounter()
    model, cfg = family.serving_model(c, ctx.seed)
    server_kw = dict(c["server"])
    harness.log(f"serve: parameters on the device "
                f"({_clock() - ctx.t_start:.1f}s); server {server_kw}")
    srv = GenerationServer(model, **server_kw)
    reg = global_registry()
    blocks_gauge = reg.gauge("serving.blocks_in_use")
    load = generator.Load(srv.submit, params, ctx.seed, cfg.vocab_size)
    traced = harness.TracedWindow(ctx.cell, ctx.seed, ctx.chips) \
        if ctx.trace else None
    try:
        load.start()
        if not load.warm.wait(timeout=ctx.warm_timeout_s):
            raise RuntimeError(
                f"the load was not warm after {ctx.warm_timeout_s}s")
        host_before = _host_reading()
        t0 = out.t0 = _clock()
        out.e2e["setup_s"] = t0 - ctx.t_start
        t1 = t0 + ctx.seconds
        trace_until = None
        if traced is not None:
            traced.start()
            trace_until = traced.t0 + min(
                float(params.get("trace_seconds", 4)), ctx.seconds)
        blocks = []
        while _clock() < t1:
            time.sleep(0.05)
            blocks.append(blocks_gauge.value())
            if trace_until is not None and _clock() >= trace_until:
                traced.stop()
                trace_until = None
        if trace_until is not None:
            traced.stop()
        out.t1 = t1
        host_after = _host_reading()
        load.stop()
        st = srv.get_stats()
        interp = reg.gauge("serving.kernel.interpret").value()
    finally:
        srv.close(drain=False)
    # -- the window, from the client's side ------------------------------
    log = list(load.log)
    _window_metrics(out, log, t0, t1)
    _window_facts(out, log, t0, t1)
    out.facts.update({"window_" + k: host_after[k] - host_before[k]
                      for k in host_before})
    finished = _count_requests(out, log, t0, t1)
    out.failed += load.submit_errors
    out.attempted += load.submit_errors
    errors = [r.error for r in log if r.error is not None
              and r.t_done <= t1]
    if errors:
        harness.log(f"serve: {len(errors)} request(s) raised before the "
                    f"window closed; the first: {errors[0]!r}")
    out.requests = log
    out.traced = traced
    out.samples["blocks_in_use"] = blocks
    out.facts.update(
        pool_blocks=srv.cache.num_blocks, kernel=st["kernel"],
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        head_dim=cfg.hidden_size // cfg.num_heads,
        kv_itemsize=np.dtype(srv.cache.dtype).itemsize,
        chunk=st["chunk"], requests_submitted=len(log),
        requests_resolved=out.attempted,
        compiles_in_run=compiles.total(),
        compile_cache_misses=compiles.cache_misses,
        **(family.serving_flops(c) if hasattr(family, "serving_flops")
           else {}))
    out.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    harness.log(f"serve: window {ctx.seconds}s: {out.attempted} requests "
                f"resolved, {out.failed} failed, "
                f"{out.facts['window_tokens']} tokens, ttft samples "
                f"{out.facts['ttft_samples']}, gaps "
                f"{out.facts['itl_samples']}; ttft p90 "
                f"{out.facts['ttft_p90_ms']}, itl p50 "
                f"{out.facts['itl_p50_ms']}")
    # -- correct ---------------------------------------------------------
    kern = st["kernel"]
    ok = out.within("requests_failed", out.failed, 0,
                    f"requests failed of {out.attempted} resolved")
    ok &= out.check(out.attempted > 0, "a request resolved in the window")
    ok &= out.check(kern["version"] in ("v1", "v2")
                    and kern["fallback_dispatches"] == 0
                    and kern["kernel_dispatches"] == cfg.num_layers,
                    f"paged kernel engaged in every layer: {kern}")
    ok &= out.check(interp == (1 if ctx.rehearsal else 0),
                    f"serving.kernel.interpret gauge is {interp}")
    ok &= out.check(st["fused_step_signatures"] == 1,
                    f"{st['fused_step_signatures']} fused-step signature(s)")
    ok &= out.within("compilations_in_window", compiles.inside(t0, t1), 0,
                     f"compilations inside the window ({compiles.total()} "
                     f"in the whole run)")
    ok &= _reference_check(out, ref, model, cfg, c, log, finished)
    out.correct = bool(ok)
    return out
