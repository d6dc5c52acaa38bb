"""Runner `serve_state`: runner `serve` for a configuration whose every
layer carries a state a lane and walks no block table.

The window, the metrics, the facts and the comparison with the plain
reference are `serve`'s own, through its helpers. One condition of
`correct` differs. `serve` asks every layer for the paged walk, of
generation v1 or v2. A state layer's kernel has no generation: it
counts under its name alone (`get_stats()["kernel"]["name"]`). So here
every layer must dispatch to a named kernel of no generation, and none
may fall back to its reference.
"""

import time

import numpy as np

from benchmark import harness
from benchmark.runners import serve

CHIPS = serve.CHIPS
_clock = time.perf_counter


def run(ctx):
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.serving import GenerationServer

    c, params = ctx.config, ctx.traffic
    family = harness.by_name("models", c["family"])
    generator = harness.by_name("generators", params["generator"])
    ref = harness.by_name("reference", c["correct"]["reference"])
    if ctx.chips not in CHIPS:
        raise SystemExit(
            f"runner 'serve_state' drives one replica on one chip, not "
            f"{ctx.chips}")
    out = harness.Run(ctx)
    compiles = harness.CompileCounter()
    model, cfg = family.serving_model(c, ctx.seed)
    server_kw = dict(c["server"])
    harness.log(f"serve_state: parameters on the device "
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
        host_before = serve._host_reading()
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
        host_after = serve._host_reading()
        load.stop()
        st = srv.get_stats()
        interp = reg.gauge("serving.kernel.interpret").value()
    finally:
        srv.close(drain=False)
    # -- the window, from the client's side ------------------------------
    log = list(load.log)
    serve._window_metrics(out, log, t0, t1)
    serve._window_facts(out, log, t0, t1)
    out.facts.update({"window_" + k: host_after[k] - host_before[k]
                      for k in host_before})
    finished = serve._count_requests(out, log, t0, t1)
    out.failed += load.submit_errors
    out.attempted += load.submit_errors
    errors = [r.error for r in log if r.error is not None
              and r.t_done <= t1]
    if errors:
        harness.log(f"serve_state: {len(errors)} request(s) raised before "
                    f"the window closed; the first: {errors[0]!r}")
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
    harness.log(f"serve_state: window {ctx.seconds}s: {out.attempted} "
                f"requests resolved, {out.failed} failed, "
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
    ok &= out.check(kern["version"] is None and bool(kern["name"])
                    and kern["fallback_dispatches"] == 0
                    and kern["kernel_dispatches"] == cfg.num_layers,
                    f"a state kernel engaged in every layer: {kern}")
    ok &= out.check(interp == (1 if ctx.rehearsal else 0),
                    f"serving.kernel.interpret gauge is {interp}")
    ok &= out.check(st["fused_step_signatures"] == 1,
                    f"{st['fused_step_signatures']} fused-step signature(s)")
    ok &= out.within("compilations_in_window", compiles.inside(t0, t1), 0,
                     f"compilations inside the window ({compiles.total()} "
                     f"in the whole run)")
    ok &= serve._reference_check(out, ref, model, cfg, c, log, finished)
    out.correct = bool(ok)
    return out
