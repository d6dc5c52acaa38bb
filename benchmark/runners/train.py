"""Runner `train`: a configuration's pre-training program through
`Executor` on one chip, fed a ring of seeded host batches as numpy every
step, the loss brought to the host every `log_every` steps and at the
window's end.

The rate is all the tokens of all the steps of the window over all its
time: the window opens after the warm-up steps (which compile) and
closes when the last step's loss has reached the host, so nothing the
device still owes is left out. A step is some hundreds of milliseconds
and the window many steps, so the host clock's half millisecond does not
show.
"""

import time

import numpy as np

from benchmark import harness

CHIPS = (1,)
_clock = time.perf_counter


def _loss(fetch):
    return float(np.asarray(fetch).reshape(-1)[0])


def run(ctx):
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.ops.pallas import flash

    c, params = ctx.config, ctx.traffic
    family = harness.by_name("models", c["family"])
    generator = harness.by_name("generators", params["generator"])
    ref = harness.by_name("reference", c["correct"]["reference"])
    if ctx.chips not in CHIPS:
        raise SystemExit(
            f"runner 'train' drives Executor on one chip; a cell on "
            f"{ctx.chips} chips (PERF.md Open questions, row "
            f"bert-large.pretrain-seq512-dp4) needs a runner of its own "
            f"that goes through CompiledProgram.with_data_parallel")
    out = harness.Run(ctx)
    compiles = harness.CompileCounter()
    seq_len, rows = int(params["seq_len"]), int(c["rows_per_step"])
    log_every = int(params["log_every"])
    main, startup, test, loss, fwd_flops_row = family.pretrain_programs(
        c, seq_len, ctx.seed)
    ring = generator.batches(params, c, rows, ctx.seed)
    harness.log(f"train: programs and batches built "
                f"({_clock() - ctx.t_start:.1f}s)")
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = Scope()
    traced = harness.TracedWindow(ctx.cell, ctx.seed, ctx.chips) \
        if ctx.trace else None
    losses = []

    def step(i):
        return exe.run(main, feed=ring[i % len(ring)], fetch_list=[loss],
                       return_numpy=False)[0]

    with scope_guard(scope):
        exe.run(startup)
        # the startup program's outputs are uncommitted arrays and a
        # step's are committed, so Executor compiles the train step
        # twice (PERF.md section 6). Committing them here, where they
        # already lie, leaves one lowering and one compile to set-up.
        import jax
        for name in scope.names():
            scope.set(name, jax.device_put(scope.get(name), ctx.devices[0]))
        harness.log(f"train: parameters on the device "
                    f"({_clock() - ctx.t_start:.1f}s)")
        traces0 = flash.TRACE_COUNT
        n = 0
        for _ in range(int(params["warmup_steps"])):
            losses.append(_loss(step(n)))
            n += 1
            harness.log(f"train: warm-up step {n} done "
                        f"({_clock() - ctx.t_start:.1f}s)")
        flash_traced = flash.TRACE_COUNT - traces0
        # -- the window ---------------------------------------------------
        t0 = out.t0 = _clock()
        out.e2e["setup_s"] = t0 - ctx.t_start
        trace_until = None
        if traced is not None:
            traced.start()
            trace_until = traced.t0 + min(
                float(params.get("trace_seconds", 4)), ctx.seconds)
        steps0, last = n, None
        rate_t0, rate_steps0 = t0, n
        while _clock() - t0 < ctx.seconds:
            last = step(n)
            n += 1
            if (n - steps0) % log_every == 0:
                losses.append(_loss(last))
                last = None
            if trace_until is not None and _clock() >= trace_until:
                traced.stop()
                trace_until = None
                # stopping the profiler holds this thread for seconds, so
                # a traced run takes its rate (for the MFU) from the rest
                # of the window, the profiler off and the device drained
                if last is not None:
                    losses.append(_loss(last))
                    last = None
                if _clock() - t0 < ctx.seconds:
                    rate_t0, rate_steps0 = _clock(), n
        if last is not None:
            losses.append(_loss(last))
        t1 = out.t1 = _clock()
        if trace_until is not None:
            traced.stop()
        steps = n - steps0
        out.e2e["train_tokens_per_s"] = \
            (n - rate_steps0) * rows * seq_len / (t1 - rate_t0)
        out.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
        harness.log(f"train: window {t1 - t0:.2f}s: {steps} steps of "
                    f"{rows} x {seq_len}, losses {losses[0]:.4f} .. "
                    f"{losses[-1]:.4f} ({len(losses)} fetched)")
        # -- correct, outside the window ------------------------------------
        (got,) = exe.run(test, feed=ring[0], fetch_list=[loss])
        got = _loss(got)
        harness.log(f"train: test-mode program ran "
                    f"({_clock() - ctx.t_start:.1f}s)")
        want = ref.pretrain_loss(scope, c, ring[0])
        harness.log(f"train: reference ran "
                    f"({_clock() - ctx.t_start:.1f}s)")
        if traced is not None:
            # what the compiled step holds on the chip, from the
            # compiler's own account of it: the backend's
            # peak_bytes_in_use leaves a step's temporaries out (PERF.md
            # section 4). Lowering the step again and walking its jaxpr
            # takes 111 s, so only a traced run pays for it.
            try:
                report = exe.explain(main, feed=ring[0], fetch_list=[loss],
                                     backend=True)
                out.samples["step_memory"] = report["xla"]["memory"]
            except RuntimeError as exc:
                harness.log(f"train: no memory analysis of the step: "
                            f"{exc}")
            harness.log(f"train: step explained "
                        f"({_clock() - ctx.t_start:.1f}s)")
    exe.close()
    out.traced = traced
    out.attempted, out.failed = steps, 0
    shape = family.shape_facts(c)
    out.facts.update(steps=steps, rows=rows, seq_len=seq_len, **shape,
                     forward_matmul_flops_per_token=fwd_flops_row / seq_len,
                     loss_program=got, loss_reference=want)
    rtol = float(c["correct"]["loss_rtol"])
    ok = out.check(all(np.isfinite(losses)), "every fetched loss finite")
    half = len(losses) // 2
    ok &= out.check(
        half >= 1 and np.mean(losses[half:]) < np.mean(losses[:half]),
        f"loss falls: later half {np.mean(losses[half:]):.4f} below "
        f"earlier half {np.mean(losses[:max(half, 1)]):.4f}")
    ok &= out.within(
        "loss_relative_gap", abs(got - want) / abs(want), rtol,
        f"test-mode loss of batch 0 at the trained parameters: program "
        f"{got:.6f}, reference {want:.6f}, relative gap")
    ok &= out.check(flash_traced >= shape["num_layers"],
                    f"flash attention traced {flash_traced} times")
    ok &= out.check(flash._interpret() == ctx.rehearsal,
                    f"flash interpret mode is {flash._interpret()}")
    if traced is not None and traced.device is not None:
        # a traced run sees the kernels themselves; reading them out of
        # the compiled text instead costs every run a second lowering of
        # the step, and on a cold cache a second compile (PERF.md
        # section 6, finding 3)
        for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
            k = traced.device.kernel_calls((kernel,))
            ok &= out.check(k >= 1, f"the trace holds {k:.0f} executions "
                                    f"of {kernel}")
    ok &= out.within("compilations_in_window", compiles.inside(t0, t1), 0,
                     f"compilations inside the window ({compiles.total()} "
                     f"in the whole run)")
    out.correct = bool(ok)
    return out
