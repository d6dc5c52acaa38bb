#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process, one TPU (or one four-chip host with ``--chips 4``), the
entry points a user calls, at the full width of the dense control model
(``models/gpt.py:GPTConfig()`` — 12 layers, 768 wide, 12 heads, FFN
3072, vocab 32000, 1024 positions; dropout off):

* device: fail unless ``jax.default_backend() == "tpu"``;
* train:  ``build_lm_net`` -> ``AdamOptimizer.minimize`` ->
  ``amp.cast_model_to_bf16`` -> ``Executor(TPUPlace(0))``, a few seeded
  token rows trained to memorisation. The compiled step must hold the
  Mosaic flash forward and backward kernels;
* serve:  the same parameters behind ``GenerationServer`` at the
  engine's own defaults, worker thread on, concurrent ``submit()``s of
  mixed prompt lengths with streaming callbacks. The fused step must
  have compiled a paged-attention kernel, no reference fallback, one
  signature;
* check:  every request returns its row's memorised continuation
  exactly (a converged argmax gap dwarfs bf16 rounding; a broken kernel
  does not survive it), and the dense path (``gpt.build_kv_step`` +
  ``inference/decoding`` cache) teacher-forced on the engine's own
  tokens on the same chip and weights agrees on every argmax and on the
  per-token log-probs to LOGP_TOL.

Any failed phase raises: the exit code is non-zero and no result line
is printed. On success the last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--rehearse-on-cpu`` runs the same phases at a tiny size with
``JAX_PLATFORMS=cpu`` and the kernels interpreted, to debug the script
before chip time is spent; its output says ``platform: cpu``. It is
never the default.

Wall times printed here are set-up diagnostics, not metrics.
"""

import argparse
import json
import os
import sys
import time

LOGP_TOL = 0.05         # mean |engine - dense| per generated token, nats
LOSS_TARGET = 0.02      # mean next-token loss that counts as memorised
DP_LOSS_RTOL = 2e-2     # four-chip vs one-chip loss, bf16 matmuls


def _log(msg):
    print(f"chip_smoke: {msg}", flush=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


class _Sizes:
    """What the run is cut to. Full: the dense control at published
    width. Rehearsal: the CPU tests' tiny model."""

    def __init__(self, rehearsal):
        from paddle_tpu.models import gpt
        if rehearsal:
            self.cfg = gpt.gpt_tiny()
            self.seq_len = 64
            self.lr = 2e-3
            self.max_steps = 400
            self.prompt_lens = (3, 9, 20, 33, 41)
            self.new_tokens = 8
        else:
            self.cfg = gpt.GPTConfig(dropout=0.0)
            self.seq_len = 1024
            self.lr = 5e-4
            self.max_steps = 600
            # the last two span 26 and 57 sixteen-token blocks and 105
            # and 225 four-token prefill chunks
            self.prompt_lens = (5, 37, 130, 420, 900, 64)
            self.new_tokens = 24
        self.rows = 4       # the global batch, on one chip or four


def _device_phase(rehearsal, chips):
    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    backend = jax.default_backend()
    devs = jax.devices()
    cache_dir = enable_compile_cache()
    _log(f"platform: {devs[0].platform}  device_kind: "
         f"{devs[0].device_kind}  count: {len(devs)}  "
         f"compile cache: {cache_dir}")
    if rehearsal:
        _check(backend == "cpu", f"rehearsal runs on cpu, got {backend}")
    else:
        _check(backend == "tpu",
               f"no TPU: jax.default_backend() is {backend!r}")
    _check(len(devs) >= chips,
           f"--chips {chips} but jax has {len(devs)} device(s)")
    return devs[:chips], cache_dir


def _cache_entries(cache_dir):
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def _build_program(sz):
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import framework
    from paddle_tpu.models import gpt

    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        _tokens, loss, _logits = gpt.build_lm_net(sz.cfg,
                                                  seq_len=sz.seq_len)
        fluid.optimizer.AdamOptimizer(sz.lr).minimize(loss)
    amp.cast_model_to_bf16(main)
    return main, startup, loss


def _train(sz, rows, devices, dp, max_steps, target, rehearsal):
    """Train the rows until the loss reaches `target` (or `max_steps`).
    Returns (scope, losses). dp=True runs the same program through
    CompiledProgram.with_data_parallel over every device."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.ops.pallas import flash

    main, startup, loss = _build_program(sz)
    program = (fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name) if dp else main)
    place = fluid.TPUPlace(0)
    exe = fluid.Executor(place)
    scope = Scope()
    feed = {"tokens": rows.astype(np.int64)}
    traces0 = flash.TRACE_COUNT
    losses = []
    with scope_guard(scope):
        exe.run(startup)
        t0 = time.perf_counter()
        for step in range(max_steps):
            (out,) = exe.run(program, feed=feed, fetch_list=[loss],
                             return_numpy=False)
            if step == 0:
                want = set(devices) if dp else {place.jax_device()}
                _check(out.devices() == want,
                       f"fetch lives on {out.devices()}, place names "
                       f"{want}")
                _log(f"train: first step (compile included) "
                     f"{time.perf_counter() - t0:.1f}s")
            losses.append(float(np.asarray(out).reshape(-1)[0]))
            _check(np.isfinite(losses[-1]),
                   f"loss not finite at step {step}: {losses[-1]}")
            if losses[-1] < target:
                break
        _log(f"train: {len(losses)} steps, loss {losses[0]:.3f} -> "
             f"{losses[-1]:.4f} ({time.perf_counter() - t0:.1f}s)")
        _check(losses[-1] < losses[0], "loss did not fall")
        _check(target <= 0 or losses[-1] < target,
               f"loss {losses[-1]:.4f} did not reach {target} in "
               f"{max_steps} steps — rows not memorised")

        # the compiled step holds the flash kernels, compiled by Mosaic
        _check(flash.TRACE_COUNT - traces0 >= sz.cfg.num_layers,
               "training did not trace the flash kernel once per layer")
        _check(flash._interpret() == rehearsal,
               f"flash interpret mode is {flash._interpret()}")
        hlo = exe.last_compiled_text()
        if not rehearsal:
            calls = [ln for ln in hlo.splitlines()
                     if "tpu_custom_call" in ln]
            for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
                n = sum(kernel in ln for ln in calls)
                _check(n >= 1, f"compiled train step holds no Mosaic "
                               f"custom call for {kernel}")
            _log(f"train: compiled step holds {len(calls)} Mosaic "
                 f"custom calls (flash fwd + dq + dkv per layer)")
        if dp:
            _check("all-reduce" in hlo,
                   "data-parallel step compiled without an all-reduce")
    if dp:
        p = scope.get("gpt_word_emb")
        _check(len(p.addressable_shards) == len(devices),
               f"params live on {len(p.addressable_shards)} devices, "
               f"not {len(devices)}")
    exe.close()
    return scope, losses


def _dense_reference(params, cfg, seqs, max_len, dtype):
    """The dense cached path, teacher-forced: for each sequence, the
    argmax and the log-prob the model gives the NEXT fed token at every
    position. Returns (argmax (B, T-1), logp (B, T-1))."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import decoding as dec
    from paddle_tpu.models import gpt

    t_max = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), t_max), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    ids = jnp.asarray(ids)
    step = gpt.build_kv_step(params, cfg, max_len)
    cache = dec.init_kv_cache(len(seqs), cfg.num_layers, cfg.num_heads,
                              max_len, cfg.hidden_size // cfg.num_heads,
                              dtype)

    def body(cache, t):
        logits, cache = step(ids[:, t], cache, t)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        fed = jnp.take_along_axis(logp, ids[:, t + 1][:, None], -1)[:, 0]
        return cache, (jnp.argmax(logp, -1), fed)

    _, (am, lp) = jax.jit(
        lambda c: jax.lax.scan(body, c, jnp.arange(t_max - 1)))(cache)
    return np.asarray(am).T, np.asarray(lp).T


def _serve_and_check(sz, scope, rows, mesh, rehearsal):
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    cfg = sz.cfg
    model = GPTServingModel.from_scope(scope, cfg, dtype=jnp.bfloat16)
    dense_params = model.params     # a mesh server re-binds its own copy
    kw = {"mesh": mesh} if mesh is not None else {}
    srv = GenerationServer(model, **kw)      # the engine's own defaults
    try:
        streamed, futs = {}, []
        t0 = time.perf_counter()
        for i, plen in enumerate(sz.prompt_lens):
            row = rows[i % len(rows)]
            streamed[i] = []
            futs.append(srv.submit(
                row[:plen], max_new_tokens=sz.new_tokens,
                stream=lambda rid, tok, i=i: streamed[i].append(
                    int(tok))))
        results = [f.result(timeout=600) for f in futs]
        _log(f"serve: {len(results)} requests, prompts "
             f"{list(sz.prompt_lens)}, {sz.new_tokens} new tokens each "
             f"({time.perf_counter() - t0:.1f}s, compile included)")
        st = srv.get_stats()
    finally:
        srv.close()

    kern = st["kernel"]
    _log(f"serve: kernel {kern}  signatures "
         f"{st['fused_step_signatures']}  block_size {st['block_size']}"
         f"  max_context {st['max_context']}")
    _check(kern["version"] in ("v1", "v2"),
           f"no paged kernel engaged: {kern}")
    _check(kern["fallback_dispatches"] == 0
           and kern["kernel_dispatches"] == cfg.num_layers,
           f"reference path taken: {kern}")
    interp = global_registry().gauge("serving.kernel.interpret").value()
    _check(interp == (1 if rehearsal else 0),
           f"serving.kernel.interpret gauge is {interp}")
    _check(st["fused_step_signatures"] == 1,
           f"{st['fused_step_signatures']} fused signatures")
    if mesh is not None:
        tp = mesh.devices.size
        _check(st["mesh"]["tp"] == tp, f"mesh stats {st['mesh']}")
        shards = srv.cache.pools[0]["kv"].addressable_shards
        _check(len(shards) == tp and shards[0].data.shape[1]
               == cfg.num_heads // tp,
               f"KV pool is not head-sharded over {tp} devices")

    # -- check 1: the memorised continuation, exactly ------------------
    seqs = []
    for i, (plen, res) in enumerate(zip(sz.prompt_lens, results)):
        row = rows[i % len(rows)]
        got = [int(t) for t in res.token_ids]
        want = [int(t) for t in row[plen:plen + sz.new_tokens]]
        _check(got == want,
               f"request {i} (prompt {plen}): got {got}, memorised "
               f"continuation is {want}")
        _check(streamed[i] == got,
               f"request {i}: streamed {streamed[i]} != result {got}")
        seqs.append(np.concatenate([row[:plen], res.token_ids]))

    # -- check 2: the dense path on the same chip and weights ----------
    am, lp = _dense_reference(dense_params, cfg, seqs, st["max_context"],
                              jnp.bfloat16)
    worst = 0.0
    for i, (plen, res) in enumerate(zip(sz.prompt_lens, results)):
        n = len(res.token_ids)
        sl = slice(plen - 1, plen - 1 + n)
        _check(list(am[i, sl]) == [int(t) for t in res.token_ids],
               f"request {i}: dense argmax {list(am[i, sl])} != engine "
               f"{list(res.token_ids)}")
        diff = abs(float(lp[i, sl].sum()) - float(res.score)) / n
        worst = max(worst, diff)
        _check(diff <= LOGP_TOL,
               f"request {i}: engine score {res.score:.4f} vs dense "
               f"{lp[i, sl].sum():.4f} — {diff:.4f} nats/token > "
               f"{LOGP_TOL}")
    _log(f"check: {len(results)} continuations exact; dense-path "
         f"argmax agrees; worst log-prob gap {worst:.4f} nats/token "
         f"(tolerance {LOGP_TOL})")


def _four_chip_evidence(devices):
    for d in devices:
        stats = d.memory_stats() or {}
        used = stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
        _log(f"four-chip: {d} peak_bytes_in_use {used}")
        _check(used > (1 << 20),
               f"{d} holds {used} bytes — nothing ran there")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: with_data_parallel training and tp=4 "
                         "serving on one four-chip host")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="NOT a chip run: tiny model, JAX_PLATFORMS=cpu,"
                         " kernels interpreted — for debugging this "
                         "script")
    args = ap.parse_args(argv)
    rehearsal, chips = args.rehearse_on_cpu, args.chips
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_FORCE_FLASH"] = "1"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
        _log("REHEARSAL on cpu — not a chip run")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    import numpy as np
    import jax

    t_start = time.perf_counter()
    devices, cache_dir = _device_phase(rehearsal, chips)
    entries0 = _cache_entries(cache_dir)

    sz = _Sizes(rehearsal)
    rows = np.random.default_rng(0).integers(
        3, sz.cfg.vocab_size, (sz.rows, sz.seq_len)).astype(np.int32)

    mesh = None
    if chips == 1:
        scope, _ = _train(sz, rows, devices, False, sz.max_steps,
                          LOSS_TARGET, rehearsal)
    else:
        # the same global batch on one chip, then on four: the first
        # losses must agree before the four-chip run goes on to memorise
        _, one = _train(sz, rows, devices, False, 3, 0.0, rehearsal)
        scope, four = _train(sz, rows, devices, True, sz.max_steps,
                             LOSS_TARGET, rehearsal)
        _log(f"four-chip: loss one chip {one} vs four {four[:3]}")
        _check(np.allclose(one, four[:3], rtol=DP_LOSS_RTOL),
               f"four-chip loss {four[:3]} != one-chip {one}")
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices), ("tp",))
    _serve_and_check(sz, scope, rows, mesh, rehearsal)
    if chips > 1 and not rehearsal:
        _four_chip_evidence(devices)

    new = _cache_entries(cache_dir) - entries0
    _log(f"compile cache: {new} new entries in {cache_dir}")
    _log(f"wall {time.perf_counter() - t_start:.1f}s (set-up time, not "
         f"a metric)")
    d0 = jax.devices()[0]
    result = {"ok": True, "device": {"platform": d0.platform,
                                     "kind": d0.device_kind,
                                     "count": len(jax.devices())}}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
