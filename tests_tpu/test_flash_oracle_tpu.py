"""Flash-vs-oracle on REAL TPU hardware (the compiled Mosaic kernel, not
the CPU Pallas interpreter that `tests/ops/test_flash_attention.py`
exercises). Each assertion message carries the case's max error.

Tolerances are bf16-aware: the production kernel runs bf16 inputs with
f32 accumulation; the oracle is computed in f32 and compared against a
bf16-rounded reference error bound.
"""

import time

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _rand(shape, seed, dtype):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype_name,tol", [("float32", 2e-5),
                                            ("bfloat16", 2e-2)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_tpu(dtype_name, tol, causal):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    dtype = jnp.dtype(dtype_name)
    b, h, t, d = 2, 4, 512, 64
    q, k, v = (_rand((b, h, t, d), s, dtype) for s in (0, 1, 2))
    scale = 1.0 / d ** 0.5
    got = flash.flash_attention(q, k, v, scale=scale, causal=causal)
    want = flash._xla_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), scale, causal)
    err = np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want)))
    assert err <= tol, f"max_abs_err {err} > {tol}"


@pytest.mark.parametrize("bias_kind", ["none", "key_mask"])
def test_flash_bwd_tpu(bias_kind):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, t, d = 2, 4, 256, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.float32) for s in (0, 1, 2))
    scale = 1.0 / d ** 0.5
    bias = None
    if bias_kind == "key_mask":
        m = np.zeros((b, 1, 1, t), np.float32)
        m[0, :, :, t // 2:] = -1e9
        bias = jnp.asarray(m)

    def floss(q, k, v):
        o = flash.flash_attention(q, k, v, bias=bias, scale=scale)
        return jnp.sum(jnp.sin(o))

    def oloss(q, k, v):
        o = flash._xla_ref(q, k, v, scale, False, bias=bias)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(oloss, argnums=(0, 1, 2))(q, k, v)
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
              for a, b_ in zip(gf, go))
    tol = 5e-4
    assert err <= tol, f"max grad err {err} > {tol}"


def test_flash_bench_shape_bwd_runs_promptly():
    """Isolates the headline attention shape (BERT-base: h=12, t=512,
    d=64, bf16, fwd+bwd) from the rest of a training step: if the Mosaic
    kernel compiles and steps in seconds here, a future stall of a
    training cell is not the flash kernel's fault. The bound is a hang
    tripwire (minutes of slack), not a perf assertion."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, t, d = 8, 12, 512, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.bfloat16) for s in (0, 1, 2))

    def loss(q, k, v):
        o = flash.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    t0 = time.time()
    jax.block_until_ready(g(q, k, v))       # compile + first step
    t_compile = time.time() - t0
    t0 = time.time()
    for _ in range(5):
        out = g(q, k, v)
    jax.block_until_ready(out)
    t_steps = time.time() - t0
    assert t_compile < 300, f"flash compile took {t_compile:.0f}s"
    assert t_steps < 60, f"5 fwd+bwd steps took {t_steps:.0f}s"


def test_flash_bwd_causal_pruning_tpu():
    """Causal BACKWARD on the compiled Mosaic kernel: the causal
    block-pruning skips fully-masked K/Q tiles in the bwd kernels too.
    Grads must equal the XLA oracle's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, t, d = 2, 4, 512, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.float32) for s in (10, 11, 12))
    scale = 1.0 / d ** 0.5

    def floss(q, k, v):
        o = flash.flash_attention(q, k, v, scale=scale, causal=True)
        return jnp.sum(jnp.sin(o))

    def oloss(q, k, v):
        o = flash._xla_ref(q, k, v, scale, True)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(oloss, argnums=(0, 1, 2))(q, k, v)
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
              for a, b_ in zip(gf, go))
    tol = 5e-4
    assert err <= tol, f"max grad err {err} > {tol}"


def test_flash_packed_rows_segment_ids_tpu():
    """Packed-row segment masking (r4 commits 0dbe37c/cc7ed0a) on real
    hardware: boundaries STRADDLE the 128-wide blocks (no tile is
    skippable), fwd and grads vs the explicit cross-segment -inf oracle.
    Pad slots (id 0) excluded from the comparison as in the CPU tier."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, t, d = 2, 4, 512, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.float32) for s in (13, 14, 15))
    seg = np.zeros((b, t), np.int32)
    seg[0, :200] = 1
    seg[0, 200:440] = 2            # 72 pad slots
    seg[1, :130] = 1               # boundaries straddle the 128-blocks
    seg[1, 130:512] = 2
    seg = jnp.asarray(seg)
    scale = 1.0 / d ** 0.5

    got = flash.flash_attention(q, k, v, scale=scale, segment_ids=seg)
    want = flash._xla_ref(q, k, v, scale, False,
                          bias=flash.segment_mask_bias(seg, seg))
    err = max(
        float(np.max(np.abs(np.asarray(got)[0, :, :440]
                            - np.asarray(want)[0, :, :440]))),
        float(np.max(np.abs(np.asarray(got)[1] - np.asarray(want)[1]))))
    tol = 2e-5
    assert err <= tol, f"max_abs_err {err} > {tol}"

    def floss(q, k, v):
        o = flash.flash_attention(q, k, v, scale=scale, segment_ids=seg)
        return jnp.sum(jnp.sin(o[0, :, :440])) + jnp.sum(jnp.sin(o[1]))

    def oloss(q, k, v):
        o = flash._xla_ref(q, k, v, scale, False,
                           bias=flash.segment_mask_bias(seg, seg))
        return jnp.sum(jnp.sin(o[0, :, :440])) + jnp.sum(jnp.sin(o[1]))

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(oloss, argnums=(0, 1, 2))(q, k, v)
    gerr = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
               for a, b_ in zip(gf, go))
    gtol = 5e-4
    assert gerr <= gtol, f"max grad err {gerr} > {gtol}"


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_skip_tiles_tpu(causal):
    """Block-ALIGNED disjoint segments (4x128 with block 128) force the
    segment-tile SKIP branch in the compiled kernels — the packed-row
    block-sparsity path (commit 0dbe37c) that had only ever run under
    the CPU interpreter. causal=True composes the causal-AND-overlap
    guard (the packed-GPT hot path, commit cc7ed0a)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, t, d = 2, 4, 512, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.float32) for s in (16, 17, 18))
    seg = jnp.asarray(np.repeat([[1, 2, 3, 4]], b, 0).repeat(128, 1))
    scale = 1.0 / d ** 0.5

    got = flash.flash_attention(q, k, v, scale=scale, causal=causal,
                                block_q=128, block_k=128, segment_ids=seg)
    want = flash._xla_ref(q, k, v, scale, causal,
                          bias=flash.segment_mask_bias(seg, seg))
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    tol = 2e-5
    assert err <= tol, f"max_abs_err {err} > {tol}"

    def floss(q, k, v):
        o = flash.flash_attention(q, k, v, scale=scale, causal=causal,
                                  block_q=128, block_k=128,
                                  segment_ids=seg)
        return jnp.sum(jnp.sin(o))

    def oloss(q, k, v):
        o = flash._xla_ref(q, k, v, scale, causal,
                           bias=flash.segment_mask_bias(seg, seg))
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(oloss, argnums=(0, 1, 2))(q, k, v)
    gerr = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
               for a, b_ in zip(gf, go))
    gtol = 5e-4
    assert gerr <= gtol, f"max grad err {gerr} > {gtol}"


def test_flash_causal_no_visible_keys_tpu():
    """Zero-visible-row semantics (commit a4f6691) on hardware: causal
    q_len > kv_len leaves rows with NO visible key; the compiled pruned
    kernel must output exactly 0 there and match the oracle on rows
    that do have visible keys."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    b, h, tq, tk, d = 1, 4, 256, 128, 64
    q = _rand((b, h, tq, d), 20, jnp.float32)
    k = _rand((b, h, tk, d), 21, jnp.float32)
    v = _rand((b, h, tk, d), 22, jnp.float32)
    scale = 1.0 / d ** 0.5
    got = np.asarray(flash.flash_attention(q, k, v, scale=scale,
                                           causal=True))
    dead = tq - tk
    zero_err = float(np.max(np.abs(got[:, :, :dead])))
    want = np.asarray(flash._xla_ref(q, k, v, scale, True))
    live_err = float(np.max(np.abs(got[:, :, dead:] - want[:, :, dead:])))
    tol = 2e-5
    assert zero_err == 0.0, f"dead rows not exactly zero: {zero_err}"
    assert live_err <= tol, f"live-row err {live_err} > {tol}"


def test_prefill_matches_stepwise_on_tpu():
    """Serving prefill on the compiled Mosaic kernels: the parallel
    prompt forward (models/gpt.py build_prefill — ONE flash call per
    layer) must reproduce the sequential KV-cache rollout's cache and
    last-position logits on real hardware. f32 end-to-end (exact-
    comparison tier, like the rest of this file); the bf16 serving
    dtype's kernel behavior is covered by the bf16 flash cases above."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import decoding as dec
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                        num_heads=4, inner_size=512, max_position=512,
                        dropout=0.0)
    d = cfg.hidden_size // cfg.num_heads
    key = jax.random.PRNGKey(0)
    params = {"word_emb": jax.random.normal(
        key, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * 0.02,
        "pos_emb": jax.random.normal(
            jax.random.fold_in(key, 1),
            (cfg.max_position, cfg.hidden_size), jnp.float32) * 0.02,
        "lnf_s": jnp.ones((cfg.hidden_size,)),
        "lnf_b": jnp.zeros((cfg.hidden_size,))}
    for i in range(cfg.num_layers):
        lk = jax.random.fold_in(key, 10 + i)
        m, inner = cfg.hidden_size, cfg.inner_size
        params[f"l{i}"] = {
            "ln1_s": jnp.ones((m,)), "ln1_b": jnp.zeros((m,)),
            "ln2_s": jnp.ones((m,)), "ln2_b": jnp.zeros((m,)),
            "wq": jax.random.normal(lk, (m, m)) * 0.02,
            "wk": jax.random.normal(jax.random.fold_in(lk, 1),
                                    (m, m)) * 0.02,
            "wv": jax.random.normal(jax.random.fold_in(lk, 2),
                                    (m, m)) * 0.02,
            "wo": jax.random.normal(jax.random.fold_in(lk, 3),
                                    (m, m)) * 0.02,
            "bq": jnp.zeros((m,)), "bk": jnp.zeros((m,)),
            "bv": jnp.zeros((m,)), "bo": jnp.zeros((m,)),
            "f0w": jax.random.normal(jax.random.fold_in(lk, 4),
                                     (m, inner)) * 0.02,
            "f0b": jnp.zeros((inner,)),
            "f1w": jax.random.normal(jax.random.fold_in(lk, 5),
                                     (inner, m)) * 0.02,
            "f1b": jnp.zeros((m,)),
        }

    max_len, p = 512, 384
    prompt = jax.random.randint(jax.random.fold_in(key, 99), (2, p),
                                3, cfg.vocab_size, jnp.int32)
    prefill = jax.jit(gpt.build_prefill(params, cfg, max_len))
    got_cache, got_logits = prefill(prompt)

    step = gpt.build_kv_step(params, cfg, max_len)
    cache = dec.init_kv_cache(2, cfg.num_layers, cfg.num_heads, max_len,
                              d)

    def roll(cache, prompt):
        # scan, NOT a python loop: unrolling p sequential steps into
        # one graph would take minutes of TPU compile (this file's own
        # timing test treats that as a hang)
        def body(c, t):
            logits, c = step(jnp.take(prompt, t, axis=1), c, t)
            return c, logits

        cache, logits_seq = jax.lax.scan(body, cache, jnp.arange(p))
        return cache, logits_seq[-1]

    ref_cache, ref_logits = jax.jit(roll)(cache, prompt)
    err = max(
        float(np.max(np.abs(np.asarray(got_cache[i][kv])
                            - np.asarray(ref_cache[i][kv]))))
        for i in range(cfg.num_layers) for kv in ("k", "v"))
    lerr = float(np.max(np.abs(np.asarray(got_logits[:, -1])
                               - np.asarray(ref_logits))))
    tol = 5e-4
    assert err <= tol and lerr <= tol, (err, lerr)


@pytest.mark.parametrize("kgrid", ["0", "1"], ids=["loop", "kgrid"])
def test_flash_variants_seq1024_tpu(kgrid, monkeypatch):
    """Both shipped flash variants — K/V resident with an in-kernel
    loop, and K/V streamed by the grid — forward and backward at the
    GPT training geometry (seq 1024, head_dim 64, causal), each pinned
    by PT_FLASH_KGRID and compared with the XLA oracle."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    monkeypatch.setenv("PT_FLASH_KGRID", kgrid)
    b, h, t, d = 1, 4, 1024, 64
    q, k, v = (_rand((b, h, t, d), s, jnp.float32) for s in (30, 31, 32))
    scale = 1.0 / d ** 0.5

    def floss(q, k, v):
        o = flash.flash_attention(q, k, v, scale=scale, causal=True)
        return jnp.sum(jnp.sin(o)), o

    def oloss(q, k, v):
        o = flash._xla_ref(q, k, v, scale, True)
        return jnp.sum(jnp.sin(o)), o

    (_, got), gf = jax.value_and_grad(floss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    (_, want), go = jax.value_and_grad(oloss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    gerr = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
               for a, b_ in zip(gf, go))
    assert err <= 2e-5, f"max_abs_err {err}"
    assert gerr <= 5e-4, f"max grad err {gerr}"


def test_flash_actually_compiled_not_interpreted():
    """On a real TPU the kernel must take the compiled Mosaic path, not
    the interpreter — otherwise the perf story is fiction."""
    import jax
    from paddle_tpu.ops.pallas import flash

    assert jax.devices()[0].platform == "tpu"
    assert not flash._interpret(), \
        "flash kernel chose interpret mode on TPU"
