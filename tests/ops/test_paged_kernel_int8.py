"""Int8 quantized-pool path of the Pallas ragged paged attention
kernel (ISSUE 14): the kernel's fused dequant vs the pure-JAX
reference's int8 branch.

Contract (extends tests/ops/test_paged_kernel.py):

- int8 pools + (N, H, bs) f32 scale pools: kernel output is
  BITWISE-identical to `paged_attention_reference` under jit for
  chunked prefill, decode, ragged mixed-length batches and NULL-padded
  tables — the kernel mirrors the reference's dequant -> f32 score ->
  softmax -> compute-dtype PV sequence on its VMEM-resident gather;
- the output dtype follows the QUERY dtype (the model's activation
  dtype), not the int8 pool dtype;
- quantize-at-write (quantize_kv_rows / write_block_kv_quant) bounds
  the dequant error at the int8 resolution per row;
- the NULL block is never read: NaN-poisoned scale rows in block 0
  change nothing (an int8 pool cannot hold NaN — the scales carry the
  poison, mirroring the engine's chaos hook);
- dispatch: auto mode routes int8 pools to the kernel; int8 pools
  without scales never reach it (the reference raises the friendly
  error instead of serving garbage).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged
from paddle_tpu.serving import kv_cache as kvc

pytestmark = [pytest.mark.pallas, pytest.mark.quant]


def make_case(qdt=jnp.float32, b=3, h=2, c=4, d=8, bs=8, m=6, seed=0,
              poison_null_scale=False):
    """Ragged int8 batch: float pools quantized row-wise through the
    REAL write-path helper, shuffled tables, NULL padding. Returns
    (args tuple with scales, float pools for accuracy baselines)."""
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    kf = rng.standard_normal((n, h, bs, d)).astype(np.float32)
    vf = rng.standard_normal((n, h, bs, d)).astype(np.float32)
    kf[kvc.NULL_BLOCK] = 0.0
    vf[kvc.NULL_BLOCK] = 0.0
    kq, ks = kvc.quantize_kv_rows(jnp.asarray(kf))
    vq, vs = kvc.quantize_kv_rows(jnp.asarray(vf))
    if poison_null_scale:
        ks = ks.at[kvc.NULL_BLOCK].set(jnp.nan)
        vs = vs.at[kvc.NULL_BLOCK].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((b, h, c, d)), qdt)
    tables = np.full((b, m), kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    free = list(range(1, n))
    rng.shuffle(free)
    for i in range(b):
        length = int(rng.integers(1, m * bs - c))
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        q_pos[i] = np.arange(length, length + c)
    args = (q, kvc.fuse_kv(kq, vq), jnp.asarray(tables),
            jnp.asarray(q_pos), ks, vs)
    return args, (kf, vf)


def _run_both(args):
    ref = jax.jit(kvc.paged_attention_reference)(*args)
    out = jax.jit(paged.ragged_paged_attention)(*args)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


# ---------------------------------------------------------------------------
# bitwise pins (int8 pools, f32 and bf16 compute)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(),                                       # chunked prefill C=4
    dict(c=1, seed=1),                            # decode C=1
    dict(b=5, h=3, c=3, d=5, bs=4, m=9, seed=7),  # odd, ragged
    dict(qdt=jnp.bfloat16, seed=2),               # bf16 activations
    dict(qdt=jnp.bfloat16, c=1, seed=3),
], ids=["prefill", "decode", "ragged_odd", "bf16_prefill",
        "bf16_decode"])
def test_int8_kernel_bitwise_matches_reference(case):
    args, _ = make_case(**case)
    out, ref = _run_both(args)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("c", [1, 4], ids=["decode", "prefill"])
def test_int8_grouped_walk_bf16_activations_bitwise(c):
    """A table of several groups (384 keys, lanes of 0 to 3 live
    groups), int8 pools under bf16 activations: the codes are
    dequantised as each block of a live group lands, and each lane is
    bitwise the reference on its table cut to its live groups."""
    from test_paged_kernel import cut_reference, make_walk_case
    args = make_walk_case(kind="int8", c=c, bs=16, m=24, seed=21 + c)
    args = (args[0].astype(jnp.bfloat16),) + args[1:]
    out = jax.jit(paged.ragged_paged_attention)(*args)
    assert out.dtype == jnp.bfloat16
    out = np.asarray(out, np.float32)
    np.testing.assert_array_equal(
        out, np.asarray(cut_reference(args), np.float32))
    assert not out[0].any() and out[1:].any()


def test_int8_output_dtype_follows_query():
    for qdt in (jnp.float32, jnp.bfloat16):
        args, _ = make_case(qdt=qdt, seed=4)
        assert paged.ragged_paged_attention(*args).dtype == qdt
        assert kvc.paged_attention_reference(*args).dtype == qdt


# ---------------------------------------------------------------------------
# accuracy: quantized attention tracks dense attention
# ---------------------------------------------------------------------------

def test_int8_attention_close_to_dense():
    """Dequantized attention must track the dense-f32 pools' output at
    int8 resolution — the op-level accuracy bound behind the serving
    exact-match-rate pin (per-row absmax keeps the worst-case rounding
    at scale/2 ~= absmax/254 per element)."""
    args, (kf, vf) = make_case(seed=5)
    q, _kvq, tables, q_pos, _ks, _vs = args
    out = np.asarray(jax.jit(paged.ragged_paged_attention)(*args))
    dense = np.asarray(jax.jit(kvc.paged_attention_reference)(
        q, kvc.fuse_kv(jnp.asarray(kf), jnp.asarray(vf)), tables, q_pos))
    np.testing.assert_allclose(out, dense, rtol=0.05, atol=0.02)


def test_quantize_kv_rows_roundtrip_bound():
    rng = np.random.default_rng(6)
    x = rng.standard_normal((5, 3, 4, 16)).astype(np.float32) * \
        rng.uniform(0.01, 10, (5, 3, 4, 1)).astype(np.float32)
    q, s = kvc.quantize_kv_rows(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    # worst case half a quantization step per element, per ROW scale
    bound = np.abs(x).max(-1, keepdims=True) / 127.0 * 0.5 + 1e-7
    assert (np.abs(back - x) <= bound).all()
    # all-zero rows stay exactly zero with a benign scale
    qz, sz = kvc.quantize_kv_rows(jnp.zeros((2, 3)))
    assert np.asarray(sz).min() == 1.0
    assert not np.asarray(qz).any()


def test_write_block_kv_quant_addresses_both_pools():
    """A written row's K and V codes land side by side, and their two
    scales, at the SAME (block, row) address, and reading them back
    dequantizes to the written values within the int8 bound."""
    cache = kvc.PagedKVCache(1, 2, 8, 6, block_size=4,
                             dtype=jnp.float32, kv_dtype="int8")
    rng = np.random.default_rng(7)
    k, v = (jnp.asarray(rng.standard_normal((1, 4, 2, 8)), jnp.float32)
            for _ in range(2))
    bidx = np.full((1, 4), 3, np.int32)
    off = np.arange(4, dtype=np.int32)[None, :]
    p = cache.pools[0]
    kvp, ks, vs = kvc.write_block_kv_quant(
        p["kv"], p["k_scale"], p["v_scale"], k, v, bidx, off)
    for codes, scales, vals in zip(kvc.split_kv(kvp[3]),
                                   (ks[3], vs[3]), (k, v)):
        back = (np.asarray(codes, np.float32)
                * np.asarray(scales)[..., None])        # (H, bs, D)
        want = np.asarray(vals[0]).transpose(1, 0, 2)   # (H, C=bs, D)
        np.testing.assert_allclose(back, want,
                                   atol=np.abs(want).max() / 64)
    # untouched blocks keep the benign init scale
    assert np.asarray(ks[2]).min() == np.asarray(vs[2]).min() == 1.0


# ---------------------------------------------------------------------------
# NULL block is never read (scales carry the poison for int8)
# ---------------------------------------------------------------------------

def test_null_scale_poison_stays_finite():
    args_p, _ = make_case(seed=8, poison_null_scale=True)
    out = np.asarray(jax.jit(paged.ragged_paged_attention)(*args_p),
                     np.float32)
    assert np.isfinite(out).all()
    args_c, _ = make_case(seed=8, poison_null_scale=False)
    np.testing.assert_array_equal(
        out, np.asarray(jax.jit(paged.ragged_paged_attention)(*args_c),
                        np.float32))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def test_dispatch_auto_routes_int8_to_kernel(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    args, _ = make_case(seed=9)
    k0 = kvc.KERNEL_DISPATCHES
    out = jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert kvc.KERNEL_DISPATCHES == k0 + 1
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.jit(kvc.paged_attention_reference)(*args)))


def test_int8_without_scales_is_unsupported(monkeypatch):
    """paged_kernel_supported refuses int8 pools without their scale
    pools (codes alone are meaningless), force mode raises the
    dispatcher's message, and the kernel itself validates too."""
    args, _ = make_case(seed=10)
    q, kvq, tables, q_pos, ks, vs = args
    assert kvc.paged_kernel_supported(q, kvq, ks, vs)
    assert not kvc.paged_kernel_supported(q, kvq)
    assert not kvc.paged_kernel_supported(q, kvq, ks, None)
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kvq, tables, q_pos)
    with pytest.raises(ValueError, match="scale"):
        paged.ragged_paged_attention(q, kvq, tables, q_pos)
    # scales with FLOAT pools are a caller bug, not a silent no-op —
    # on EVERY path: the kernel entry point, the reference (so a
    # PADDLE_TPU_PAGED_KERNEL=0 dev loop cannot silently drop scales
    # a TPU run would reject), and the pinned-off dispatcher
    argsf = (q.astype(jnp.float32), kvq.astype(jnp.float32))
    with pytest.raises(ValueError, match="scale"):
        paged.ragged_paged_attention(*argsf, tables, q_pos, ks, vs)
    with pytest.raises(ValueError, match="scale"):
        kvc.paged_attention_reference(*argsf, tables, q_pos, ks, vs)
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    with pytest.raises(ValueError, match="scale"):
        kvc.paged_attention(*argsf, tables, q_pos, ks, vs)


def test_int8_scale_shape_validated():
    args, _ = make_case(seed=11)
    q, kvq, tables, q_pos, ks, vs = args
    with pytest.raises(ValueError, match="scale pools"):
        paged.ragged_paged_attention(q, kvq, tables, q_pos,
                                     ks[:, :, :-1], vs)
