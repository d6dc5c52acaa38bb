"""Paged attention v1 / v2 on REAL TPU hardware: each shipped entry —
dense and int8 pools, MHA and GQA — compiled by Mosaic at realistic
geometries (head_dim 64 and 128, block sizes 16 and 32, tables of 64
and 512 blocks, decode C=1 and chunked prefill C=4) and compared with
`serving.kv_cache.paged_attention_reference` on the same chip. The CPU
tier (`tests/ops/test_paged_kernel*.py`) pins the same kernels bitwise
under the interpreter; this tier is the proof they compile and still
agree.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

NULL = 0


def _case(h, hp, c, d, bs, m, dtype, quantized, seed, b=4):
    """Ragged batch in the engine's shape: lane 0 idle (all-NULL table,
    positions 0), the others of mixed lengths — one nearly filling the
    table — with shuffled block assignment. Returns (kernel operands,
    reference operands): the kernel's NULL block is NaN-poisoned and
    must never reach the output; the reference, which gathers every
    table entry and multiplies the masked ones by zero, gets it
    zeroed."""
    import jax.numpy as jnp
    from paddle_tpu.serving import kv_cache as kvc

    rng = np.random.default_rng(seed)
    n = 1 + b * m
    k = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    v = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    k[NULL] = v[NULL] = 0.0
    q = rng.standard_normal((b, h, c, d)).astype(np.float32)
    tables = np.full((b, m), NULL, np.int32)
    pos = np.zeros((b, c), np.int32)
    free = list(range(1, n))
    rng.shuffle(free)
    lengths = [0, m * bs - c, int(rng.integers(1, bs)),
               int(rng.integers(bs, m * bs - c))]
    for i in range(1, b):
        # past the four, lanes of every live-group count of the walk
        length = (lengths[i] if i < len(lengths)
                  else int(rng.integers(bs, m * bs - c)))
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        pos[i] = np.arange(length, length + c)
    tail = [jnp.asarray(tables), jnp.asarray(pos)]
    if quantized:
        kq, ks = kvc.quantize_kv_rows(jnp.asarray(k))
        vq, vs = kvc.quantize_kv_rows(jnp.asarray(v))
        clean = [kvc.fuse_kv(kq, vq)] + tail + [ks, vs]
        # poison lives in the scales: int8 codes cannot hold a NaN
        dirty = clean[:3] + [ks.at[NULL].set(jnp.nan),
                             vs.at[NULL].set(jnp.nan)]
    else:
        kv = kvc.fuse_kv(jnp.asarray(k, dtype), jnp.asarray(v, dtype))
        clean = [kv] + tail
        dirty = [kv.at[NULL].set(jnp.nan)] + tail
    qd = jnp.asarray(q, dtype)
    return [qd] + dirty, [qd] + clean


GEOMETRIES = [
    # h, hp,  c,   d, bs,   m[,  b]
    (12, 12, 4, 64, 16, 64),        # the smoke's: GPT 12x64, defaults
    (25, 25, 16, 64, 16, 64, 16),   # gpt2-xl.closed-16's: 16 lanes
    (12, 12, 1, 64, 16, 64),        # ... decoding
    (8, 2, 4, 128, 32, 64),         # GQA, head_dim 128, wide blocks
    (8, 2, 1, 64, 16, 512),         # GQA, 8k-token table
    (4, 4, 4, 128, 16, 512),        # MHA, head_dim 128, 8k-token table
    (8, 8, 4, 64, 32, 64),
    # solar-open2-250b-ep16.long8k-closed-16's: 64 query heads on 8 KV
    # heads of 128, 16 lanes x 16 columns, an 8k-token table (v2 only)
    (64, 8, 16, 128, 16, 512, 16),
]
POOLS = [("float32", False), ("bfloat16", False), ("bfloat16", True)]
# f32: exact-f32 matmuls on both sides (conftest precision pin);
# bf16 / int8-with-bf16-activations: one bf16 ulp of the O(1) outputs
TOL = {"float32": 2e-4, "bfloat16": 3e-2}


@pytest.mark.parametrize("pool", POOLS,
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("geom", GEOMETRIES,
                         ids=lambda g: "h{}kv{}c{}d{}bs{}m{}".format(*g))
@pytest.mark.parametrize("version", ["v1", "v2"])
def test_paged_kernel_matches_reference_tpu(version, geom, pool):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged
    from paddle_tpu.serving import kv_cache as kvc

    h, hp, c, d, bs, m = geom[:6]
    if version == "v1" and 2 * h * m * bs * d * 4 > 100 << 20:
        pytest.skip("v1's head-repeated K and V of a full table exceed "
                    "VMEM; the dispatcher routes such tables to v2")
    dtype_name, quantized = pool
    b = (geom[6:] or (4,))[0]
    args, clean = _case(h, hp, c, d, bs, m, jnp.dtype(dtype_name),
                        quantized, seed=sum(geom), b=b)
    fn = (paged.ragged_paged_attention if version == "v1"
          else paged.ragged_paged_attention_v2)
    assert not paged._interpret()
    got = jax.jit(fn)(*args)
    # the reference a lane at a time: its dense head-repeated gather of
    # a whole batch of 8k-token tables would not fit the chip
    ref = jax.jit(kvc.paged_attention_reference)
    want = [ref(clean[0][i:i + 1], clean[1], clean[2][i:i + 1],
                clean[3][i:i + 1], *clean[4:]) for i in range(b)]
    assert got.dtype == want[0].dtype
    got = np.asarray(got, np.float32)
    want = np.concatenate([np.asarray(x, np.float32) for x in want])
    assert np.isfinite(got).all(), "NULL-block poison reached the output"
    assert not got[0].any(), "idle lane is not an exact zero"
    err = float(np.max(np.abs(got - want)))
    tol = TOL[dtype_name]
    assert err <= tol, f"max_abs_err {err} > {tol}"


def test_dispatcher_takes_the_kernel_on_tpu(monkeypatch):
    """Auto mode on the chip: the dispatcher routes qualifying operands
    to a kernel that is compiled (interpret gauge 0), picks v1 under
    the VMEM ceiling and v2 past it, and records no fallback."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PAGED_V2_AUTO_BYTES", raising=False)
    for geom, want_version in (((12, 12, 4, 64, 16, 64), "v1"),
                               ((12, 12, 4, 64, 16, 512), "v2")):
        args, clean = _case(*geom, jnp.bfloat16, False, seed=5)
        f0 = kvc.FALLBACK_DISPATCHES
        v0 = dict(kvc.KERNEL_VERSIONS)
        got = jax.jit(kvc.paged_attention)(*args)
        want = jax.jit(kvc.paged_attention_reference)(*clean)
        assert kvc.FALLBACK_DISPATCHES == f0
        assert kvc.KERNEL_VERSIONS.get(want_version, 0) == \
            v0.get(want_version, 0) + 1
        assert global_registry().gauge(
            "serving.kernel.interpret").value() == 0
        err = float(np.max(np.abs(np.asarray(got, np.float32)
                                  - np.asarray(want, np.float32))))
        assert err <= TOL["bfloat16"], err


# ---------------------------------------------------------------------------
# the write: whole blocks, in place (ISSUE 26)
# ---------------------------------------------------------------------------

def _write_case(cache, b, c, seed):
    """Lanes of a step: one idle, one whose chunk straddles two blocks,
    the rest anywhere; columns past each lane's count masked to
    (NULL, 0) as the fused step routes them."""
    rng = np.random.default_rng(seed)
    bs = cache.block_size
    m = (cache.num_blocks - 1) // b
    tables = rng.permutation(np.arange(1, cache.num_blocks))[
        :b * m].reshape(b, m)
    pos0 = rng.integers(0, m * bs - c, b)
    pos0[1] = bs - 1                        # wraps after one column
    count = rng.integers(1, c + 1, b)
    count[0], count[1] = 0, c
    valid = np.arange(c)[None] < count[:, None]
    pos = np.where(valid, pos0[:, None] + np.arange(c)[None], 0)
    bidx = np.where(valid, np.take_along_axis(tables, pos // bs, 1), NULL)
    return (bidx.astype(np.int32), np.where(valid, pos % bs, 0).astype(
        np.int32))


@pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8"])
@pytest.mark.parametrize("geom", [(16, 16, 16, 64, 25), (4, 1, 16, 64, 12),
                                  (3, 4, 32, 128, 8), (2, 16, 8, 64, 4)],
                         ids=lambda g: "b{}c{}bs{}d{}h{}".format(*g))
def test_block_write_matches_row_scatter_tpu(geom, kv_dtype):
    """`write_block_kv` / `_quant` on the chip, over a `PagedKVCache`'s
    pools, donated: every real block equals what a scatter of single
    rows gives, and the pools handed in are consumed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import kv_cache as kvc

    b, c, bs, d, h = geom
    cache = kvc.PagedKVCache(1, h, d, 1 + 6 * b, block_size=bs,
                             dtype=jnp.float32, kv_dtype=kv_dtype)
    layer = cache.pools[0]
    rng = np.random.default_rng(sum(geom))
    seeded = {name: jnp.asarray(
        rng.integers(-100, 100, a.shape), a.dtype) for name, a in
        layer.items()}
    k, v = (jnp.asarray(rng.standard_normal((b, c, h, d)), jnp.float32)
            for _ in range(2))
    bidx, off = _write_case(cache, b, c, seed=sum(geom) + 1)

    def write(p, k, v):
        if cache.quantized:
            kv, ks, vs = kvc.write_block_kv_quant(
                p["kv"], p["k_scale"], p["v_scale"], k, v, bidx, off)
            return dict(kv=kv, k_scale=ks, v_scale=vs)
        return dict(kv=kvc.write_block_kv(p["kv"], kvc.fuse_kv(k, v),
                                          bidx, off))

    def rows(p, k, v):
        if cache.quantized:
            (kq, ks), (vq, vs) = (kvc.quantize_kv_rows(x) for x in (k, v))
            return dict(
                kv=p["kv"].at[bidx, :, off, :].set(kvc.fuse_kv(kq, vq)),
                k_scale=p["k_scale"].at[bidx, :, off].set(ks),
                v_scale=p["v_scale"].at[bidx, :, off].set(vs))
        return dict(kv=p["kv"].at[bidx, :, off, :].set(
            kvc.fuse_kv(k, v).astype(p["kv"].dtype)))

    want = jax.jit(rows)(seeded, k, v)
    got = jax.jit(write, donate_argnums=(0,))(seeded, k, v)
    assert all(a.is_deleted() for a in seeded.values())
    for name in layer:
        np.testing.assert_array_equal(np.asarray(got[name])[1:],
                                      np.asarray(want[name])[1:], name)
