"""Paged attention kernel v2 (double-buffered block streaming + online
softmax) and grouped-query attention, op level (ISSUE 16).

Contract (extends tests/ops/test_paged_kernel.py):

- v2's online softmax is mathematically EXACT but reorders the
  reference's one-pass fp reductions (per-block partial sums, running
  rescales), so the pin is tight-allclose at f32 resolution PLUS
  argmax-identical probabilities — v1 remains the bitwise kernel and
  its pins do not move;
- scores/softmax/PV accumulate in f32 for every pool dtype (bf16 and
  int8 included), output cast once at the end;
- the white-box VMEM contract: `_v2_scratch_shapes` is one group of
  key positions (K beside V at H_kv heads) plus the carry of each KV
  head's query rows, and NO dimension depends on the table width M —
  that independence IS the unbounded-context claim;
- GQA (H_kv < H): the reference on (N, H_kv, bs, D) pools is BITWISE
  the reference on repeat-KV dense (N, H, bs, D) pools under jit (the
  repeat is a pure copy), v1 inherits its bitwise pin through the same
  repeat, v2 stays in its allclose envelope scoring each KV head's
  query heads as one tile, without ever materializing the repeat;
- the NULL block is never read by v2 either: NaN-poison changes
  nothing, bitwise (the zero-filled slots make a skipped DMA's
  0-probability product an exact 0, not NaN);
- dispatch: PADDLE_TPU_PAGED_KERNEL grows v1/v2 generation pins, auto
  routes past the v1 VMEM ceiling to v2, and every kernel dispatch
  lands a version label + the serving.kernel.version gauge.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged
from paddle_tpu.serving import kv_cache as kvc

pytestmark = pytest.mark.pallas


def make_case(dtype=jnp.float32, b=3, h=4, hp=None, c=4, d=8, bs=8, m=6,
              seed=0, poison=False, idle_lane=False):
    """test_paged_kernel.make_case with a GQA knob: pools carry hp
    (default h) heads while q keeps h — query head j reads KV head
    j // (h // hp), the contiguous-group convention."""
    hp = hp or h
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    k_pool = rng.standard_normal((n, hp, bs, d)).astype(dtype)
    v_pool = rng.standard_normal((n, hp, bs, d)).astype(dtype)
    fill = np.nan if poison else 0.0
    k_pool[kvc.NULL_BLOCK] = fill
    v_pool[kvc.NULL_BLOCK] = fill
    q = rng.standard_normal((b, h, c, d)).astype(dtype)
    tables = np.full((b, m), kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    free = list(range(1, n))
    rng.shuffle(free)
    for i in range(b):
        if idle_lane and i == 0:
            continue
        length = int(rng.integers(1, m * bs - c))
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        q_pos[i] = np.arange(length, length + c)
    return (jnp.asarray(q),
            kvc.fuse_kv(jnp.asarray(k_pool), jnp.asarray(v_pool)),
            jnp.asarray(tables), jnp.asarray(q_pos))


def make_case_int8(b=3, h=4, hp=None, c=4, d=8, bs=8, m=6, seed=0,
                   qdt=jnp.float32):
    """Int8 variant through the real quantize-at-write helper
    (test_paged_kernel_int8 idiom), with the same GQA knob."""
    hp = hp or h
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    kf = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    vf = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    kf[kvc.NULL_BLOCK] = 0.0
    vf[kvc.NULL_BLOCK] = 0.0
    kq, ks = kvc.quantize_kv_rows(jnp.asarray(kf))
    vq, vs = kvc.quantize_kv_rows(jnp.asarray(vf))
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
    return (q, kvc.fuse_kv(kq, vq), jnp.asarray(tables),
            jnp.asarray(q_pos), ks, vs)


def _assert_v2_close(args, rtol=1e-5, atol=1e-6):
    """The v2 pin: tight allclose against the jitted reference PLUS
    argmax-identical outputs per (lane, head, column) — the decode
    decision a serving stream actually takes."""
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                     np.float32)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args),
                     np.float32)
    np.testing.assert_allclose(out, ref, rtol=rtol, atol=atol)
    np.testing.assert_array_equal(out.argmax(-1), ref.argmax(-1))
    return out, ref


# ---------------------------------------------------------------------------
# v2 vs reference: the adversarial matrix (f32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(),                                      # chunked prefill C=4
    dict(c=1, seed=1),                           # decode C=1
    dict(b=5, h=3, c=3, d=5, bs=4, m=9, seed=7),  # odd, ragged
    dict(b=2, h=1, c=2, d=16, bs=16, m=3, seed=9),
    dict(idle_lane=True, seed=11),               # all-NULL masked lane
], ids=["prefill", "decode", "ragged_odd", "wide_block", "idle_lane"])
def test_v2_allclose_matches_reference_f32(case):
    _assert_v2_close(make_case(**case))


def test_v2_idle_lane_is_exact_zero():
    """An idle lane ends the stream with l == 0; the safe divide must
    land an exact 0 output, never NaN (the engine's non-finite-logits
    guard sums EVERY lane's logps, idle ones included)."""
    args = make_case(idle_lane=True, seed=11)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args))
    assert np.isfinite(out).all()
    assert not out[0].any()


def test_v2_output_dtype_follows_the_pool():
    assert paged.ragged_paged_attention_v2(
        *make_case()).dtype == jnp.float32
    assert paged.ragged_paged_attention_v2(
        *make_case(dtype=jnp.bfloat16)).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# bf16 / int8: f32 accumulation everywhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [4, 1], ids=["prefill", "decode"])
def test_v2_bf16_allclose(c):
    args = make_case(dtype=jnp.bfloat16, c=c, seed=2)
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                     np.float32)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args),
                     np.float32)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.quant
@pytest.mark.parametrize("case", [
    dict(),
    dict(c=1, seed=1),
    dict(b=5, h=3, c=3, d=5, bs=4, m=9, seed=7),
    dict(qdt=jnp.bfloat16, seed=2),
], ids=["prefill", "decode", "ragged_odd", "bf16_activations"])
def test_v2_int8_allclose(case):
    """int8 pools stream as (codes, scales) pairs with the dequant on
    the VMEM-resident slot. v2's f32 accumulation vs the reference's
    dequant-then-one-pass math: tight at f32 resolution for f32
    activations, bf16 envelope otherwise."""
    args = make_case_int8(**case)
    qdt = case.get("qdt", jnp.float32)
    tol = dict(rtol=2e-2, atol=2e-2) if qdt == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-5)
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                     np.float32)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args),
                     np.float32)
    np.testing.assert_allclose(out, ref, **tol)
    assert paged.ragged_paged_attention_v2(*args).dtype == qdt


# ---------------------------------------------------------------------------
# NULL block is never read (v2 skips the DMA on both issue and wait)
# ---------------------------------------------------------------------------

def test_v2_null_block_poison_stays_finite():
    args = make_case(seed=3, poison=True)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args))
    assert np.isfinite(out).all()
    clean = make_case(seed=3, poison=False)
    np.testing.assert_array_equal(
        out,
        np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*clean)))


# ---------------------------------------------------------------------------
# white-box: the O(2-block) VMEM contract
# ---------------------------------------------------------------------------

def test_v2_scratch_is_m_independent():
    """The streaming claim, pinned structurally: v2's VMEM scratch is
    one group of key positions and the online-softmax carry — no
    dimension involves the table width M (the function cannot even be
    passed one). v1's scratch by contrast scales linearly with M."""
    h, hp, c, d, t = 16, 2, 4, 16, 128
    rows = (h // hp) * c                # a KV head's query rows
    carry = [((hp, rows, 1), jnp.float32), ((hp, rows, 1), jnp.float32),
             ((hp, rows, d), jnp.float32)]
    assert paged._v2_scratch_shapes(h, hp, c, d, t, jnp.bfloat16) == [
        ((hp, t, 2 * d), jnp.bfloat16)] + carry
    # int8 blocks are dequantized as they land: the group sits in f32
    assert paged._v2_scratch_shapes(h, hp, c, d, t, jnp.int8) == [
        ((hp, t, 2 * d), jnp.float32)] + carry
    # the group is 128 key positions however wide the table is
    assert paged.walk_group(16, 24) * 16 == paged.walk_group(
        16, 512) * 16 == t
    narrow = paged._v1_scratch_shapes(2, 8, d, 6, jnp.bfloat16)
    wide = paged._v1_scratch_shapes(2, 8, d, 24, jnp.bfloat16)
    # one scratch, K beside V, in the pool's dtype (f32 for int8 codes,
    # which are dequantized as they land)
    assert narrow == [((2, 6 * 8, 2 * d), jnp.bfloat16)]
    assert paged._v1_scratch_shapes(2, 8, d, 6, jnp.int8) == [
        ((2, 6 * 8, 2 * d), jnp.float32)]
    assert [s[0][1] for s in wide] == [4 * s[0][1] for s in narrow]
    # and the dispatcher's v1 estimate DOES scale with M — the gap auto
    # mode routes on
    _q, kv_pool, tables, _p = make_case(m=6)
    wide = jnp.concatenate([tables] * 4, axis=1)
    assert kvc._v1_scratch_bytes(kv_pool, wide) == \
        4 * kvc._v1_scratch_bytes(kv_pool, tables)
    # at head_dim 64 the fused minor dim is the 128 lanes: the estimate
    # is the bytes themselves, nothing padded (the GPT-2 XL cell's
    # scratch: 25 heads x 1,024 tokens of bf16, 6.5 MB, under the
    # ceiling that keeps it on v1)
    cell = jnp.zeros((2, 25, 16, 128), jnp.bfloat16)
    assert kvc._v1_scratch_bytes(cell, jnp.zeros((16, 64), jnp.int32)) \
        == 25 * 1024 * 128 * 2 < kvc.V2_AUTO_VMEM_BYTES


def test_v2_wide_table_same_answer():
    """Functionally M-independent: widening a table that holds a whole
    group (128 key positions) with NULL padding (the shape a
    long-context pool geometry produces) changes nothing — v2 walks the
    same live groups of the same width; the dead ones are no step. (A
    table narrower than a group is walked in one group of its own
    width, whose sums run over fewer keys.)"""
    q, kv_pool, tables, pos = make_case(seed=4, bs=16, m=8)
    pad = jnp.full((tables.shape[0], 26), kvc.NULL_BLOCK, jnp.int32)
    wide = jnp.concatenate([tables, pad], axis=1)
    out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(
        q, kv_pool, tables, pos))
    out_w = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(
        q, kv_pool, wide, pos))
    np.testing.assert_array_equal(out, out_w)


@pytest.mark.parametrize("kind", ["f32", "int8", "gqa"])
def test_v2_steps_only_through_live_blocks(kind):
    """v2 shares v1's plan of the walk and its group of 128 key
    positions: a step is a live group of a lane, so lanes of 0 (idle),
    1, 9, 19, 23 and all 24 blocks of a 384-key table take 0 (one step,
    to write its zeros), 1, 2, 3, 3 and 3 steps, and NaN in the NULL
    block and in every stale column reaches nothing."""
    from test_paged_kernel import make_walk_case
    args = make_walk_case(kind=kind, bs=16, m=24, seed=23)
    steps, plan = paged._plan_walk(args[2], args[3], 16,
                                   paged.walk_group(16, 24))
    groups = np.asarray(plan[4])
    np.testing.assert_array_equal(groups, [0, 3, 1, 2, 3, 3])
    assert int(steps) == groups.sum() + 1       # the idle lane's one
    out, _ref = _assert_v2_close(args, rtol=1e-4 if kind == "int8" else 1e-5,
                                 atol=1e-5 if kind == "int8" else 1e-6)
    assert not out[0].any()
    dirty = make_walk_case(kind=kind, bs=16, m=24, seed=23, poison=True)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*dirty),
                   np.float32), out)


# ---------------------------------------------------------------------------
# grouped-query attention, op level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("c", [4, 1], ids=["prefill", "decode"])
def test_gqa_v2_group_walk_at_the_cells_head_ratio(c, kind):
    """The serving cell's head ratio (8 query heads a KV head, scored as
    one tile against a group's 128 keys) on 16-token blocks: lanes that
    end in the first group, mid-group, on a group's last key and on the
    next group's first stay in v2's envelope (f32: allclose +
    argmax-identical; bf16 q and pool: the cell's dtypes), the idle
    lane is an exact zero, and NaN in the NULL block and the dead
    columns of a partly live last group changes nothing, bitwise."""
    from test_paged_kernel import make_walk_case
    case = dict(kind=kind, c=c, h=16, hp=2, d=16, seed=31 + c,
                lengths=[None, 5, 200 - c, 256 - c, 257 - c])
    args = make_walk_case(**case)
    steps, plan = paged._plan_walk(args[2], args[3], 16,
                                   paged.walk_group(16, 24))
    np.testing.assert_array_equal(np.asarray(plan[4]), [0, 1, 2, 2, 3])
    assert int(steps) == 9
    if kind == "f32":
        out, _ref = _assert_v2_close(args)
    else:
        tol = dict(rtol=2e-2, atol=2e-2) if kind == "bf16" else \
            dict(rtol=1e-4, atol=1e-5)
        ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                         np.float32)
        out = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args),
                         np.float32)
        np.testing.assert_allclose(out, ref, **tol)
    assert not out[0].any()
    dirty = make_walk_case(poison=True, **case)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*dirty),
                   np.float32), out)


def _repeat_pools(args, g):
    """The repeat-KV dense equivalent: pools (and scales) expanded to
    one KV head per query head — the bitwise reference for GQA."""
    q, kv_pool, tables, pos = args[:4]
    rep = (q, jnp.repeat(kv_pool, g, axis=1), tables, pos)
    if len(args) > 4:
        rep += (jnp.repeat(args[4], g, axis=1),
                jnp.repeat(args[5], g, axis=1))
    return rep


@pytest.mark.parametrize("hp", [2, 1], ids=["group2", "mqa"])
def test_gqa_reference_bitwise_matches_repeat_kv_dense(hp):
    """The GQA ground truth: the reference on H_kv pools IS the
    reference on repeat-KV dense pools, bitwise under jit — gathering
    then repeating equals gathering the pre-repeated pool (pure
    copies), and every op after the repeat is identical."""
    args = make_case(h=4, hp=hp, seed=13)
    out = np.asarray(jax.jit(kvc.paged_attention_reference)(*args))
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(
        *_repeat_pools(args, 4 // hp)))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("case", [
    dict(h=4, hp=2, seed=13),
    dict(h=4, hp=1, c=1, seed=14),                   # MQA decode
    dict(h=6, hp=3, b=2, c=3, d=5, bs=4, m=5, seed=15),
    dict(h=4, hp=2, idle_lane=True, seed=16),
], ids=["group2", "mqa_decode", "odd_group", "idle_lane"])
def test_gqa_v1_bitwise_matches_reference(case):
    """v1 repeats the gathered rows across each group — pure copies, so
    the bitwise pin extends to GQA unchanged."""
    args = make_case(**case)
    out = np.asarray(jax.jit(paged.ragged_paged_attention)(*args))
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("case", [
    dict(h=4, hp=2, seed=13),
    dict(h=4, hp=1, c=1, seed=14),
    dict(h=6, hp=3, b=2, c=3, d=5, bs=4, m=5, seed=15),
    dict(h=4, hp=2, idle_lane=True, seed=16),
], ids=["group2", "mqa_decode", "odd_group", "idle_lane"])
def test_gqa_v2_allclose_matches_reference(case):
    """v2 batches its einsums (H_kv, group, ...) against the
    un-repeated streamed block — no repeat ever materializes — and
    stays in the same allclose envelope as MHA."""
    _assert_v2_close(make_case(**case))


@pytest.mark.quant
def test_gqa_int8_both_kernels():
    """int8 + GQA compose: the scale pools shrink with the data pools
    and the dequant-then-repeat ordering keeps v1 bitwise."""
    args = make_case_int8(h=4, hp=2, seed=17)
    ref = np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                     np.float32)
    out1 = np.asarray(jax.jit(paged.ragged_paged_attention)(*args),
                      np.float32)
    np.testing.assert_array_equal(out1, ref)
    out2 = np.asarray(jax.jit(paged.ragged_paged_attention_v2)(*args),
                      np.float32)
    np.testing.assert_allclose(out2, ref, rtol=1e-4, atol=1e-5)


def test_gqa_bad_head_geometry_raises():
    """H_kv must divide H, on every entry point: both kernels' shared
    validator, the reference, and paged_kernel_supported (so the
    dispatcher degrades instead of tracing garbage)."""
    args = make_case(h=4, hp=2, seed=13)
    q, kv_pool, tables, pos = args
    bad_q = q[:, :3]                       # h=3 not a multiple of hp=2
    for fn in (paged.ragged_paged_attention,
               paged.ragged_paged_attention_v2):
        with pytest.raises(ValueError, match="multiple of pool heads"):
            fn(bad_q, kv_pool, tables, pos)
    with pytest.raises(ValueError, match="multiple of pool heads"):
        kvc.paged_attention_reference(bad_q, kv_pool, tables, pos)
    assert not kvc.paged_kernel_supported(bad_q, kv_pool)
    # more pool heads than query heads is just as dead
    assert not kvc.paged_kernel_supported(q[:, :1], kv_pool)


# ---------------------------------------------------------------------------
# dispatch: generation pins, the auto VMEM ceiling, version metrics
# ---------------------------------------------------------------------------

def test_dispatch_v2_mode_pins_streaming_kernel(monkeypatch):
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "v2")
    args = make_case(seed=6)
    k0, t0 = kvc.KERNEL_DISPATCHES, paged.V2_TRACE_COUNT
    v0 = kvc.KERNEL_VERSIONS.get("v2", 0)
    lbl = reg.counter("serving.kernel.traced").labels(version="v2")
    c0 = lbl.value()
    out = jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert kvc.KERNEL_DISPATCHES == k0 + 1
    assert paged.V2_TRACE_COUNT == t0 + 1
    assert kvc.KERNEL_VERSIONS["v2"] == v0 + 1
    assert lbl.value() == c0 + 1
    assert reg.gauge("serving.kernel.version").value() == 2
    assert kvc.kernel_dispatch_stats()["kernel_versions"]["v2"] == \
        kvc.KERNEL_VERSIONS["v2"]
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(jax.jit(kvc.paged_attention_reference)(*args),
                   np.float32), rtol=1e-5, atol=1e-6)


def test_dispatch_v1_mode_pins_gather_kernel(monkeypatch):
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "v1")
    args = make_case(seed=6)
    t0 = paged.V2_TRACE_COUNT
    v0 = kvc.KERNEL_VERSIONS.get("v1", 0)
    out = jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert paged.V2_TRACE_COUNT == t0        # v2 never traced
    assert kvc.KERNEL_VERSIONS["v1"] == v0 + 1
    assert reg.gauge("serving.kernel.version").value() == 1
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.jit(kvc.paged_attention_reference)(*args)))


def test_dispatch_auto_routes_on_vmem_ceiling(monkeypatch):
    """auto keeps bitwise v1 while the full-table gather fits the
    ceiling and streams via v2 past it. The ceiling is the env-tunable
    PADDLE_TPU_PAGED_V2_AUTO_BYTES (default V2_AUTO_VMEM_BYTES)."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    args = make_case(seed=6)
    _q, kv_pool, tables, _p = args
    assert kvc._kernel_version_for("auto", kv_pool, tables) == "v1"
    monkeypatch.setenv("PADDLE_TPU_PAGED_V2_AUTO_BYTES", "1")
    assert kvc._v2_auto_vmem_bytes() == 1
    assert kvc._kernel_version_for("auto", kv_pool, tables) == "v2"
    t0 = paged.V2_TRACE_COUNT
    jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert paged.V2_TRACE_COUNT == t0 + 1
    monkeypatch.delenv("PADDLE_TPU_PAGED_V2_AUTO_BYTES", raising=False)
    assert kvc._v2_auto_vmem_bytes() == kvc.V2_AUTO_VMEM_BYTES
    t1 = paged.V2_TRACE_COUNT
    jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert paged.V2_TRACE_COUNT == t1        # back under the ceiling


@pytest.mark.parametrize("env", ["v1", "v2"])
def test_dispatch_generation_pin_degrades_on_unsupported(monkeypatch,
                                                         env):
    """Explicit generation pins follow auto's discipline on
    non-qualifying operands — labeled fallback, never a raise (only
    force mode raises)."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", env)
    q, kv_pool, tables, pos = make_case(seed=6)
    f0 = kvc.FALLBACK_DISPATCHES
    out = kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables,
                              pos)
    assert kvc.FALLBACK_DISPATCHES == f0 + 1
    assert out.dtype == jnp.float16
    assert kvc.kernel_dispatch_stats()["mode"] == env


def test_dispatch_fallback_carries_reference_version_label(monkeypatch):
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    lbl = reg.counter("serving.kernel.fallback").labels(
        version="reference")
    c0 = lbl.value()
    kvc.paged_attention(*make_case(seed=6))
    assert lbl.value() == c0 + 1
    assert reg.gauge("serving.kernel.version").value() == 0


def test_bad_env_message_names_all_modes(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "v3")
    with pytest.raises(ValueError, match="expected 0, 1, auto, v1 "
                                         "or v2"):
        kvc.paged_kernel_mode()


def test_v2_lazy_export():
    import paddle_tpu.ops.pallas as pk
    assert pk.ragged_paged_attention_v2 is \
        paged.ragged_paged_attention_v2


# ---------------------------------------------------------------------------
# the windowed walk over a ring a lane
# ---------------------------------------------------------------------------

def make_ring_case(lengths, c, window, dtype=jnp.float32, h=4, hp=2, d=8,
                   bs=8, m=16, seed=0):
    """Lanes that wrote positions 0 .. length + c - 1 (None: idle) into
    a ring of R = ring_blocks_for(window, c, bs) blocks each, later
    positions over earlier ones, the ring's never-written rows holding
    1e4 (a stale key that a walk must not weigh). Returns (the walk's
    operands over the ring, each lane's logical (K, V) by position, the
    lanes' query positions)."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    ring = kvc.ring_blocks_for(window, c, bs)
    pool = np.full((1 + b * ring, hp, bs, 2 * d), 1e4, np.float32)
    tables = np.full((b, m), kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    keys = []
    for i, length in enumerate(lengths):
        if length is None:
            keys.append(None)
            continue
        kv = rng.standard_normal((length + c, hp, 2 * d))
        for p in range(length + c):
            pool[1 + i * ring + (p // bs) % ring, :, p % bs] = kv[p]
        tables[i, :-(-(length + c) // bs)] = 1 + rng.integers(
            0, 999, -(-(length + c) // bs))
        q_pos[i] = np.arange(length, length + c)
        keys.append(kv)
    q = rng.standard_normal((b, h, c, d))
    ring_tbl = kvc.ring_table(jnp.asarray(tables), ring)
    return ((jnp.asarray(q, dtype), jnp.asarray(pool, dtype), ring_tbl,
             jnp.asarray(q_pos)), keys, q_pos)


def _window_dense(q, keys, q_pos, window, h, hp, d):
    """Attention of each query over ITS window of the logical keys,
    float64: key j visible to position i iff 0 <= i - j < window."""
    q = np.asarray(q, np.float64)
    out = np.zeros(q.shape)
    for b, kv in enumerate(keys):
        if kv is None:
            continue
        for ci, i in enumerate(q_pos[b]):
            lo = max(0, i - window + 1)
            for head in range(h):
                g = head // (h // hp)
                k, v = kv[lo:i + 1, g, :d], kv[lo:i + 1, g, d:]
                s = k @ q[b, head, ci] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[b, head, ci] = p @ v / p.sum()
    return out


@pytest.mark.parametrize("c", [4, 1], ids=["prefill", "decode"])
@pytest.mark.parametrize("window", [20, 8], ids=["w20", "w8"])
def test_window_walk_matches_masked_dense_attention(window, c):
    """A lane's ring holds only the last R blocks of its positions; the
    windowed walk over it equals attention over each query's window of
    the whole logical sequence, for chunks wholly inside the first
    window, straddling its edge, just past it, and far past it with the
    ring wrapped several times; an idle lane is an exact zero, and the
    ring's stale rows (1e4) weigh nothing. The dispatcher's reference,
    the same walk's spec, agrees."""
    lengths = [None, 2, window - 2, window + 1, 61, 120 - c]
    args, keys, q_pos = make_ring_case(lengths, c, window, seed=window + c)
    got = np.asarray(jax.jit(functools.partial(
        paged.ragged_paged_attention_v2, window=window))(*args))
    want = _window_dense(args[0], keys, q_pos, window, 4, 2, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[0].any()
    ref = np.asarray(kvc.paged_attention_reference(*args, window=window))
    np.testing.assert_allclose(ref[1:], want[1:], rtol=1e-5, atol=1e-5)


def test_window_plan_starts_each_lane_at_its_windows_first_group():
    """16-key blocks, groups of 8 columns (128 keys), a window of 100:
    a lane at 0-3 takes group 0 alone; at 300-303 its window reaches
    back to 201 (column 12, group 1), so groups 1-2; at 1000-1003 to
    901 (column 56, group 7): groups 7 only, one step, where the plain
    walk takes 8."""
    bs, m, p = 16, 64, 8
    tables = jnp.ones((3, m), jnp.int32)
    pos = jnp.asarray([[0, 1, 2, 3], [300, 301, 302, 303],
                       [1000, 1001, 1002, 1003]], jnp.int32)
    steps, plan = paged._plan_walk(tables, pos, bs, p, window=100)
    lane, group, _fetch, live, groups = (np.asarray(a) for a in plan)
    assert int(steps) == 4
    np.testing.assert_array_equal(lane[:4], [0, 1, 1, 2])
    np.testing.assert_array_equal(group[:4], [0, 1, 2, 7])
    np.testing.assert_array_equal(groups, [1, 3, 8])
    assert live[1, :12].sum() == 0 and live[1, 12:19].all()
    assert live[2, :56].sum() == 0 and live[2, 56:63].all()
    plain_steps, _ = paged._plan_walk(tables, pos, bs, p)
    assert int(plain_steps) == 12


def test_ring_layout_is_lane_and_position():
    """Block 1 + s * R + (j mod R) of the ring pool holds column j of
    lane s wherever the lane's table holds a block; NULL elsewhere. A
    window of 4,096 at chunks of 16 over 16-token blocks takes 264
    blocks a lane (33 walk groups)."""
    tables = jnp.asarray([[5, 9, 2, 0, 0], [0, 0, 0, 0, 0],
                          [3, 3, 3, 3, 3]], jnp.int32)
    got = np.asarray(kvc.ring_table(tables, 2))
    np.testing.assert_array_equal(got, [[1, 2, 1, 0, 0], [0] * 5,
                                        [5, 6, 5, 6, 5]])
    assert kvc.ring_blocks_for(4096, 16, 16) == 264
    # R * bs covers the window, the chunk and a block the window's
    # start falls inside
    for w, c, bs in ((20, 4, 8), (8, 1, 8), (4096, 1, 16), (100, 16, 16)):
        r = kvc.ring_blocks_for(w, c, bs)
        assert r * bs >= w + c + bs - 2
