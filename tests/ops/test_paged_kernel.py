"""Pallas ragged paged attention kernel (ops/pallas/paged.py) vs the
pure-JAX reference (serving/kv_cache.paged_attention_reference).

Tiering: everything here is tier-1 (`pallas` marker; the kernel runs
under the Pallas interpreter on CPU, so these tests exercise the REAL
kernel code path, not a shadow implementation). The contract:

- f32 pools: kernel output is BITWISE-identical to the reference for
  chunked prefill (C>1), decode (C=1), ragged mixed-length batches,
  and NULL-padded tables — the kernel mirrors the reference's op
  sequence on its in-kernel gather, so partial sums are identical, not
  just close. A table of several groups of 128 key positions is walked
  a live group a step, and a lane is bitwise the reference on its
  table CUT to its live groups (`cut_reference`); a table of one group
  is the reference as it is;
- bf16 pools: allclose within bf16 tolerance — the kernel accumulates
  scores/softmax in f32 where the reference rounds through bf16 (on
  the CPU backend XLA upcasts bf16 matmuls, so the observed diff here
  is usually 0; the tolerance is the documented contract for real-TPU
  runs where the two paths genuinely differ);
- the NULL block (block 0) is NEVER read: NaN-poisoning it must not
  reach the output, op-level and through a full GenerationServer
  stream;
- dispatch: PADDLE_TPU_PAGED_KERNEL=0 pins the reference, =1 raises on
  unsupported operands, auto falls back silently and counts it;
- the serving engine reports (and asserts) kernel engagement.
"""

import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged
from paddle_tpu.serving import kv_cache as kvc

pytestmark = pytest.mark.pallas


def make_case(dtype=jnp.float32, b=3, h=2, c=4, d=8, bs=8, m=6, seed=0,
              poison=False, idle_lane=False):
    """Ragged batch: every lane gets its own length (and so its own
    live-block count), tables are NULL-padded past the live blocks, and
    block assignment is shuffled so table order != pool order.
    idle_lane=True turns lane 0 into an engine-style masked lane: all
    positions 0, table all NULL. The pool is the fused one,
    `kvc.fuse_kv(k_pool, v_pool)`: K beside V in the minor dim."""
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    k_pool = rng.standard_normal((n, h, bs, d)).astype(dtype)
    v_pool = rng.standard_normal((n, h, bs, d)).astype(dtype)
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


def _run_both(args):
    """Run both paths under jit — the production context (the engine's
    whole life is ONE jitted fused step). Eager op-by-op dispatch may
    compile the reference einsum standalone and diverge in the last
    ulp; the bitwise contract is pinned where it is used."""
    ref = jax.jit(kvc.paged_attention_reference)(*args)
    out = jax.jit(paged.ragged_paged_attention)(*args)
    return np.asarray(out), np.asarray(ref)


# ---------------------------------------------------------------------------
# bitwise pins (f32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(),                                      # chunked prefill C=4
    dict(c=1, seed=1),                           # decode C=1
    dict(b=5, h=3, c=3, d=5, bs=4, m=9, seed=7),  # odd, ragged
    dict(b=2, h=1, c=2, d=16, bs=16, m=3, seed=9),
    dict(idle_lane=True, seed=11),               # all-NULL masked lane
], ids=["prefill", "decode", "ragged_odd", "wide_block", "idle_lane"])
def test_kernel_bitwise_matches_reference_f32(case):
    out, ref = _run_both(make_case(**case))
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(out, ref)


def test_kernel_eager_allclose_f32():
    """Outside jit the bitwise pin does NOT hold (eager op-by-op
    dispatch compiles the reference einsum standalone and the two
    paths drift in the last ulp) — but the eager kernel must still be
    usable and numerically tight."""
    args = make_case(seed=3)
    out = np.asarray(paged.ragged_paged_attention(*args))
    ref = np.asarray(kvc.paged_attention_reference(*args))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the grouped walk: tables of several groups (ISSUE 31)
# ---------------------------------------------------------------------------

def make_walk_case(kind="f32", c=4, bs=16, m=24, seed=0, poison=False):
    """A table of several groups of 128 key positions, one lane a live
    group count: lane 0 idle, lane 1 filling the table, the others
    holding 5, 130 and 300 tokens and one just short of full. kind:
    "f32" (MHA), "gqa" (2 KV heads under 4 query heads) or "int8"
    (codes + scale pools). poison=True fills the NULL block AND every
    table column past a lane's live blocks (stale entries, whole dead
    groups among them) with NaN blocks: nothing of them may be read."""
    rng = np.random.default_rng(seed)
    t = m * bs
    lengths = [None, t - c, 5, 130, 300, t - c - bs]
    b, h, d = len(lengths), 4, 8
    hp = 2 if kind == "gqa" else h
    n = 2 + b * m
    bad = n - 1                             # a real block, all NaN
    k = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    v = rng.standard_normal((n, hp, bs, d)).astype(np.float32)
    k[kvc.NULL_BLOCK] = v[kvc.NULL_BLOCK] = 0.0
    q = jnp.asarray(rng.standard_normal((b, h, c, d)), jnp.float32)
    tables = np.full((b, m), bad if poison else kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    free = list(range(1, bad))
    rng.shuffle(free)
    for i, length in enumerate(lengths):
        if length is None:
            tables[i, 0] = kvc.NULL_BLOCK   # idle: nothing to attend
            continue
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        q_pos[i] = np.arange(length, length + c)
    tail = (jnp.asarray(tables), jnp.asarray(q_pos))
    if kind == "int8":
        kq, ks = kvc.quantize_kv_rows(jnp.asarray(k))
        vq, vs = kvc.quantize_kv_rows(jnp.asarray(v))
        if poison:      # int8 codes cannot hold a NaN: the scales do
            ks, vs = (x.at[kvc.NULL_BLOCK].set(jnp.nan).at[bad].set(
                jnp.nan) for x in (ks, vs))
        return (q, kvc.fuse_kv(kq, vq)) + tail + (ks, vs)
    if poison:
        k[kvc.NULL_BLOCK] = v[kvc.NULL_BLOCK] = k[bad] = v[bad] = np.nan
    return (q, kvc.fuse_kv(jnp.asarray(k), jnp.asarray(v))) + tail


def cut_reference(args):
    """v1's contract on a table of several groups: the reference, lane
    by lane, on the lane's table CUT to its live groups (masked keys
    contribute exact zeros, so the cut is an identity in real
    arithmetic; in floating point it fixes the width the sums run
    over). A table of more than 8 groups is cut in steps of
    `walk_rung` groups. An idle lane keeps one step."""
    q, kv_pool, tables, q_pos = args[:4]
    bs, m = kv_pool.shape[2], tables.shape[1]
    p = paged.walk_group(bs, m) * paged.walk_rung(bs, m)
    ref = jax.jit(kvc.paged_attention_reference)
    out = []
    for i in range(q.shape[0]):
        n_live = min(int(np.max(np.asarray(q_pos[i]))) // bs + 1, m)
        width = min(-(-n_live // p) * p, m)
        out.append(np.asarray(ref(q[i:i + 1], kv_pool,
                                  tables[i:i + 1, :width],
                                  q_pos[i:i + 1], *args[4:])))
    return np.concatenate(out)


WALK_GEOMETRIES = [dict(bs=16, m=24),       # 384 keys: 3 groups of 8
                   dict(bs=16, m=64),       # 1,024: the cell's table
                   dict(bs=8, m=50),        # 400: 3 groups of 16 + 2
                   dict(bs=16, m=21),       # 336: 2 groups of 8 + 5
                   dict(bs=16, m=84)]       # 1,344: 11 groups, 2 a rung
WALK_IDS = ["t384", "t1024", "t400_ragged_group", "t336_ragged_group",
            "t1344_rungs_of_two"]


def testwalk_group_is_128_key_positions():
    assert paged.walk_group(16, 64) == 8
    assert paged.walk_group(8, 50) == 16
    assert paged.walk_group(32, 64) == 4
    assert paged.walk_group(256, 4) == 1       # a block past a group
    # a table under one group is one step, at its own width: as before
    assert paged.walk_group(8, 6) == 6
    assert paged.walk_group(4, 9) == 9
    # the value path is compiled at 8 widths at most
    assert [paged.walk_rung(16, m) for m in (3, 64, 65, 84, 512)] \
        == [1, 1, 2, 2, 8]


@pytest.mark.parametrize("c", [1, 4], ids=["decode", "prefill"])
@pytest.mark.parametrize("kind", ["f32", "int8", "gqa"])
@pytest.mark.parametrize("geom", WALK_GEOMETRIES, ids=WALK_IDS)
def test_grouped_walk_bitwise_matches_cut_reference(geom, kind, c):
    """Lanes of 0, 1, 2, ... and all live groups in one call: each is
    bitwise the reference at the width of its own live groups, and the
    idle lane an exact zero."""
    args = make_walk_case(kind=kind, c=c, seed=c + geom["m"], **geom)
    out = np.asarray(jax.jit(paged.ragged_paged_attention)(*args))
    np.testing.assert_array_equal(out, cut_reference(args))
    assert not out[0].any()
    assert out[1:].any()


@pytest.mark.parametrize("kind", ["f32", "int8", "gqa"])
@pytest.mark.parametrize("geom", WALK_GEOMETRIES[::2], ids=WALK_IDS[::2])
def test_grouped_walk_never_reads_a_dead_block(geom, kind):
    """NaN in the NULL block, in the dead columns of a live group and
    in every block of a dead group reaches no output and changes no bit
    of it; the idle lane stays an exact zero."""
    fn = jax.jit(paged.ragged_paged_attention)
    dirty = np.asarray(fn(*make_walk_case(kind=kind, seed=5, poison=True,
                                          **geom)))
    clean = np.asarray(fn(*make_walk_case(kind=kind, seed=5, **geom)))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    assert not dirty[0].any()


def test_plan_walk_steps_only_through_live_groups():
    """A step is one live group of one lane (an idle lane takes one, to
    write its zeros) and their sum is the grid's bound; past a lane's
    last live column every window of its last group repeats the block
    it held a step before, so the pipeline issues no copy for it, and
    in a lane's first group it names the NULL block."""
    tables = jnp.asarray([[7, 8, 9, 10, 11, 0, 0, 0, 0, 0],
                          [3, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                          [0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                          [4, 5, 6, 12, 13, 14, 15, 16, 17, 18]],
                         jnp.int32)
    pos = jnp.asarray([[4 * 5 - 1], [2], [0], [39]], jnp.int32)  # bs 4
    steps, plan = paged._plan_walk(tables, pos, 4, 4)
    lane, group, fetch, live, groups = (np.asarray(x) for x in plan)
    assert fetch.shape == live.shape == (4, 12)     # padded to 3 groups
    # (what `fetch` says of a dead group is never read)
    np.testing.assert_array_equal(fetch[0, :8], [7, 8, 9, 10, 11, 8, 9, 10])
    np.testing.assert_array_equal(fetch[1, :4], [3, 0, 0, 0])
    np.testing.assert_array_equal(
        fetch[3], [4, 5, 6, 12, 13, 14, 15, 16, 17, 18, 15, 16])
    np.testing.assert_array_equal(live[0], [1] * 5 + [0] * 7)
    np.testing.assert_array_equal(live[1], [1] + [0] * 11)
    np.testing.assert_array_equal(live[3], [1] * 10 + [0] * 2)
    assert not live[2].any() and not fetch[2].any()
    np.testing.assert_array_equal(groups, [2, 1, 0, 3])
    assert int(steps) == 2 + 1 + 1 + 3
    assert lane.shape == group.shape == (4 * 3,)
    np.testing.assert_array_equal(lane[:7], [0, 0, 1, 2, 3, 3, 3])
    np.testing.assert_array_equal(group[:7], [0, 1, 0, 0, 0, 1, 2])


# ---------------------------------------------------------------------------
# bf16: f32 accumulation, documented tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [4, 1], ids=["prefill", "decode"])
def test_kernel_bf16_allclose(c):
    out, ref = _run_both(make_case(dtype=jnp.bfloat16, c=c, seed=2))
    assert out.dtype == jnp.bfloat16
    # one-bf16-ulp headroom: the kernel's f32 score accumulation may
    # round differently from the reference's bf16 score math
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_output_dtype_follows_the_pool():
    argsf = make_case()
    assert paged.ragged_paged_attention(*argsf).dtype == jnp.float32
    argsb = make_case(dtype=jnp.bfloat16)
    assert paged.ragged_paged_attention(*argsb).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# NULL block is never read
# ---------------------------------------------------------------------------

def test_null_block_poison_stays_finite_op_level():
    """NaN in block 0 must not reach the kernel output (the reference,
    which gathers the dense view including NULL rows, does NOT have
    this property — that asymmetry is the proof the kernel skips the
    read instead of multiplying it by zero)."""
    args = make_case(seed=3, poison=True)
    out = np.asarray(paged.ragged_paged_attention(*args))
    assert np.isfinite(out).all()
    clean = make_case(seed=3, poison=False)
    np.testing.assert_array_equal(
        out, np.asarray(paged.ragged_paged_attention(*clean)))


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("half", ["k_lanes", "v_lanes"])
def test_null_block_poison_in_one_half_stays_finite(half, version):
    """K and V of the NULL block share one array now: NaN in its K
    lanes alone, or in its V lanes alone, reaches no output either, and
    changes no bit of it, under both kernels."""
    fn = jax.jit(paged.ragged_paged_attention if version == "v1"
                 else paged.ragged_paged_attention_v2)
    q, kv_pool, tables, pos = make_case(seed=3, idle_lane=True)
    d = q.shape[-1]
    lanes = slice(0, d) if half == "k_lanes" else slice(d, 2 * d)
    dirty = kv_pool.at[kvc.NULL_BLOCK, :, :, lanes].set(jnp.nan)
    out = np.asarray(fn(q, dirty, tables, pos))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(
        out, np.asarray(fn(q, kv_pool, tables, pos)))


def test_consts_mirror_kv_cache():
    """The kernel module duplicates NULL_BLOCK/NEG_INF (it must not
    import the serving layer); drift would silently break the bitwise
    pin or the NULL-skip guard."""
    assert paged.NULL_BLOCK == kvc.NULL_BLOCK
    assert paged.NEG_INF == kvc.NEG_INF


# ---------------------------------------------------------------------------
# gather pair (reference-path satellite)
# ---------------------------------------------------------------------------

def test_gather_block_kv_pair_matches_single_gathers():
    """The fused pool gathered once and split is the K pool and the V
    pool each gathered alone; fuse_kv and split_kv are inverses."""
    _q, kv_pool, tables, _pos = make_case(seed=5)
    k_pool, v_pool = kvc.split_kv(kv_pool)
    np.testing.assert_array_equal(
        np.asarray(kvc.fuse_kv(k_pool, v_pool)), np.asarray(kv_pool))
    gk, gv = kvc.gather_block_kv_pair(kv_pool, tables)
    np.testing.assert_array_equal(
        np.asarray(gk), np.asarray(kvc.gather_block_kv(k_pool, tables)))
    np.testing.assert_array_equal(
        np.asarray(gv), np.asarray(kvc.gather_block_kv(v_pool, tables)))


# ---------------------------------------------------------------------------
# dispatch + counters
# ---------------------------------------------------------------------------

def test_dispatch_auto_routes_to_kernel_and_counts(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    args = make_case(seed=6)
    k0 = kvc.KERNEL_DISPATCHES
    m0 = reg.counter("serving.kernel.traced").value()
    # fresh jit wrapper: dispatch happens at TRACE time, once
    out = jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert kvc.KERNEL_DISPATCHES == k0 + 1
    assert reg.counter("serving.kernel.traced").value() == m0 + 1
    assert reg.gauge("serving.kernel.interpret").value() == 1  # CPU
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.jit(kvc.paged_attention_reference)(*args)))


def test_dispatch_env_zero_pins_reference(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    from paddle_tpu.observability.metrics import global_registry
    args = make_case(seed=6)
    f0 = kvc.FALLBACK_DISPATCHES
    m0 = global_registry().counter("serving.kernel.fallback").value()
    kvc.paged_attention(*args)
    assert kvc.FALLBACK_DISPATCHES == f0 + 1
    assert global_registry().counter(
        "serving.kernel.fallback").value() == m0 + 1
    assert kvc.kernel_dispatch_stats()["mode"] == "off"


def test_dispatch_force_raises_on_unsupported(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    q, kv_pool, tables, pos = make_case(seed=6)
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables, pos)
    # a pool that is not (N, H, bs, 2 * head_dim) is no fused pool of
    # this q's, whatever its dtype: K alone, the pair's older shape
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kvc.split_kv(kv_pool)[0], tables, pos)


def test_dispatch_auto_falls_back_on_unsupported(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    q, kv_pool, tables, pos = make_case(seed=6)
    f0 = kvc.FALLBACK_DISPATCHES
    out = kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables,
                              pos)
    assert kvc.FALLBACK_DISPATCHES == f0 + 1
    assert out.dtype == jnp.float16


def test_bad_env_value_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "maybe")
    with pytest.raises(ValueError, match="PADDLE_TPU_PAGED_KERNEL"):
        kvc.paged_kernel_mode()


def test_dispatch_takes_the_shard_map_fact_as_an_argument(monkeypatch):
    """The dispatcher does not inspect tracers: whether it runs inside
    a shard_map is a fact its caller passes (`in_shard_map=`, set by
    GPTServingModel.build_fused_step's tensor-parallel branch). With
    it, force mode + non-qualifying operands falls back under the
    unsupported_under_shard_map label; without it the same call
    raises. A plain jit(vmap(...)) trace gets no special treatment —
    it takes the kernel like any other trace."""
    from paddle_tpu.observability.metrics import global_registry
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    q, kv_pool, tables, pos = make_case(b=2, c=1, m=3, seed=8)
    kv16 = kv_pool.astype(jnp.float16)
    reason = global_registry().counter(
        "serving.kernel.fallback").labels(
        reason="unsupported_under_shard_map")
    r0 = reason.value()
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kv16, tables, pos)
    out = kvc.paged_attention(q, kv16, tables, pos, in_shard_map=True)
    assert reason.value() == r0 + 1
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(kvc.paged_attention_reference(
            q, kv16, tables, pos)))
    k0, f0 = kvc.KERNEL_DISPATCHES, kvc.FALLBACK_DISPATCHES
    jax.jit(jax.vmap(lambda a: kvc.paged_attention(
        a, kv_pool, tables, pos)))(jnp.stack([q, q + 1]))
    assert (kvc.KERNEL_DISPATCHES, kvc.FALLBACK_DISPATCHES) == \
        (k0 + 1, f0)


def test_no_module_sniffs_jax_tracer_internals():
    """The jax 0.9 upgrade removed `jax.interpreters.batching.
    BatchTracer` and took every serving path down with it. Nothing
    under paddle_tpu/ may reach into jax.interpreters or the axis-env
    probes again."""
    import os
    import re
    root = os.path.dirname(os.path.abspath(kvc.__file__))
    root = os.path.dirname(root)
    pat = re.compile(r"jax\.interpreters|from jax import interpreters"
                     r"|nonempty_axis_env|get_axis_env")
    hits = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    if pat.search(fh.read()):
                        hits.append(os.path.relpath(path, root))
    assert not hits, hits


def test_dispatch_fallback_reason_labels(monkeypatch):
    """The other fallback reasons ride the same labeled series:
    pinned_off for PADDLE_TPU_PAGED_KERNEL=0, unsupported for
    non-qualifying operands in auto mode."""
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    args = make_case(seed=12)
    off = reg.counter("serving.kernel.fallback").labels(
        reason="pinned_off")
    uns = reg.counter("serving.kernel.fallback").labels(
        reason="unsupported")
    o0, u0 = off.value(), uns.value()
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    kvc.paged_attention(*args)
    assert off.value() == o0 + 1 and uns.value() == u0
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    q, kv_pool, tables, pos = args
    kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables, pos)
    assert uns.value() == u0 + 1
    # a deliberate pin DOMINATES: off mode under a vmap trace still
    # records pinned_off, never vmap_trace — a dashboard alerting on
    # non-pinned_off fallback reasons must not page on the pin
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    o1 = off.value()
    jax.vmap(lambda a: kvc.paged_attention(a, kv_pool, tables,
                                           pos))(jnp.stack([q, q]))
    assert off.value() == o1 + 1


def test_kernel_validates_shapes():
    q, kv_pool, tables, pos = make_case(seed=6)
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q, kv_pool, tables, pos[:1])
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q[:, :1], kv_pool, tables, pos)
    # the older pair's K pool alone: half a fused pool's minor dim
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q, kvc.split_kv(kv_pool)[0], tables,
                                     pos)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, gpt.load_params(scope, cfg)


def _server(params, cfg, **kw):
    from paddle_tpu.serving import GenerationServer, GPTServingModel
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def test_engine_reports_kernel_engagement(tiny_gpt, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    assert srv.get_stats()["kernel"]["engaged"] is None
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    assert len(fut.result(timeout=5).token_ids) == 4
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"]["engaged"] is True
    assert st["kernel"]["kernel_dispatches"] == cfg.num_layers
    assert st["kernel"]["fallback_dispatches"] == 0
    # the layout, as a fact beside the kernel's: one block of a layer's
    # pool is (H_kv, block_size, 2 * head_dim), K beside V
    shape = [cfg.num_heads, 8, 2 * cfg.hidden_size // cfg.num_heads]
    assert st["kernel"]["pool_block_shape"] == shape
    assert list(srv.cache.pools[0]["kv"].shape[1:]) == shape


def test_engine_reference_mode_not_engaged(tiny_gpt, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    ids_ref = list(fut.result(timeout=5).token_ids)
    st = srv.get_stats()
    assert st["kernel"]["engaged"] is False
    assert st["kernel"]["fallback_dispatches"] == cfg.num_layers

    # kernel-mode server on the same params produces the same ids
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    srv2 = _server(params, cfg)
    fut2 = srv2.submit([5, 9, 11], max_new_tokens=4)
    srv2.run_until_idle()
    assert list(fut2.result(timeout=5).token_ids) == ids_ref
    assert srv2.get_stats()["kernel"]["engaged"] is True


def test_engine_bf16_pools_run_on_kernel(tiny_gpt, monkeypatch):
    """bf16 KV pools qualify for the kernel (f32 accumulation inside);
    a bf16 server must engage it and produce tokens end to end."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    from paddle_tpu.serving import GenerationServer, GPTServingModel
    cfg, params = tiny_gpt
    srv = GenerationServer(
        GPTServingModel(params, cfg, dtype=jnp.bfloat16), num_slots=2,
        block_size=8, max_context=64, chunk=4, start=False)
    assert srv.cache.dtype == jnp.bfloat16
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert len(res.token_ids) == 4
    st = srv.get_stats()
    assert st["kernel"]["engaged"] is True
    assert st["fused_step_signatures"] == 1


def test_engine_null_block_poison_full_stream(tiny_gpt, monkeypatch):
    """The acceptance poison test: fill every layer's block 0 with NaN
    BEFORE serving, run a mixed-length stream on the kernel path —
    every output token id matches the clean run and every logprob is
    finite. Masked lanes and table padding contributed exactly
    nothing."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    prompts = [np.array([5, 9, 11, 2, 7], np.int32),
               np.array([7] * 11, np.int32),
               np.array([3, 4], np.int32)]
    lens = [6, 4, 8]

    def run(poison):
        srv = _server(params, cfg)
        if poison:
            nanrow = jnp.full((cfg.num_heads, srv.block_size,
                               2 * cfg.hidden_size // cfg.num_heads),
                              jnp.nan, srv.cache.dtype)
            srv.cache.pools = [
                {"kv": p["kv"].at[kvc.NULL_BLOCK].set(nanrow)}
                for p in srv.cache.pools]
        futs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        srv.run_until_idle()
        res = [f.result(timeout=5) for f in futs]
        assert srv.get_stats()["kernel"]["engaged"] is True
        return res

    clean = run(poison=False)
    poisoned = run(poison=True)
    for c, p in zip(clean, poisoned):
        assert list(p.token_ids) == list(c.token_ids)
        assert np.isfinite(p.score)


# ---------------------------------------------------------------------------
# lazy export
# ---------------------------------------------------------------------------

def test_pallas_package_lazy_exports():
    import paddle_tpu.ops.pallas as pk
    assert pk.ragged_paged_attention is paged.ragged_paged_attention
    assert pk.paged is paged
    assert "flash_attention" in dir(pk)


def test_pallas_package_import_stays_cheap():
    """Importing the package must touch neither kernel module — CPU
    workloads that never hit attention pay no Pallas import."""
    code = ("import sys, paddle_tpu.ops.pallas; "
            "mods = [m for m in sys.modules if m.startswith("
            "'paddle_tpu.ops.pallas.')]; "
            "assert not mods, mods; print('lazy ok')")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "lazy ok" in out.stdout
