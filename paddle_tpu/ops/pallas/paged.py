"""Ragged paged attention for TPU (Pallas): the serving hot-loop kernel.

The pure-JAX reference (`serving/kv_cache.paged_attention_reference`)
materializes a dense (B, H, M*bs, D) gather of every request's FULL
block table on every fused step — each decode iteration pays
O(max_blocks) HBM traffic per lane regardless of how many tokens the
lane actually holds. The kernels here (per the *Ragged Paged Attention*
TPU paper, PAPERS.md) walk the block table INSIDE the kernel instead,
and a call costs what its lanes hold, not what the table could hold.

**The walk** (`_plan_walk`, `_paged_call`). The unit is a GROUP of
table columns of one lane: 128 key positions (`walk_group`: 8 columns of
16-token blocks, read from the shapes), for every kernel here. The grid is
ONE axis whose steps are the LIVE groups only, lane after lane; its
bound, the sum of the lanes' live groups (an idle lane takes one step,
to write its zeros), is a value the call computes from the positions,
not a shape. `_plan_walk` is plain XLA on the table and the positions
(every layer of a step shares them: a step computes the plan once): it
names each step's lane and group and each column's block, and all of
it rides scalar prefetch (SMEM), where the index maps read it. A step's
blocks arrive through BlockSpecs — the one pool operand is handed to
the call once a column of the group, each with its own index map — so
the Pallas pipeline issues (and double-buffers) the HBM->VMEM copies,
eight in flight where a step of one column had one, and the next
lane's first group lands while this lane's last computes. A dead group
is no step at all; a column past a lane's last live one repeats the
block its window held last, and the pipeline skips a copy whose block
index did not change. (On the chip, at `gpt2-xl.closed-16`'s shape and
contexts: 1,024 steps of a column each took 415 us a call, 128 steps
of a group, live or dead, 157, the 59 live ones 119: PERF.md section
6, PR 31.)

A layer's pool is ONE array (N, H_kv, bs, 2*D): the K row of a token in
lanes [0, D), its V row in [D, 2*D) (`serving/kv_cache.fuse_kv`). At
head_dim 64 the minor dim is the 128 lanes of a TPU tile, so the
device's own layout of the pool is the row-major one these kernels
read and no step re-lays a pool out (PERF.md section 6, PR 29); a
block is one DMA, not two. Two generations share that walk:

* **v1** (`ragged_paged_attention`): every live block is copied into
  an (H_kv, M*bs, 2*D) VMEM scratch as it arrives; the lane's last
  step takes K and V out of it with two lane slices and runs the
  reference's exact op sequence on the VMEM-resident gather, over the
  key positions of the lane's live groups and no further: one branch a
  live-group count, each of static width (128, 256, ... M*bs; a table
  of more than 8 groups is cut in `walk_rung` groups, so a kernel is
  compiled at 8 widths at most).
  The contract: f32 and int8 pools are BITWISE, under jit in interpret
  mode, the reference evaluated on the lane's table CUT to those live
  groups. Masked keys contribute exact zeros, so the cut is an identity
  in real arithmetic; in floating point it names the width the
  softmax's and the PV product's sums run over. A table of one group is
  bitwise the reference as it is. The price is VMEM scratch
  proportional to the table width M.
* **v2** (`ragged_paged_attention_v2`): a step's group lands in an
  (H_kv, 128, 2*D) VMEM scratch (a dead column as zeros) and folds
  straight into a flash-style online-softmax carry (running max,
  rescaled sum, rescaled PV partial, all f32 VMEM scratch), each KV
  head's H/H_kv query heads x C columns scored as ONE tile of rows
  against the group's 128 keys (one product batched over the KV
  heads for the scores, one for the values). VMEM holds the group and
  the carry — independent of M, so context length is unbounded at
  fixed VMEM. (In `solar-open2-250b-ep16.long8k-closed-16`, 64 query
  heads on 8 KV heads of 128 at 1k-8k contexts: 4.26 ms a call while a
  step was one column of repeated heads, 0.92 with a step a group:
  PERF.md section 6, PR 37.) Online softmax is
  mathematically EXACT (every rescale is an identity in real
  arithmetic) but reorders the floating-point reductions the reference
  performs in one pass, so v2 is pinned allclose-at-f32-tightness plus
  argmax-identical — v1 remains the bitwise-stable kernel and the
  dispatcher's default for tables under the VMEM ceiling.

Both kernels share the serving contract:

* the NULL block (block 0 — table padding, masked-lane writes) never
  enters the arithmetic: padding entries and idle lanes contribute
  exactly nothing, even if block 0 holds garbage, and neither does a
  stale entry past a lane's last live column (pinned by NaN-poison
  tests); an idle lane is an exact zero, and does no arithmetic;
* chunked prefill (C > 1) and decode (C = 1) are ONE kernel — the
  engine's single fused-step signature survives unchanged;
* bf16 pools are welcome: scores and softmax accumulate in f32
  (EQuARX-style reduced-precision hot path with full-precision
  accumulation);
* int8 pools (quantized serving, ISSUE 14) fuse the DEQUANT into the
  walk: the pipeline copies the int8 codes (K and V of a block in
  one (H_kv, bs, 2*D) window) plus their two (H_kv, bs) f32 scale
  rows — roughly HALF the bytes a bf16 pool moves per block —
  and the dequant multiply happens on the VMEM-resident block right
  where the value path consumes it;
* grouped-query attention (ISSUE 16): pools may carry H_kv < H heads
  (H % H_kv == 0). Query head j attends KV head j // (H/H_kv) — the
  contiguous-group convention, so Megatron column-sharded projections
  stay head-aligned. v1 repeats the VMEM-resident KV rows across each
  group (a pure copy, so its bitwise pin extends to GQA); v2 repeats
  nothing: a KV head's query heads are contiguous, so (H, C, D) is a
  free reshape to (H_kv, H/H_kv * C, D), one tile of rows a KV head.
  HBM traffic stays at H_kv heads.

Why BlockSpecs and not hand-rolled `make_async_copy`: the pipeline's
own copies take any block whose trailing dims equal the array's, every
pool dtype and block size the same way (a DMA slice of an array whose
minor dim is under one 128-lane tile Mosaic refuses, which ruled the
other way out while a pool was 64 wide; since PR 29 it is 128 and that
reason is gone), the interpreter runs them unchanged, and the dequant
of an int8 block needs the block staged in VMEM anyway. What they cost
is the pipeline's bookkeeping, about 0.1 us a window a step: the floor
this walk stands on (PERF.md section 6, PR 31).

VMEM budget: v1's scratch holds one lane's full K+V working set,
H_kv * M*bs * 2*D elements in the pool's dtype (f32 for int8 pools,
which are dequantized as they land) — the full-KV-resident discipline
of flash.py's default forward. At head_dim 64 the 2*D minor dim is
exactly the 128 lanes, so nothing is padded and the dispatcher's
estimate (`v1_scratch_bytes`) is what Mosaic allocates: 6.5 MB for 25
heads x 1,024 tokens of bf16, where two 64-lane scratches padded to
13. The K and V slices the value path takes are temporaries of the same
size again at most. The windows of a group are 2 * `walk_group` blocks
(1.6 MB at that geometry). v2 holds one group and the carry whatever
M is (2 MB at the Solar cell's geometry: `_v2_scratch_shapes`); the
dispatcher (serving/kv_cache.paged_attention) routes tables past
the v1 ceiling to v2 automatically.

Off-TPU the kernels run under the Pallas interpreter (same policy as
flash.py) so the CPU suite exercises the real kernel code.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NULL_BLOCK = 0          # mirrors serving.kv_cache.NULL_BLOCK
NEG_INF = -1e9          # mirrors serving.kv_cache.NEG_INF (the masked
                        # score value the bitwise pin depends on)

# Incremented each time a kernel is TRACED — the serving engine and
# its tests assert the kernel path actually engaged instead of silently
# falling back to the dense gather (flash.py's TRACE_COUNT /
# VERDICT r1 weak #7 lesson). V2_TRACE_COUNT counts the v2 subset.
TRACE_COUNT = 0
V2_TRACE_COUNT = 0


def _interpret():
    return jax.default_backend() != "tpu"


def _validate_paged_args(q, kv_pool, block_table, q_positions,
                         k_scale, v_scale):
    """Shared v1/v2 operand validation. Returns
    (b, h, c, d, n, hp, bs, m, quantized); `hp` is the pool (KV) head
    count — equal to h for MHA, a divisor of h for GQA."""
    b, h, c, d = q.shape
    n, hp, bs, dp = kv_pool.shape
    if dp != 2 * d or hp > h or h % hp != 0:
        raise ValueError(
            f"pool {kv_pool.shape} and q {q.shape} do not match (a "
            f"fused pool is (N, H_kv, bs, 2 * head_dim), K beside V; "
            f"GQA needs q heads a multiple of pool heads)")
    m = block_table.shape[1]
    if block_table.shape[0] != b or q_positions.shape != (b, c):
        raise ValueError(
            f"table {block_table.shape} / positions {q_positions.shape} "
            f"do not match q {q.shape}")
    quantized = kv_pool.dtype == jnp.int8
    if quantized:
        if k_scale is None or v_scale is None:
            raise ValueError(
                "int8 pools need k_scale/v_scale (N, H_kv, bs) f32 "
                "scale pools — quantized KV is (codes, scales) pairs")
        if (k_scale.shape != (n, hp, bs)
                or v_scale.shape != (n, hp, bs)):
            raise ValueError(
                f"scale pools {k_scale.shape}/{v_scale.shape} do not "
                f"match data pool {kv_pool.shape} (want {(n, hp, bs)})")
    elif k_scale is not None or v_scale is not None:
        raise ValueError(
            f"scale pools passed with a non-int8 pool "
            f"({kv_pool.dtype}) — scales only mean something for "
            f"quantized KV")
    return b, h, c, d, n, hp, bs, m, quantized


def walk_group(bs, m):
    """Table columns a grid step of v1 takes: 128 key positions' worth
    (one lane tile of scores), the whole table where it is narrower."""
    return max(1, min(128 // bs, m))


def walk_rung(bs, m):
    """Groups between two widths of v1's value path: one, until a table
    holds more than 8 groups; then an eighth of them, so that a kernel
    is compiled at no more than 8 widths however long its table is."""
    return -(-(-(-m // walk_group(bs, m))) // 8)


def _plan_walk(block_table, q_positions, bs, p):
    """The walk, as small int32 arrays the kernels read from SMEM
    (plain XLA on the table and the positions: every layer of a step
    shares them, so a step computes them once). A grid step is one LIVE
    group of p table columns of one lane; column j * p + i of a lane is
    the i-th block of its group j. Returns (steps, plan):

    steps (): how many grid steps the call takes, the sum of the lanes'
        live groups (an idle lane takes one, to write its zeros): the
        grid's bound, known only when the call runs;
    lane, group (B * G,): the lane and the group of each step, lane by
        lane, groups in order (G = ceil(M / p));
    fetch (B, G * p): the pool block that column's window holds. Past
        the lane's last live column, in its last live group, a window
        repeats the block it held a step before (NULL_BLOCK in a first
        group), and the pipeline skips a copy whose block index did not
        change: the dead columns of a lane's last group move nothing.
        (A dead group is no step: what `fetch` says there is not read);
    live (B, G * p): 1 where the column holds a block to attend: not
        past the lane's highest query position, and not NULL_BLOCK
        (table padding, an idle lane). Whatever the pipeline delivered
        for a column that is not live is never touched;
    groups (B,): the lane's live groups, ceil(live columns / p); 0 for
        a lane with no live block at all (idle)."""
    tbl = block_table.astype(jnp.int32)
    b, m = tbl.shape
    g = -(-m // p)
    tbl = jnp.pad(tbl, ((0, 0), (0, g * p - m)),
                  constant_values=NULL_BLOCK)
    n_live = jnp.minimum(
        jnp.max(q_positions.astype(jnp.int32), axis=1) // bs + 1, m)
    col = jnp.arange(g * p, dtype=jnp.int32)[None]
    last = n_live[:, None] - 1
    a_step_before = jnp.pad(tbl, ((0, 0), (p, 0)),
                            constant_values=NULL_BLOCK)[:, :g * p]
    fetch = jnp.where(col <= last, tbl, a_step_before)
    live = (col <= last) & (tbl != NULL_BLOCK)
    groups = jnp.where(live.any(axis=1), (n_live + p - 1) // p, 0)
    steps = jnp.maximum(groups, 1)
    step = jnp.arange(b * g, dtype=jnp.int32)
    done = step[:, None] >= jnp.cumsum(steps)[None]     # (steps, lanes)
    lane = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), b - 1)
    group = step - jnp.sum(jnp.where(done, steps[None], 0), axis=1)
    return jnp.sum(steps), (lane, group, fetch, live.astype(jnp.int32),
                            groups)


def _page_specs(block_shape, p):
    """BlockSpecs for one pool, one per column of a group: at grid step
    s the i-th window holds pool block fetch[lane[s], group[s] * p + i]."""
    zeros = (0,) * (len(block_shape) - 1)

    def index_map(i, s, lane, group, fetch, live, groups, pos):
        del live, groups, pos
        return (fetch[lane[s], group[s] * p + i],) + zeros

    return [pl.BlockSpec((1,) + tuple(block_shape[1:]),
                         functools.partial(index_map, i))
            for i in range(p)]


def _pos_matrix(pos_ref, b, c, shape, axis):
    """int32 array of `shape` holding pos_ref[b, i] at index i along
    `axis` — the lane's query positions as a vector operand (SMEM
    scalars cannot be stacked into a vector directly)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.full(shape, pos_ref[b, 0], jnp.int32)
    for ci in range(1, c):
        out = jnp.where(idx == ci, pos_ref[b, ci], out)
    return out


def _dequant(codes, k_scales, v_scales):
    """int8 block (H_kv, bs, 2*D) times its (H_kv, bs) f32 row scales,
    the K scale over lanes [0, D) and the V scale over [D, 2*D) — per
    element the reference's dequant product, in f32."""
    d = codes.shape[-1] // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 2)
    scales = jnp.where(lane < d, k_scales[..., None], v_scales[..., None])
    return codes.astype(jnp.float32) * scales


def _repeat_heads(x, g):
    """(H_kv, ...) -> (H_kv * g, ...): query head j reads KV head
    j // g. A leading-dim copy."""
    if g == 1:
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], g) + x.shape[1:]
                            ).reshape((x.shape[0] * g,) + x.shape[1:])


def _mxu_precision(dtype):
    """Matmul precision for in-kernel dots on `dtype` operands. bf16
    operands ride the MXU natively (every product is exact in the f32
    accumulator, so DEFAULT loses nothing) — and must say so, because
    Mosaic refuses bf16 operands under a process-wide "highest" matmul
    precision ("Bad lhs type"). f32 operands follow the process
    setting."""
    return None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _padded_bytes(shape, dtype):
    """VMEM bytes of one buffer: minor dim padded to 128 lanes, second
    minor to the dtype's sublane tile (8 rows of 32 bits)."""
    item = np.dtype(dtype).itemsize
    dims = list(shape)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        sub = 8 * (4 // item)
        dims[-2] = -(-dims[-2] // sub) * sub
    return int(np.prod(dims)) * item


def _compiler_params(vmem_bytes):
    """One grid axis, lane after lane: a lane's steps carry scratch
    state. The VMEM limit is stated (Mosaic's default scope is smaller
    than v1's gather at long tables) with headroom for the pipeline
    windows and value temporaries."""
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(max(2 * vmem_bytes + (8 << 20),
                                     32 << 20), 100 << 20)))


def _paged_kernel(lane_ref, group_ref, fetch_ref, live_ref, groups_ref,
                  pos_ref, q_ref, *rest, bs, m, p, h, hp, d,
                  quantized=False):
    """Grid step s: lane b = lane[s], its j-th GROUP of p table columns
    (j = group[s]), all heads — dense AND int8 pools share this walk
    (selected at trace time by `quantized`, so the NULL guard, the
    zero-fill the bitwise pin depends on, and the mask/softmax tail
    exist exactly once).

    lane_ref / group_ref (B*G,), fetch_ref / live_ref (B, G*p),
    groups_ref (B,), pos_ref (B, C): scalar-prefetched SMEM
    (`_plan_walk`). q_ref (1, H, C, D); then p windows
    (1, H_kv, bs, 2*D): pool blocks fetch[b, j*p : (j+1)*p],
    delivered by the pipeline together, K in lanes [0, D) and V in
    [D, 2*D). g scratch (H_kv, M*bs, 2*D) VMEM — the lane's gathered
    view, rows in logical-position order exactly like the reference's
    dense gather, so the value-path math below can mirror it op for op
    once K and V are sliced out of it. Quantized adds p windows
    (1, H_kv, bs) for each of the two f32 scale pools; the dequant
    product happens as each block lands (g scratch f32; V is cast to
    the output dtype where the reference casts it, after the gather).
    GQA (hp < h) repeats the gathered rows across each query-head group
    — a pure copy, identical to the reference's repeat of its dense
    gather, so the bitwise pin holds.

    Only a lane's live groups are steps at all, and the value path
    runs once, at the lane's last step, over the key positions of its
    live groups and no further (rounded up to `walk_rung` groups, and
    those cleared, where a table holds more than 8)."""
    del fetch_ref                           # read by the index maps
    kv_refs, rest = rest[:p], rest[p:]
    if quantized:
        ks_refs, vs_refs, rest = rest[:p], rest[p:2 * p], rest[2 * p:]
    o_ref, g_ref = rest
    step = pl.program_id(0)
    b, j = lane_ref[step], group_ref[step]
    c = pos_ref.shape[1]
    n_groups, n_all = groups_ref[b], -(-m // p)

    @pl.when(n_groups > 0)
    def _gather():
        for i in range(p):
            col = j * p + i
            rows = pl.ds(pl.multiple_of(col * bs, bs), bs)
            is_live = live_ref[b, col] != 0

            @pl.when(is_live)
            def _land():
                blk = kv_refs[i][0]                   # (H_kv, bs, 2*D)
                if quantized:
                    blk = _dequant(blk, ks_refs[i][0], vs_refs[i][0])
                g_ref[:, rows, :] = blk

            # the rest of a live group must hold zeros, not stale VMEM:
            # its (masked) probabilities are exactly 0 and 0 * 0 keeps
            # the PV partial sums bitwise-identical to the reference's
            # 0 * null-block terms. Columns the table's padding added
            # past M have no rows.
            dead = jnp.logical_not(is_live)
            if (n_all - 1) * p + i >= m:
                dead &= col < m

            @pl.when(dead)
            def _clear():
                g_ref[:, rows, :] = jnp.zeros((hp, bs, 2 * d),
                                              g_ref.dtype)

    # ---- value path: the reference body on the VMEM-resident gather --
    # (same einsums batched over H, same mask constant, same
    # jax.nn.softmax — the bitwise pin lives here), at the width of the
    # lane's live groups: one branch a group count, each of static shape
    def _attend(t):
        q = q_ref[0]                                      # (H, C, D)
        g = g_ref[:, :t, :]
        gk = _repeat_heads(g[..., :d], h // hp)
        gv = _repeat_heads(g[..., d:].astype(o_ref.dtype), h // hp)
        s = jnp.einsum("hcd,htd->hct", q.astype(gk.dtype), gk,
                       precision=_mxu_precision(gk.dtype),
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        key_pos = jax.lax.broadcasted_iota(jnp.int32, (c, t), 1)
        mask = (key_pos <= _pos_matrix(pos_ref, b, c, (c, t), 0))[None]
        s = jnp.where(mask, s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1).astype(gv.dtype)
        o_ref[0] = jnp.einsum(
            "hct,htd->hcd", prob, gv, precision=_mxu_precision(gv.dtype),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    rung = walk_rung(bs, m)
    for lo in range(0, n_all, rung):
        hi = min(lo + rung, n_all)

        @pl.when((j == n_groups - 1) & (n_groups > lo) & (n_groups <= hi))
        def _(lo=lo, hi=hi):
            for dead in range(lo + 1, hi):      # none while rung is 1
                rows = slice(dead * p * bs, min((dead + 1) * p, m) * bs)

                @pl.when(dead >= n_groups)
                def _clear_group():
                    g_ref[:, rows, :] = jnp.zeros(
                        (hp, rows.stop - rows.start, 2 * d), g_ref.dtype)

            _attend(min(hi * p, m) * bs)

    @pl.when(n_groups == 0)
    def _idle():
        o_ref[0] = jnp.zeros_like(o_ref[0])


@functools.partial(jax.jit, static_argnames=(
    "name", "kernel", "statics", "group", "scratch", "out_shape",
    "out_dtype", "vmem_bytes", "interpret"))
def _paged_call(q, kv_pool, scales, block_table, q_positions, *, name,
                kernel, statics, group, scratch, out_shape, out_dtype,
                vmem_bytes, interpret):
    """The pallas_call every walk shares: one grid step a live group of
    `group` table columns of a lane (`_plan_walk`; the grid's bound is
    a value, not a shape), the walk's plan and the positions
    scalar-prefetched, q/out one lane per block (whatever lies behind
    the lane axis), the pool (and each scale pool) `group`
    table-addressed blocks per step: the one pool operand is handed in
    once a window, each with its own index map. `statics` are the
    kernel's own keyword facts (a tuple of pairs), beside the block
    size, the table width and the group every walk is told.

    Jitted, so that the layers of a step, which call it with the same
    shapes, share ONE trace of the kernel and ONE lowering to Mosaic:
    48 layers each tracing and lowering v1's eight branches put 14 s
    into every start of a server, cache or no cache."""
    bs = kv_pool.shape[2]
    m = block_table.shape[1]

    def lane_spec(shape):
        zeros = (0,) * (len(shape) - 1)
        return pl.BlockSpec((1,) + tuple(shape[1:]),
                            lambda s, lane, *plan: (lane[s],) + zeros)

    pools = [kv_pool] + scales
    in_specs = [lane_spec(q.shape)]
    for pool in pools:
        in_specs += _page_specs(pool.shape, group)
    steps, plan = _plan_walk(block_table, q_positions, bs, group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,          # the plan's five, positions
        grid=(steps,),
        in_specs=in_specs,
        out_specs=lane_spec(out_shape),
        scratch_shapes=[pltpu.VMEM(shp, dt) for shp, dt in scratch],
    )
    return pl.pallas_call(
        functools.partial(kernel, bs=bs, m=m, p=group, **dict(statics)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        compiler_params=_compiler_params(vmem_bytes),
        name=name,
        interpret=interpret,
    )(*plan, q_positions.astype(jnp.int32), q,
      *[pool for pool in pools for _ in range(group)])


def _kv_statics(h, hp, d, quantized):
    """What the two K-beside-V kernels are told beside the walk."""
    return (("h", h), ("hp", hp), ("d", d), ("quantized", quantized))


def _v1_scratch_shapes(hp, bs, d, m, pool_dtype):
    """v1's VMEM gather: one lane's K and V side by side at full table
    width, in the pool's dtype; int8 pools are dequantized as they land
    and sit in f32."""
    dt = jnp.float32 if pool_dtype == jnp.int8 else pool_dtype
    return [((hp, m * bs, 2 * d), dt)]


def v1_scratch_bytes(hp, bs, d, m, pool_dtype):
    """VMEM bytes of that scratch as Mosaic tiles it — what the
    dispatcher holds against its v1 ceiling."""
    return sum(_padded_bytes(shp, dt) for shp, dt in
               _v1_scratch_shapes(hp, bs, d, m, pool_dtype))


def ragged_paged_attention(q, kv_pool, block_table, q_positions,
                           k_scale=None, v_scale=None, interpret=None):
    """Paged attention kernel v1: gather-then-compute table walk.

    Same contract as `serving.kv_cache.paged_attention` (which is the
    dispatcher that normally routes here):

        q:           (B, H, C, D) — C query tokens per request lane
        kv_pool:     (N, H_kv, bs, 2*D), K beside V (f32, bf16 or
                     int8); H_kv == H (MHA) or a divisor of H (GQA)
        block_table: (B, M) int32 (NULL_BLOCK-padded)
        q_positions: (B, C) int32
        k/v_scale:   (N, H_kv, bs) f32 — required for int8 pools (the
                     per-row dequant scales; dequant is fused into the
                     kernel's gather), absent otherwise
        returns      (B, H, C, D) in the pool's dtype (int8 pools: in
                     q's dtype)

    `interpret` defaults to "off-TPU" (flash.py policy)."""
    global TRACE_COUNT
    TRACE_COUNT += 1
    b, h, c, d, n, hp, bs, m, quantized = _validate_paged_args(
        q, kv_pool, block_table, q_positions, k_scale, v_scale)
    if interpret is None:
        interpret = _interpret()
    out_dtype = q.dtype if quantized else kv_pool.dtype
    scratch = _v1_scratch_shapes(hp, bs, d, m, kv_pool.dtype)
    # the gather, its two head-repeated slices (each padded back to the
    # scratch's 128 lanes at head_dim 64), and the (H, C, T) scores
    vmem = (v1_scratch_bytes(hp, bs, d, m, kv_pool.dtype)
            + 2 * _padded_bytes((h, m * bs, d), scratch[0][1])
            + 3 * _padded_bytes((h, c, m * bs), jnp.float32))
    return _paged_call(q, kv_pool,
                       [k_scale, v_scale] if quantized else [],
                       block_table, q_positions,
                       name="paged_attention_v1", kernel=_paged_kernel,
                       statics=_kv_statics(h, hp, d, quantized),
                       group=walk_group(bs, m), scratch=tuple(scratch),
                       out_shape=q.shape, out_dtype=out_dtype,
                       vmem_bytes=vmem,
                       interpret=bool(interpret))


# ---------------------------------------------------------------------------
# kernel v2: a group walk + online softmax, each KV head's query heads one tile
# ---------------------------------------------------------------------------

def _v2_scratch_shapes(h, hp, c, d, t, pool_dtype):
    """The v2 VMEM scratch contract, exposed for the white-box test: a
    group's t key positions (t = `walk_group` x bs: 128, or one block
    where a block is wider), K beside V at H_kv heads in the pool's
    dtype (f32 for int8 pools, which are dequantized as they land),
    and the online-softmax carry (running max, exp-sum, PV partial, all
    f32) of each KV head's (H / H_kv) x C query rows. NO dimension
    depends on the table width M — that independence IS the
    unbounded-context claim. Returns [(shape, dtype), ...]."""
    dt = jnp.float32 if pool_dtype == jnp.int8 else pool_dtype
    rows = (h // hp) * c
    return [((hp, t, 2 * d), dt), ((hp, rows, 1), jnp.float32),
            ((hp, rows, 1), jnp.float32), ((hp, rows, d), jnp.float32)]


def _paged_kernel_v2(lane_ref, group_ref, fetch_ref, live_ref,
                     groups_ref, pos_ref, q_ref, *rest, bs, m, p, h, hp,
                     d, quantized=False):
    """Grid step s: lane b = lane[s], its j-th live GROUP of p table
    columns (j = group[s]), every KV head. q_ref (1, H_kv, g*C, D): the
    lane's queries with the g = H / H_kv query heads of a KV head as
    one tile of rows (row gi*C + ci is head hk*g + gi, column ci: the
    contiguous-group convention makes that a free reshape of (H, C,
    D)). Then p windows (1, H_kv, bs, 2*D): pool blocks
    fetch[b, j*p : (j+1)*p], K in lanes [0, D) and V in [D, 2*D)
    (quantized adds p windows (1, H_kv, bs) for each of the two f32
    scale pools, and a block is dequantized as it lands). The group's
    blocks land in `g_ref` (H_kv, p*bs, 2*D) in position order, a dead
    column's rows as zeros; ONE product batched over the KV heads
    scores each head's g*C rows against the group's keys, and a second
    against the same rows' V lanes folds them into the flash-style
    carry (m: running row max, l: rescaled exp-sum, acc: rescaled PV
    partial, all f32). No head is repeated.

    Arithmetic: scores take bf16 operands where q and the pool are
    both bf16 (every product exact in the f32 accumulator), f32
    otherwise; probabilities enter the value product in f32, against
    the V lanes in f32. NULL blocks (padding, idle lanes) and columns
    past the lane's last live block are masked out and land as zeros:
    nothing they hold — garbage, NaN poison — is ever multiplied.

    Two traps the masking dodges, pinned by tests:
    * NEG_INF is finite (-1e9), so on an all-masked prefix
      m_new == NEG_INF and exp(s - m_new) == exp(0) == 1 for masked
      entries — probabilities MUST come from
      `where(mask, exp(s - m_new), 0)`, never from the bare exp;
    * an idle lane finishes with l == 0; dividing by
      `where(l > 0, l, 1)` lands an exact 0 output instead of NaN (the
      engine's non-finite-logits guard sums every lane's logps)."""
    del fetch_ref, m                        # read by the index maps
    kv_refs, rest = rest[:p], rest[p:]
    if quantized:
        ks_refs, vs_refs, rest = rest[:p], rest[p:2 * p], rest[2 * p:]
    o_ref, g_ref, m_ref, l_ref, acc_ref = rest
    step = pl.program_id(0)
    b, j = lane_ref[step], group_ref[step]
    c = pos_ref.shape[1]
    rows, t = (h // hp) * c, p * bs
    n_groups = groups_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_groups > 0)
    def _fold():
        for i in range(p):
            span = pl.ds(i * bs, bs)
            is_live = live_ref[b, j * p + i] != 0

            @pl.when(is_live)
            def _land():
                blk = kv_refs[i][0]                   # (H_kv, bs, 2*D)
                if quantized:
                    blk = _dequant(blk, ks_refs[i][0], vs_refs[i][0])
                g_ref[:, span, :] = blk

            @pl.when(jnp.logical_not(is_live))
            def _clear():
                g_ref[:, span, :] = jnp.zeros((hp, bs, 2 * d),
                                              g_ref.dtype)

        kv = g_ref[...]                               # (H_kv, t, 2*D)
        q = q_ref[0]                                  # (H_kv, g*C, D)
        sdt = jnp.promote_types(q.dtype, kv.dtype)
        s = jnp.einsum("hrd,htd->hrt", q.astype(sdt),
                       kv[..., :d].astype(sdt),
                       precision=_mxu_precision(sdt),
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        # the mask is one (g*C, t) tile for every KV head: row r asks
        # at its column's position, pos[b, r % C]; key k of the group
        # is position j*t + k, and attends only where its column lives
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0)
        key = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
        q_pos = jnp.full((rows, t), pos_ref[b, 0], jnp.int32)
        if c > 1:
            col = jax.lax.rem(row, c)
            for ci in range(1, c):
                q_pos = jnp.where(col == ci, pos_ref[b, ci], q_pos)
        dead = jnp.full((rows, t), 1 - live_ref[b, j * p], jnp.int32)
        for i in range(1, p):
            dead = jnp.where(key >= i * bs, 1 - live_ref[b, j * p + i],
                             dead)
        mask = ((j * t + key <= q_pos) & (dead == 0))[None]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # on an all-masked prefix both maxes sit at the finite NEG_INF,
        # so m_prev - m_new == 0 and corr == 1 exactly — the carry stays
        # untouched instead of decaying through exp(-1e9)
        corr = jnp.exp(m_prev - m_new)
        prob = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(prob, axis=-1,
                                                 keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hrt,htd->hrd", prob, kv[..., d:].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == jnp.maximum(n_groups, 1) - 1)
    def _flush():
        # idle lanes (every key masked) land l == 0: divide by 1 and
        # output an exact 0 — never NaN
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
            o_ref.dtype)


def ragged_paged_attention_v2(q, kv_pool, block_table, q_positions,
                              k_scale=None, v_scale=None,
                              interpret=None):
    """Paged attention kernel v2: a group walk with a flash-style
    online softmax. Identical call contract to
    `ragged_paged_attention` (v1); the difference is the resource
    shape — VMEM is one group of 128 key positions plus the carry
    (`_v2_scratch_shapes`) regardless of the table width, and
    scores/softmax/PV accumulate in f32 for EVERY pool dtype, with the
    output cast once at the end. v2 is mathematically exact vs the
    reference but reorders its fp reductions (per-group partial sums +
    rescales), so the tier-1 pin is tight-allclose + argmax-identical
    rather than v1's bitwise."""
    global TRACE_COUNT, V2_TRACE_COUNT
    TRACE_COUNT += 1
    V2_TRACE_COUNT += 1
    b, h, c, d, n, hp, bs, m, quantized = _validate_paged_args(
        q, kv_pool, block_table, q_positions, k_scale, v_scale)
    if interpret is None:
        interpret = _interpret()
    out_dtype = q.dtype if quantized else kv_pool.dtype
    group = walk_group(bs, m)
    rows, t = (h // hp) * c, group * bs
    scratch = _v2_scratch_shapes(h, hp, c, d, t, kv_pool.dtype)
    # the group and the carry, the (H_kv, g*C, t) scores and their
    # temporaries, the V lanes in f32
    vmem = (sum(_padded_bytes(shp, dt) for shp, dt in scratch)
            + 4 * _padded_bytes((hp, rows, t), jnp.float32)
            + 2 * _padded_bytes((hp, t, d), jnp.float32))
    out = _paged_call(q.reshape(b, hp, rows, d), kv_pool,
                      [k_scale, v_scale] if quantized else [],
                      block_table, q_positions,
                      name="paged_attention_v2", kernel=_paged_kernel_v2,
                      statics=_kv_statics(h, hp, d, quantized),
                      group=group, scratch=tuple(scratch),
                      out_shape=(b, hp, rows, d), out_dtype=out_dtype,
                      vmem_bytes=vmem,
                      interpret=bool(interpret))
    return out.reshape(b, h, c, d)


# ---------------------------------------------------------------------------
# the latent walk: one row a token, read by every head
# ---------------------------------------------------------------------------

LATENT_TRACE_COUNT = 0


def latent_row_width(kv_lora_rank, rope_dim):
    """Minor dim of a latent pool: the token's compressed KV and its
    one rotated key, `[c_kv | k_rope]`, padded with zeros to whole
    128-lane tiles (576 -> 640). The device pads a minor dim to the
    tile anyway, so the padding is stated, and the score product runs
    over whole tiles."""
    return -(-(int(kv_lora_rank) + int(rope_dim)) // 128) * 128


def _latent_scratch_shapes(rows, width, value_width, p, bs, pool_dtype):
    """The latent walk's VMEM scratch: the group's keys in position
    order, and the online-softmax carry (running max, exp-sum, value
    partial) of all heads' rows. Nothing depends on the table width."""
    return [((p * bs, width), pool_dtype),
            ((rows, 1), jnp.float32), ((rows, 1), jnp.float32),
            ((rows, value_width), jnp.float32)]


def _latent_kernel(lane_ref, group_ref, fetch_ref, live_ref, groups_ref,
                   pos_ref, q_ref, *rest, bs, m, p, heads, value_width,
                   scale):
    """Grid step s: lane b = lane[s], its j-th live GROUP of p table
    columns (j = group[s]), every head of every column at once.

    q_ref (1, C * H, W): the lane's absorbed queries, column-major
    (row ci * H + h is head h of column ci), `[q_nope W_uk^T | q_rope]`
    padded like the pool's rows. Then p windows (1, 1, bs, W): pool
    blocks fetch[b, j*p : (j+1)*p], `[c_kv | k_rope | 0]` a token, ONE
    row whoever the head. The group's rows land in `k_ref` in position
    order (a dead column's rows as zeros); one (C*H, W) x (W, p*bs)
    product scores them for all heads, and the VALUES are the first
    `value_width` lanes of the same VMEM rows: the pool is read once.
    The rows fold into a flash-style carry (m, l, acc: f32), as v2's
    do, with v2's two traps dodged the same way (probabilities come
    from where(mask, exp, 0); an idle lane divides by 1). A lane that
    feeds one token computes its first column's rows only."""
    del fetch_ref, m                        # read by the index maps
    kv_refs, rest = rest[:p], rest[p:]
    o_ref, k_ref, m_ref, l_ref, acc_ref = rest
    step = pl.program_id(0)
    b, j = lane_ref[step], group_ref[step]
    c = pos_ref.shape[1]
    rows, t = c * heads, p * bs
    n_groups = groups_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_groups > 0)
    def _land_group():
        for i in range(p):
            span = pl.ds(i * bs, bs)
            is_live = live_ref[b, j * p + i] != 0

            @pl.when(is_live)
            def _land():
                k_ref[span, :] = kv_refs[i][0, 0]

            @pl.when(jnp.logical_not(is_live))
            def _clear():
                k_ref[span, :] = jnp.zeros((bs, k_ref.shape[1]),
                                           k_ref.dtype)

    def fold(n_cols):
        """Fold the group into the carry of the lane's first `n_cols`
        columns (static): their n_cols * heads rows."""
        n = n_cols * heads
        keys = k_ref[...]                                   # (t, W)
        q = q_ref[0, :n, :].astype(keys.dtype)              # (n, W)
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            precision=_mxu_precision(keys.dtype),
            preferred_element_type=jnp.float32) * scale     # (n, t)
        # row r belongs to column r // heads: its query position
        row = jax.lax.broadcasted_iota(jnp.int32, (n, t), 0)
        q_pos = jnp.full((n, t), pos_ref[b, 0], jnp.int32)
        for ci in range(1, n_cols):
            q_pos = jnp.where(row >= ci * heads, pos_ref[b, ci], q_pos)
        key = jax.lax.broadcasted_iota(jnp.int32, (n, t), 1)
        dead = jnp.full((n, t), 1 - live_ref[b, j * p], jnp.int32)
        for i in range(1, p):
            dead = jnp.where(key >= i * bs,
                             1 - live_ref[b, j * p + i], dead)
        mask = (j * t + key <= q_pos) & (dead == 0)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:n, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        prob = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[:n, :] = l_ref[:n, :] * corr + jnp.sum(prob, axis=-1,
                                                     keepdims=True)
        acc_ref[:n, :] = acc_ref[:n, :] * corr + jnp.dot(
            prob.astype(keys.dtype), keys[:, :value_width],
            precision=_mxu_precision(keys.dtype),
            preferred_element_type=jnp.float32)
        m_ref[:n, :] = m_new

    # A lane's live columns hold consecutive positions and the rest
    # hold 0 (the scheduler's contract, `_plan_block_writes`), so a
    # lane whose second column does not continue its first feeds ONE
    # token: a decode lane. Its other columns' rows are padding: they
    # are not computed, and stay the zeros the carry starts from.
    if c == 1:
        pl.when(n_groups > 0)(lambda: fold(1))
    else:
        one = pos_ref[b, 1] <= pos_ref[b, 0]
        pl.when((n_groups > 0) & one)(lambda: fold(1))
        pl.when((n_groups > 0) & jnp.logical_not(one))(lambda: fold(c))

    @pl.when(j == jnp.maximum(n_groups, 1) - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
            o_ref.dtype)


def paged_latent_attention(q, kv_pool, block_table, q_positions, *,
                           value_width, scale, interpret=None):
    """Paged attention over a LATENT pool (multi-head latent attention
    in its absorbed form): the walk of `_plan_walk` / `_paged_call`,
    over one row a token that every head reads.

        q:           (B, C, H, W) absorbed queries, `[q_nope W_uk^T |
                     q_rope]` zero-padded to the pool's row width
        kv_pool:     (N, 1, bs, W), `[c_kv | k_rope | 0]` a token (f32
                     or bf16; `latent_row_width`)
        block_table: (B, M) int32 (NULL_BLOCK-padded)
        q_positions: (B, C) int32
        value_width: the leading lanes of a row that are its value
                     (kv_lora_rank); `scale` multiplies the scores
        returns      (B, C, H, value_width) in the pool's dtype: each
                     head's probabilities over c_kv, which the caller
                     expands through W_uv

    Scores and softmax state are f32 whatever the pool holds. The same
    serving contract as v1/v2: NULL and stale blocks never enter the
    arithmetic, an idle lane is an exact zero, prefill chunks and
    decode tokens are one kernel."""
    global TRACE_COUNT, LATENT_TRACE_COUNT
    TRACE_COUNT += 1
    LATENT_TRACE_COUNT += 1
    b, c, h, w = q.shape
    n, one, bs, wp = kv_pool.shape
    if one != 1 or wp != w or not 0 < value_width <= w:
        raise ValueError(
            f"latent pool {kv_pool.shape} and q {q.shape} do not match "
            f"(a latent pool is (N, 1, bs, W), one row a token; q is "
            f"(B, C, H, W) at the same W; value_width {value_width})")
    m = block_table.shape[1]
    if block_table.shape[0] != b or q_positions.shape != (b, c):
        raise ValueError(
            f"table {block_table.shape} / positions {q_positions.shape} "
            f"do not match q {q.shape}")
    if interpret is None:
        interpret = _interpret()
    group = walk_group(bs, m)
    scratch = _latent_scratch_shapes(c * h, w, value_width, group, bs,
                                     kv_pool.dtype)
    vmem = (sum(_padded_bytes(shp, dt) for shp, dt in scratch)
            + 2 * _padded_bytes((c * h, w), q.dtype)
            + 4 * _padded_bytes((c * h, group * bs), jnp.float32))
    out = _paged_call(q.reshape(b, c * h, w), kv_pool, [], block_table,
                      q_positions, name="paged_latent_attention",
                      kernel=_latent_kernel,
                      statics=(("heads", h), ("value_width", value_width),
                               ("scale", float(scale))),
                      group=group, scratch=tuple(scratch),
                      out_shape=(b, c * h, value_width),
                      out_dtype=kv_pool.dtype, vmem_bytes=vmem,
                      interpret=bool(interpret))
    return out.reshape(b, c, h, value_width)


# ---------------------------------------------------------------------------
# reading whole blocks out of a pool, where the pool lies
# ---------------------------------------------------------------------------

def _copy_block_kernel(blk_ref, pool_ref, o_ref):
    del blk_ref                     # read by the index_map alone
    o_ref[...] = pool_ref[...]


def gather_pool_blocks(pool, blocks, interpret=None):
    """pool[blocks] for whole blocks: pool (N, H, bs, ...), blocks (G,)
    int32 -> (G, H, bs, ...). One block a grid step, the id scalar-
    prefetched into the pool's index_map as the attention kernels do
    it, so the pool is read in the row-major layout those kernels read
    it in. For an XLA gather of the same blocks of a 64-lane pool the
    TPU's compiler re-laid the whole pool out first, 52 MB to fetch 1.6
    (PERF.md section 6, PR 26). NULL may repeat among `blocks`; each repeat
    reads it again."""
    if interpret is None:
        interpret = _interpret()
    g = blocks.shape[0]
    block = (1,) + tuple(pool.shape[1:])
    rest = (0,) * (pool.ndim - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # blocks
        grid=(g,),
        in_specs=[pl.BlockSpec(block, lambda i, blk: (blk[i],) + rest)],
        out_specs=pl.BlockSpec(block, lambda i, blk: (i,) + rest),
    )
    return pl.pallas_call(
        _copy_block_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g,) + tuple(pool.shape[1:]),
                                       pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="gather_pool_blocks",
        interpret=interpret,
    )(blocks.astype(jnp.int32), pool)
