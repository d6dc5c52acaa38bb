"""Ragged paged attention for TPU (Pallas): the serving hot-loop kernel.

The pure-JAX reference (`serving/kv_cache.paged_attention_reference`)
materializes a dense (B, H, M*bs, D) gather of every request's FULL
block table on every fused step — each decode iteration pays
O(max_blocks) HBM traffic per lane regardless of how many tokens the
lane actually holds. The kernels here (per the *Ragged Paged Attention*
TPU paper, PAPERS.md) walk the block table INSIDE the kernel instead:
the grid is (lane, table column) and each block arrives through a
BlockSpec whose index_map reads the scalar-prefetched table, so the
Pallas pipeline issues (and double-buffers) the HBM->VMEM copies.

A layer's pool is ONE array (N, H_kv, bs, 2*D): the K row of a token in
lanes [0, D), its V row in [D, 2*D) (`serving/kv_cache.fuse_kv`). At
head_dim 64 the minor dim is the 128 lanes of a TPU tile, so the
device's own layout of the pool is the row-major one these kernels
read and no step re-lays a pool out (PERF.md section 6, PR 29); a grid
step is one DMA, not two. Two generations share that walk:

* **v1** (`ragged_paged_attention`): every live block is copied into
  an (H_kv, M*bs, 2*D) VMEM scratch as it arrives; the last grid step
  takes K and V out of it with two lane slices and runs the reference's
  exact op sequence on the VMEM-resident gather.
  f32 and int8 pools are pinned BITWISE against the reference under jit
  in interpret mode — the price is VMEM scratch proportional to the
  table width M.
* **v2** (`ragged_paged_attention_v2`): each arriving block folds
  straight into a flash-style online-softmax accumulator (running max,
  rescaled sum, rescaled PV partial, all f32 VMEM scratch), split into
  its K and V lanes as it lands. VMEM holds the pipeline's two block
  windows plus the carry — independent of M,
  so context length is unbounded at fixed VMEM. Online softmax is
  mathematically EXACT (every rescale is an identity in real
  arithmetic) but reorders the floating-point reductions the reference
  performs in one pass, so v2 is pinned allclose-at-f32-tightness plus
  argmax-identical — v1 remains the bitwise-stable kernel and the
  dispatcher's default for tables under the VMEM ceiling.

Both kernels share the serving contract:

* the block table and query positions ride scalar prefetch (SMEM), so
  block indices are available to the index_maps the way jax's own
  paged-attention kernel does it;
* per-lane early stop: past a lane's highest live block the index_map
  repeats the last live block index, so that the pipeline can skip a
  copy whose block index did not change, and the step's compute is
  predicated off (the HBM bytes this saves are not measured: PERF.md
  section 7);
* the NULL block (block 0 — table padding, masked-lane writes) never
  enters the arithmetic: padding entries and idle lanes contribute
  exactly nothing, even if block 0 holds garbage (pinned by NaN-poison
  tests);
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
  stay head-aligned. Both kernels repeat the VMEM-resident KV rows
  across each group (a pure copy, so v1's bitwise pin extends to GQA);
  HBM traffic stays at H_kv heads.

Why BlockSpecs and not hand-rolled `make_async_copy`: Mosaic refuses a
DMA slice of an array whose minor dim is under one 128-lane tile
("Slice shape along dimension 3 must be aligned to tiling (128), but is
64"), and head_dim 64 is the GPT-2 geometry. The pipeline's own copies
take any block whose trailing dims equal the array's.

VMEM budget: v1's scratch holds one lane's full K+V working set,
H_kv * M*bs * 2*D elements in the pool's dtype (f32 for int8 pools,
which are dequantized as they land) — the full-KV-resident discipline
of flash.py's default forward. At head_dim 64 the 2*D minor dim is
exactly the 128 lanes, so nothing is padded and the dispatcher's
estimate (`v1_scratch_bytes`) is what Mosaic allocates: 6.5 MB for 25
heads x 1,024 tokens of bf16, where two 64-lane scratches padded to
13. The K and V slices `_attend` takes are temporaries of the same
size again. v2 holds two block windows whatever M is; the dispatcher
(serving/kv_cache.paged_attention) routes tables past the v1 ceiling
to v2 automatically.

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


def _n_live(pos_ref, b, c, bs, m):
    """A lane's live-block count, from its highest query position
    (scalar reads; C is static and small). Always >= 1."""
    max_pos = pos_ref[b, 0]
    for ci in range(1, c):
        max_pos = jnp.maximum(max_pos, pos_ref[b, ci])
    return jnp.minimum(max_pos // bs + 1, m)


def _block_is_live(tbl_ref, pos_ref, b, j, bs, m):
    """Does grid step (b, j) hold a block to attend? Not past the
    lane's last live block, and not table padding / an idle lane's
    NULL_BLOCK — whatever the pipeline delivered for those steps is
    never touched."""
    return ((j < _n_live(pos_ref, b, pos_ref.shape[1], bs, m))
            & (tbl_ref[b, j] != NULL_BLOCK))


def _page_spec(block_shape, c, bs, m):
    """BlockSpec for one pool: grid step (b, j) sees pool block
    table[b, j]. Past the lane's last live block the index repeats, so
    the pipeline issues no further copies for that lane (early stop)."""
    zeros = (0,) * (len(block_shape) - 1)

    def index_map(b, j, tbl, pos):
        last = _n_live(pos, b, c, bs, m) - 1
        return (tbl[b, jnp.minimum(j, last)],) + zeros

    return pl.BlockSpec((1,) + tuple(block_shape[1:]), index_map)


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
    """Lanes are independent, table columns carry scratch state. The
    VMEM limit is stated (Mosaic's default scope is smaller than v1's
    gather at long tables) with headroom for the pipeline windows and
    value temporaries."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(2 * vmem_bytes + (8 << 20),
                                     32 << 20), 100 << 20)))


def _paged_kernel(tbl_ref, pos_ref, q_ref, kv_ref, *rest, bs, m, h, hp,
                  d, quantized=False):
    """Grid step (b, j): lane b, table column j, all heads — dense AND
    int8 pools share this walk (selected at trace time by `quantized`,
    so the early-stop arithmetic, the NULL guard, the zero-fill the
    bitwise pin depends on, and the mask/softmax tail exist exactly
    once).

    tbl_ref (B, M) / pos_ref (B, C): scalar-prefetched SMEM.
    q_ref (1, H, C, D); kv_ref (1, H_kv, bs, 2*D): pool block
    table[b, j], delivered by the pipeline, K in lanes [0, D) and V in
    [D, 2*D). g scratch (H_kv, M*bs, 2*D) VMEM — the lane's gathered
    view, rows in logical-position order exactly like the reference's
    dense gather, so the value-path math below can mirror it op for op
    once K and V are sliced out of it. Quantized adds the two
    (1, H_kv, bs) f32 scale blocks; the dequant product happens as each
    block lands (g scratch f32; V is cast to the output dtype where the
    reference casts it, after the gather). GQA (hp < h) repeats the
    gathered rows across each query-head group — a pure copy, identical
    to the reference's repeat of its dense gather, so the bitwise pin
    holds."""
    if quantized:
        ks_ref, vs_ref, o_ref, g_ref = rest
    else:
        o_ref, g_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    c = pos_ref.shape[1]
    t = m * bs

    # the skipped tail must hold zeros, not stale VMEM: its (masked)
    # probabilities are exactly 0 and 0 * 0 keeps the PV partial sums
    # bitwise-identical to the reference's 0 * null-block terms
    @pl.when(j == 0)
    def _zero():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(_block_is_live(tbl_ref, pos_ref, b, j, bs, m))
    def _gather():
        blk = kv_ref[0]                               # (H_kv, bs, 2*D)
        if quantized:
            blk = _dequant(blk, ks_ref[0], vs_ref[0])
        g_ref[:, pl.ds(pl.multiple_of(j * bs, bs), bs), :] = blk

    # ---- value path: the reference body on the VMEM-resident gather --
    # (same einsums batched over H, same mask constant, same
    # jax.nn.softmax — the bitwise pin lives here)
    @pl.when(j == m - 1)
    def _attend():
        q = q_ref[0]                                      # (H, C, D)
        g = g_ref[...]
        gk = _repeat_heads(g[..., :d], h // hp)
        gv = _repeat_heads(g[..., d:].astype(o_ref.dtype), h // hp)
        s = jnp.einsum("hcd,htd->hct", q.astype(gk.dtype), gk,
                       precision=_mxu_precision(gk.dtype),
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        key_pos = jax.lax.broadcasted_iota(jnp.int32, (c, t), 1)
        mask = (key_pos <= _pos_matrix(pos_ref, b, c, (c, t), 0))[None]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(gv.dtype)
        o_ref[0] = jnp.einsum(
            "hct,htd->hcd", p, gv, precision=_mxu_precision(gv.dtype),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _paged_call(name, kernel, q, kv_pool, scales, block_table,
                q_positions, scratch, out_dtype, vmem_bytes, interpret):
    """The pallas_call both generations share: grid (lane, table
    column), table + positions scalar-prefetched, q/out one lane per
    block, the pool (and each scale pool) one table-addressed block per
    step."""
    b, h, c, d = q.shape
    _n, hp, bs, _d2 = kv_pool.shape
    m = block_table.shape[1]
    lane_spec = pl.BlockSpec((1, h, c, d),
                             lambda b_, j, tbl, pos: (b_, 0, 0, 0))
    in_specs = [lane_spec]
    in_specs += [_page_spec(p.shape, c, bs, m)
                 for p in [kv_pool] + scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_table, q_positions
        grid=(b, m),
        in_specs=in_specs,
        out_specs=lane_spec,
        scratch_shapes=[pltpu.VMEM(shp, dt) for shp, dt in scratch],
    )
    return pl.pallas_call(
        functools.partial(kernel, bs=bs, m=m, h=h, hp=hp, d=d,
                          quantized=bool(scales)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, d), out_dtype),
        compiler_params=_compiler_params(vmem_bytes),
        name=name,
        interpret=interpret,
    )(block_table.astype(jnp.int32), q_positions.astype(jnp.int32),
      q, kv_pool, *scales)


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
    return _paged_call("paged_attention_v1", _paged_kernel, q, kv_pool,
                       [k_scale, v_scale] if quantized else [],
                       block_table, q_positions, scratch, out_dtype,
                       vmem, interpret)


# ---------------------------------------------------------------------------
# kernel v2: block streaming + online softmax
# ---------------------------------------------------------------------------

def _v2_scratch_shapes(h, c, d):
    """The v2 VMEM scratch contract, exposed for the white-box test:
    the online-softmax carry (running max, exp-sum, PV partial) — NO
    dimension depends on the table width M, and the block windows are
    the pipeline's own two block-sized buffers. That independence IS the
    unbounded-context claim. Returns [(shape, dtype), ...]."""
    return [((h, c, 1), jnp.float32), ((h, c, 1), jnp.float32),
            ((h, c, d), jnp.float32)]


def _paged_kernel_v2(tbl_ref, pos_ref, q_ref, kv_ref, *rest, bs, m, h,
                     hp, d, quantized=False):
    """Grid step (b, j): lane b, table column j, all heads. Block
    table[b, j] arrives through the pipeline (the NEXT block's copy is
    already in flight while this one computes — the two-window overlap),
    is split into its K lanes [0, D) and V lanes [D, 2*D), and folds
    into the online-softmax carry held in VMEM scratch
    (m: running row max, l: rescaled exp-sum, acc: rescaled PV partial,
    all f32). NULL blocks (padding, idle lanes) and columns past the
    lane's last live block are predicated off whole: nothing they hold
    — garbage, NaN poison — is ever multiplied.

    Two traps the masking dodges, pinned by tests:
    * NEG_INF is finite (-1e9), so on an all-masked prefix
      m_new == NEG_INF and exp(s - m_new) == exp(0) == 1 for masked
      entries — probabilities MUST come from
      `where(mask, exp(s - m_new), 0)`, never from the bare exp;
    * an idle lane finishes with l == 0; dividing by
      `where(l > 0, l, 1)` lands an exact 0 output instead of NaN (the
      engine's non-finite-logits guard sums every lane's logps)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    c = pos_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_is_live(tbl_ref, pos_ref, b, j, bs, m))
    def _fold():
        blk = kv_ref[0]                               # (H_kv, bs, 2*D)
        if quantized:
            blk = _dequant(blk, ks_ref[0], vs_ref[0])
        blk = blk.astype(jnp.float32)
        kb = _repeat_heads(blk[..., :d], h // hp)
        vb = _repeat_heads(blk[..., d:], h // hp)
        s = jnp.einsum("hcd,hbd->hcb", q_ref[0].astype(jnp.float32), kb,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        key_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (c, bs), 1)
        mask = (key_pos <= _pos_matrix(pos_ref, b, c, (c, bs), 0))[None]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # on an all-masked prefix both maxes sit at the finite NEG_INF,
        # so m_prev - m_new == 0 and corr == 1 exactly — the carry stays
        # untouched instead of decaying through exp(-1e9)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hcb,hbd->hcd", p, vb, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == m - 1)
    def _flush():
        # idle lanes (every key masked) land l == 0: divide by 1 and
        # output an exact 0 — never NaN
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
            o_ref.dtype)


def ragged_paged_attention_v2(q, kv_pool, block_table, q_positions,
                              k_scale=None, v_scale=None,
                              interpret=None):
    """Paged attention kernel v2: block streaming with a flash-style
    online softmax. Identical call contract to
    `ragged_paged_attention` (v1); the difference is the resource
    shape — VMEM is two block windows plus the carry
    (`_v2_scratch_shapes`) regardless of the table width, and
    scores/softmax/PV accumulate in f32 for EVERY pool dtype, with the
    output cast once at the end. v2 is mathematically exact vs the
    reference but reorders its fp reductions (per-block partial sums +
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
    scratch = _v2_scratch_shapes(h, c, d)
    # carry + the f32 head-repeated views of one K and one V block
    vmem = (sum(_padded_bytes(shp, dt) for shp, dt in scratch)
            + 4 * _padded_bytes((h, bs, d), jnp.float32))
    return _paged_call("paged_attention_v2", _paged_kernel_v2, q,
                       kv_pool,
                       [k_scale, v_scale] if quantized else [],
                       block_table, q_positions, scratch, out_dtype,
                       vmem, interpret)


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
