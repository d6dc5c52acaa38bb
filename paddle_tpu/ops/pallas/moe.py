"""The held experts of a mixture-of-experts layer, for TPU (Pallas).

A chip of an expert-parallel deployment holds a few of a layer's
experts. A step hands the layer T token columns, each with its
combine weight for every held expert (zero where the router sent the
token elsewhere). The kernel `moe_experts` walks the held experts that
GOT a token, expert after expert (the assignments grouped by expert),
a tile of `tile` of an expert's tokens a grid step:

* the plan (`_plan_groups`, plain XLA on the (T, E) selection: a
  cumulative sum, no sort) names each step's expert and tile and each
  token's rank among its expert's tokens; the grid's bound is the sum
  of the experts' tiles, a value, as the paged walk's is
  (`ops/pallas/paged._plan_walk`). An expert nobody chose is no step,
  and its weights are never read;
* a step's expert arrives through BlockSpecs (gate|up and down, 9.4 MB
  at 2048 x 768 in bf16), so the pipeline fetches the next expert's
  weights while this one computes, and a second tile of the same
  expert moves nothing. An expert whose two buffers would pass
  `WHOLE_EXPERT_VMEM_BYTES` (31.5 MB at 4096 x 1280: 63 MB of the
  chip's 128) arrives a slice of its INNER width at a time instead, on
  a second grid axis (`_inner_blocks`): the gate and up columns and
  the down rows of the slice, whose partial products add up;
* the tile's tokens are gathered from the VMEM-resident activations by
  a one-hot product (rank == row), which is exact, and their weighted
  results are scattered back into a float32 (T, hidden) accumulator the
  same way: no token is dropped whatever the imbalance, because an
  expert simply takes as many tiles as its tokens need.

Shapes are static for the worst case (every column here: T * E / tile
+ E steps); what a step costs is what its tokens touch.

Off-TPU the kernel runs under the Pallas interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged import _interpret, _mxu_precision, _padded_bytes

TRACE_COUNT = 0
# an expert's gate activation, by the name `moe_experts` is given
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# what an expert's double-buffered weights may take of VMEM before the
# kernel takes its inner width in slices
WHOLE_EXPERT_VMEM_BYTES = 32 << 20


def _plan_groups(sel, tile):
    """sel (T, E) bool: token t goes to held expert e. Returns
    (steps, expert (S,), tile_of (S,), rank (E, 1, T)): the live steps'
    count, each step's expert and which tile of that expert's tokens
    it takes (S = the worst case, E * ceil(T / tile)), and each
    token's rank among its expert's tokens, -1 where it has none."""
    t, e = sel.shape
    sel = sel.astype(jnp.int32)
    rank = jnp.where(sel > 0, jnp.cumsum(sel, axis=0) - 1, -1)
    tiles = -(-jnp.sum(sel, axis=0) // tile)                    # (E,)
    worst = e * -(-t // tile)
    step = jnp.arange(worst, dtype=jnp.int32)
    done = step[:, None] >= jnp.cumsum(tiles)[None]     # (steps, E)
    expert = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), e - 1)
    tile_of = step - jnp.sum(jnp.where(done, tiles[None], 0), axis=1)
    # at least one step: with nobody routed here, a dead expert's tile 0
    # picks no token (every rank is -1) and the step writes the zeros
    return (jnp.maximum(jnp.sum(tiles), 1).astype(jnp.int32),
            expert.astype(jnp.int32),
            tile_of.astype(jnp.int32), rank.T[:, None, :])


def _inner_blocks(hidden, inner, dtype):
    """How many slices an expert's inner width is taken in: 1 (the
    whole expert a grid step) while its two buffers fit
    `WHOLE_EXPERT_VMEM_BYTES`, else the fewest whole-lane-tile slices
    that do."""
    def buffers(n):
        return 2 * (_padded_bytes((hidden, 2 * inner // n), dtype)
                    + _padded_bytes((inner // n, hidden), dtype))
    for n in range(1, inner // 128 + 1):
        if inner % (n * 128) == 0 and \
                buffers(n) <= WHOLE_EXPERT_VMEM_BYTES:
            return n
    return 1


def _tile_rows(tile_ref, x_ref, rank_ref, rank_c_ref, comb_ref, tile):
    """What a grid step computes of its tile whatever the weights'
    blocking: (the tile's tokens gathered (tile, H), each row's combine
    weight (tile, 1) f32, the (T, tile) one-hot that scatters the rows
    back)."""
    s = pl.program_id(0)
    x = x_ref[...]
    t = x.shape[0]
    base = tile_ref[s] * tile
    # (tile, T): row r takes the token whose rank is base + r
    want = base + jax.lax.broadcasted_iota(jnp.int32, (tile, t), 0)
    pick = rank_ref[0] == want
    gather = jnp.where(pick, 1.0, 0.0).astype(x.dtype)
    xg = jnp.dot(gather, x, precision=_mxu_precision(x.dtype),
                 preferred_element_type=jnp.float32).astype(x.dtype)
    # each row's combine weight, in f32: the one nonzero of its row
    w_row = jnp.sum(jnp.where(pick, comb_ref[0], 0.0), axis=1,
                    keepdims=True)
    # (T, tile): token t receives row rank[t] - base
    col = base + jax.lax.broadcasted_iota(jnp.int32, (t, tile), 1)
    scatter = jnp.where(rank_c_ref[0] == col, 1.0, 0.0).astype(x.dtype)
    return xg, w_row, scatter


def _moe_kernel(expert_ref, tile_ref, x_ref, rank_ref, rank_c_ref,
                comb_ref, gu_ref, down_ref, o_ref, *, tile, inner,
                activation):
    """Grid step s: expert e = expert[s], the tile_of[s]-th tile of its
    tokens. x_ref (T, H) and o_ref (T, H) f32 stay in VMEM for the
    whole call; rank_ref (1, 1, T) / rank_c_ref (1, T, 1) are e's ranks
    along lanes and along sublanes, comb_ref (1, 1, T) its combine
    weights; gu_ref (1, H, 2 * inner), down_ref (1, inner, H)."""
    del expert_ref                          # read by the index maps
    dt = x_ref.dtype
    prec = _mxu_precision(dt)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xg, w_row, scatter = _tile_rows(tile_ref, x_ref, rank_ref,
                                    rank_c_ref, comb_ref, tile)
    gu = jnp.dot(xg, gu_ref[0], precision=prec,
                 preferred_element_type=jnp.float32)
    act = (ACTIVATIONS[activation](gu[:, :inner])
           * gu[:, inner:]).astype(dt)
    y = jnp.dot(act, down_ref[0], precision=prec,
                preferred_element_type=jnp.float32)        # (tile, H)
    o_ref[...] += jnp.dot(scatter, (y * w_row).astype(dt),
                          precision=prec,
                          preferred_element_type=jnp.float32)


def _moe_kernel_sliced(expert_ref, tile_ref, x_ref, rank_ref, rank_c_ref,
                       comb_ref, gate_ref, up_ref, down_ref, o_ref, *,
                       tile, activation):
    """Grid step (s, j): as `_moe_kernel`, over slice j of the expert's
    inner width: gate_ref and up_ref (1, H, inner / n), down_ref
    (1, inner / n, H). The slices' products add up in o_ref."""
    del expert_ref
    dt = x_ref.dtype
    prec = _mxu_precision(dt)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xg, w_row, scatter = _tile_rows(tile_ref, x_ref, rank_ref,
                                    rank_c_ref, comb_ref, tile)
    gate = jnp.dot(xg, gate_ref[0], precision=prec,
                   preferred_element_type=jnp.float32)
    up = jnp.dot(xg, up_ref[0], precision=prec,
                 preferred_element_type=jnp.float32)
    y = jnp.dot((ACTIVATIONS[activation](gate) * up).astype(dt),
                down_ref[0],
                precision=prec, preferred_element_type=jnp.float32)
    o_ref[...] += jnp.dot(scatter, (y * w_row).astype(dt),
                          precision=prec,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "activation",
                                             "interpret"))
def _moe_call(x, sel, comb, w_gu, w_down, *, tile, activation,
              interpret):
    t, h = x.shape
    e, _h, inner2 = w_gu.shape
    steps, expert, tile_of, rank = _plan_groups(sel, tile)
    rank_c = jnp.swapaxes(rank, 1, 2)                       # (E, T, 1)
    comb_t = comb.astype(jnp.float32).T[:, None, :]         # (E, 1, T)

    inner = inner2 // 2
    n = _inner_blocks(h, inner, w_gu.dtype)
    width = inner // n

    def whole(shape):
        return pl.BlockSpec(shape, lambda s, *_: (0,) * len(shape))

    def of_expert(shape):
        return pl.BlockSpec((1,) + shape[1:],
                            lambda s, *a: (a[-2][s],) + (0,) * (
                                len(shape) - 1))

    rows = [whole((t, h)), of_expert(rank.shape), of_expert(rank_c.shape),
            of_expert(comb_t.shape)]
    if n == 1:
        grid, kernel = (steps,), functools.partial(
            _moe_kernel, tile=tile, inner=inner, activation=activation)
        weights = [of_expert(w_gu.shape), of_expert(w_down.shape)]
        operands = (w_gu, w_down)
    else:
        # slice j of the inner width: gate columns [j], up columns
        # [n + j] of the side-by-side array, down rows [j]
        grid, kernel = (steps, n), functools.partial(
            _moe_kernel_sliced, tile=tile, activation=activation)
        weights = [
            pl.BlockSpec((1, h, width), lambda s, j, ex, tl: (ex[s], 0, j)),
            pl.BlockSpec((1, h, width),
                         lambda s, j, ex, tl: (ex[s], 0, n + j)),
            pl.BlockSpec((1, width, h), lambda s, j, ex, tl: (ex[s], j, 0))]
        operands = (w_gu, w_gu, w_down)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # expert, tile_of
        grid=grid, in_specs=rows + weights, out_specs=whole((t, h)),
    )
    vmem = (2 * (_padded_bytes((h, 2 * width), w_gu.dtype)
                 + _padded_bytes((width, h), w_down.dtype))
            + 2 * _padded_bytes((t, h), x.dtype)
            + 2 * _padded_bytes((t, h), jnp.float32)
            + 4 * _padded_bytes((tile, 2 * width), jnp.float32)
            + 4 * _padded_bytes((t, 1), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=int(min(vmem + (16 << 20), 100 << 20))),
        name="moe_experts",
        interpret=interpret,
    )(expert, tile_of, x, rank, rank_c, comb_t, *operands)


def moe_experts(x, sel, comb, w_gu, w_down, tile=None, interpret=None,
                activation="silu"):
    """sum over held experts e of comb[t, e] * E_e(x[t]), for the
    tokens `sel` sends to e:

        x:      (T, H) the layer's normed input, f32 or bf16
        sel:    (T, E) bool, token t is routed to held expert e
        comb:   (T, E) f32 combine weights (read where sel)
        w_gu:   (E, H, 2 * I): an expert's gate and up side by side
        w_down: (E, I, H)
        returns (T, H) float32; E_e(h) = (act(h gate) * (h up)) down,
                act `activation`: "silu" (SwiGLU) or "relu" (ReGLU)

    An expert's tokens are computed in the activations' type with
    float32 accumulation, weighted in float32, and summed over the
    experts in float32. A call nobody is routed into (no step at all)
    still takes one step, which writes the zeros."""
    global TRACE_COUNT
    TRACE_COUNT += 1
    t = x.shape[0]
    if tile is None:
        tile = min(32, -(-t // 8) * 8)
    if interpret is None:
        interpret = _interpret()
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r}: expected one of "
                         f"{sorted(ACTIVATIONS)}")
    return _moe_call(x, sel, comb, w_gu, w_down, tile=int(tile),
                     activation=activation, interpret=bool(interpret))
