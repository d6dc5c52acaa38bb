"""Power retention (attention whose weight is a gated square of the
query-key product, normalised by the sum of its weights), chunked, for
TPU (Pallas).

A KV head keeps a state in place of a cache of keys and values. With
`phi` the symmetric degree-2 expansion (`phi(x) . phi(y) = (x . y)^2`),
`a_t <= 0` the head's log-gate at token t, a token moves it by

    S_t = exp(a_t) S_{t-1} + phi(k_t) v_t^T      (D x d_v)
    z_t = exp(a_t) z_{t-1} + phi(k_t)            (D)

and a query head of the group reads it as

    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

(the scale 1 / sqrt(head_dim) is folded into q by the caller). Written
as attention this is `o_t = sum_{s<=t} w(t, s) v_s / (sum_{s<=t}
w(t, s) + EPS)` with `w(t, s) = exp(A_t - A_s) (q_t . k_s)^2`, `A` the
running sum of `a`.

**The expansion's layout** (`expansion_layout`): row r of `phi(x)` is
`x[left[r]] * x[right[r]]` over the pairs `left <= right`, row-major,
so D = d (d + 1) / 2 (8,256 at d = 128), padded with zero rows to a
multiple of 128 lanes (`state_rows`: 8,320). The key side is
unweighted; the QUERY side weighs a pair off the diagonal 2, so that
`phi_q(x) . phi_k(y) = (x . y)^2` exactly, with no square root of 2 in
either factor. The state holds the key side.

`power_retention_chunk` applies the C columns a lane feeds in one
serving step in the CHUNKWISE form: with `G_t` the running sum of `a`
over the chunk's valid columns,

    o_t = [sum_{s<=t} exp(G_t - G_s) (q_t . k_s)^2 v_s
           + exp(G_t) phi_q(q_t)^T S_0]
          / [sum_{s<=t} exp(G_t - G_s) (q_t . k_s)^2
             + exp(G_t) phi_q(q_t)^T z_0 + EPS]
    S_C = exp(G_C) S_0 + sum_s exp(G_C - G_s) phi(k_s) v_s^T

(z_C likewise), which is the recurrence exactly. Every exponent is of
a difference that is at most 0, so no chunk overflows whatever its
decay. A lane's valid columns are a prefix; a padded column takes no
part, so a prefill chunk, a chunk that ends a prompt and a decode
lane's one column take this one path. A lane that starts a request
(`reset`) starts from the zero state inside the call; a lane with no
valid column keeps its state bitwise.

**The kernel**: grid (lane, tile of R state rows), every KV head of
the lane in one step. A tile's rows of `phi(q)` and `phi(k)` are built
in VMEM from the head's d channels: two products with one-hot (d, R)
selections made from the layout's `left` and `right` rows (exact: each
picks one value), then one elementwise product. The G query heads of a
KV head are one tile of G x C rows against each state tile. The
intra-chunk scores are computed once a lane (at tile 0) into f32 VMEM
accumulators, each tile adds its decayed read, and the last tile
divides. The state and the normaliser are read, rewritten and aliased
to their outputs; float32 throughout, every product with operands in
the activations' type and float32 accumulation. A lane with no valid
column maps its state blocks onto those of the busy lane before it (or
after it), so the pipeline fetches and writes nothing for it.

`power_retention_chunk_reference` is the same chunk in plain
`jax.numpy` (the dispatcher's fallback, `serving/kv_cache.
power_retention_chunk`); `power_retention_recurrence` the token-by-token
rule the tests hold both to. Off-TPU the kernel runs under the Pallas
interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged import _interpret, _mxu_precision, _padded_bytes

__all__ = ["power_retention_chunk", "power_retention_chunk_reference",
           "power_retention_recurrence", "expansion_layout", "expand",
           "state_rows"]

EPS = 1e-6


def state_rows(d):
    """Rows of a head's state: the d (d + 1) / 2 pairs, padded to a
    multiple of 128."""
    return -(-(d * (d + 1) // 2) // 128) * 128


def expansion_layout(d):
    """(left, right, weight), each (state_rows(d),): row r of the
    expansion is x[left] * x[right], a pair left <= right in row-major
    order, -1 on a padding row; `weight` is the query side's, 2 off the
    diagonal, 1 on it, 0 on padding. Built from iotas, never a numpy
    table (a table in the step is a constant that
    JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS hoists out as an argument)."""
    rows = state_rows(d)
    r = jax.lax.iota(jnp.int32, rows)
    a = jax.lax.iota(jnp.int32, d)
    start = a * d - a * (a - 1) // 2            # first row of left = a
    left = jnp.sum(start[None, 1:] <= r[:, None], axis=1, dtype=jnp.int32)
    right = r - jnp.take(start, left) + left
    live = r < d * (d + 1) // 2
    left = jnp.where(live, left, -1)
    right = jnp.where(live, right, -1)
    weight = jnp.where(live, jnp.where(left == right, 1.0, 2.0), 0.0)
    return left, right, weight.astype(jnp.float32)


def expand(x, side):
    """phi(x) (..., state_rows(d)) float32 of x (..., d): the key side,
    or the query side (`side="query"`, off-diagonal pairs weighed 2)."""
    left, right, weight = expansion_layout(x.shape[-1])
    x = x.astype(jnp.float32)
    phi = jnp.where(left >= 0,
                    jnp.take(x, jnp.maximum(left, 0), axis=-1)
                    * jnp.take(x, jnp.maximum(right, 0), axis=-1), 0.0)
    return phi * weight if side == "query" else phi


def _gates(a, counts):
    """(G (S, C, K) the running sum of the valid columns' log-gates,
    G_C (S, K) its last value, live (S, C))."""
    c = a.shape[1]
    live = jax.lax.iota(jnp.int32, c)[None] < counts[:, None]
    g = jnp.cumsum(jnp.where(live[..., None], a.astype(jnp.float32), 0.0),
                   axis=1)
    return g, g[:, -1], live


def _start(state, norm, reset):
    return (jnp.where(reset[:, None, None, None], 0.0,
                      state.astype(jnp.float32)),
            jnp.where(reset[:, None, None], 0.0, norm.astype(jnp.float32)))


def power_retention_recurrence(q, k, v, a, state, norm, counts, reset):
    """The rule token by token (`lax.scan` over the chunk's columns),
    float32 at the highest precision: what the chunk forms compute.
    Shapes as `power_retention_chunk`."""
    s, c, h, d = q.shape
    kv = k.shape[2]
    hi = jax.lax.Precision.HIGHEST
    s0, z0 = _start(state, norm, reset)

    def step(carry, xs):
        st, z = carry
        qt, kt, vt, at, t = xs
        live = t < counts
        pk = expand(kt, "key")                              # (S,K,D)
        dec = jnp.exp(at)
        new_s = dec[..., None, None] * st + jnp.einsum(
            "skr,skv->skrv", pk, vt, precision=hi)
        new_z = dec[..., None] * z + pk
        st = jnp.where(live[:, None, None, None], new_s, st)
        z = jnp.where(live[:, None, None], new_z, z)
        pq = expand(qt.reshape(s, kv, h // kv, d), "query")  # (S,K,G,D)
        num = jnp.einsum("skgr,skrv->skgv", pq, st, precision=hi)
        den = jnp.einsum("skgr,skr->skg", pq, z, precision=hi)
        return (st, z), (num / (den[..., None] + EPS)).reshape(s, h, d)

    f32 = [jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (q, k, v, a)]
    (st, z), o = jax.lax.scan(step, (s0, z0),
                              (*f32, jax.lax.iota(jnp.int32, c)))
    busy = counts > 0
    return (jnp.moveaxis(o, 0, 1),
            jnp.where(busy[:, None, None, None], st, state),
            jnp.where(busy[:, None, None], z, norm))


def power_retention_chunk_reference(q, k, v, a, state, norm, counts,
                                    reset):
    """`power_retention_chunk` in plain `jax.numpy`: the chunkwise form
    over all lanes and heads at once, float32 at the highest
    precision."""
    s, c, h, d = q.shape
    kv = k.shape[2]
    hi = jax.lax.Precision.HIGHEST
    gsum, g_end, live = _gates(a, counts)
    s0, z0 = _start(state, norm, reset)
    qf = q.astype(jnp.float32).reshape(s, c, kv, h // kv, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    t_idx = jax.lax.iota(jnp.int32, c)
    seen = (t_idx[None, :, None] >= t_idx[None, None, :]) \
        & live[:, None, :]                                  # (S, t, u)
    diff = gsum[:, :, None, :] - gsum[:, None, :, :]        # (S,t,u,K)
    decay = jnp.where(seen[..., None], jnp.exp(jnp.minimum(diff, 0.0)),
                      0.0)
    scores = jnp.einsum("stkgd,sukd->stukg", qf, kf, precision=hi) ** 2
    w = scores * decay[..., None]
    eg = jnp.exp(gsum)[..., None]                           # (S,C,K,1)
    pq = expand(qf, "query")                                # (S,C,K,G,D)
    num = jnp.einsum("stukg,sukv->stkgv", w, vf, precision=hi) \
        + eg[..., None] * jnp.einsum("stkgr,skrv->stkgv", pq, s0,
                                     precision=hi)
    den = jnp.sum(w, axis=2) + eg * jnp.einsum(
        "stkgr,skr->stkg", pq, z0, precision=hi)
    o = (num / (den[..., None] + EPS)).reshape(s, c, h, d)
    e = jnp.where(live[..., None], jnp.exp(g_end[:, None] - gsum), 0.0)
    pk = expand(kf, "key") * e[..., None]                   # (S,C,K,D)
    keep = jnp.exp(g_end)
    new_s = keep[..., None, None] * s0 + jnp.einsum(
        "sckr,sckv->skrv", pk, vf, precision=hi)
    new_z = keep[..., None] * z0 + jnp.sum(pk, axis=1)
    busy = counts > 0
    return (o, jnp.where(busy[:, None, None, None], new_s, state),
            jnp.where(busy[:, None, None], new_z, norm))


def _retention_kernel(count_ref, reset_ref, mode_ref, lane_ref, tile_ref,
                      left_ref, right_ref, wrow_ref, q_ref, k_ref, v_ref,
                      rq_ref, wi_ref, ek_ref, keep_ref, s_ref, z_ref,
                      o_ref, s_out_ref, z_out_ref, num_ref, den_ref):
    """Grid step (lane, tile): q (1, K, G*C, d), rows (g, t); k, v
    (1, K, C, d); rq (1, K, G*C, 1) exp(G_t) a query row; wi
    (1, K, G*C, C) the intra-chunk decays, causal and valid; ek
    (1, K, C, 1) exp(G_C - G_s) a valid key column; keep (1, K, 1, 128)
    exp(G_C); the state tile (1, K, R, d) and the normaliser's
    (1, K, R). `mode` 0: the lane is busy; 1: idle, its state blocks
    are a busy lane's, left as they are; 2: idle in a call with no busy
    lane, every step maps onto one block and copies it through."""
    del count_ref, lane_ref, tile_ref
    lane, tile = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    mode = mode_ref[lane]
    kv, gc, d = q_ref.shape[1:]
    c = k_ref.shape[2]
    cdt = q_ref.dtype
    prec = _mxu_precision(cdt)

    def dot(x, y, contract=((1,), (0,))):
        return jax.lax.dot_general(
            x.astype(cdt), y.astype(cdt), (contract, ((), ())),
            precision=prec, preferred_element_type=jnp.float32)

    @pl.when(mode == 2)
    def _copy():
        s_out_ref[...] = s_ref[...]
        z_out_ref[...] = z_ref[...]

    @pl.when((mode != 0) & (tile == 0))
    def _idle():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(mode == 0)
    def _busy():
        reset = reset_ref[lane] > 0

        @pl.when(tile == 0)
        def _intra():
            for h in range(kv):
                sc = dot(q_ref[0, h], k_ref[0, h], ((1,), (1,)))
                w = sc * sc * wi_ref[0, h]                  # (GC, C)
                num_ref[h] = dot(w, v_ref[0, h])
                den_ref[h] = jnp.sum(w, axis=-1, keepdims=True)

        rows = left_ref.shape[1]
        chan = jax.lax.broadcasted_iota(jnp.int32, (d, rows), 0)
        sel_l = (chan == left_ref[...]).astype(cdt)         # (d, R)
        sel_r = (chan == right_ref[...]).astype(cdt)
        qs = q_ref[0].reshape(kv * gc, d)
        ks = k_ref[0].reshape(kv * c, d)
        pq = dot(qs, sel_l) * dot(qs, sel_r) * wrow_ref[...]   # (K*GC, R)
        pk = dot(ks, sel_l) * dot(ks, sel_r)                    # (K*C, R)
        for h in range(kv):
            s0 = jnp.where(reset, 0.0, s_ref[0, h])         # (R, d)
            z0 = jnp.where(reset, 0.0, z_ref[0, h:h + 1, :])   # (1, R)
            pqh = pq[h * gc:(h + 1) * gc]
            rq = rq_ref[0, h]                               # (GC, 1)
            num_ref[h] += rq * dot(pqh, s0)
            den_ref[h] += rq * jnp.sum(pqh * z0, axis=-1, keepdims=True)
            pkh = pk[h * c:(h + 1) * c] * ek_ref[0, h]      # (C, R)
            keep = keep_ref[0, h][:, 0:1]                   # (1, 1)
            s_out_ref[0, h] = keep * s0 + dot(pkh, v_ref[0, h],
                                              ((0,), (0,)))
            z_out_ref[0, h:h + 1, :] = keep * z0 + jnp.sum(
                pkh, axis=0, keepdims=True)

        @pl.when(tile == last)
        def _flush():
            o_ref[0] = num_ref[...] / (den_ref[...] + EPS)


def tile_rows(rows):
    """State rows a grid step takes: 128 x the largest divisor of
    rows / 128 up to 5 (640 of 8,320 at head_dim 128: 2.6 MB of state
    for a lane's 8 KV heads, 13 steps a lane)."""
    n = rows // 128
    return 128 * max(m for m in range(1, 6) if n % m == 0)


@functools.partial(jax.jit, static_argnames=("rows_per_step", "interpret"))
def _retention_call(plan, tables, q, k, v, rq, wi, ek, keep, state, norm,
                    *, rows_per_step, interpret):
    s, kv, gc, d = q.shape
    c = k.shape[2]
    rows = state.shape[2]
    r = rows_per_step
    steps = rows // r

    def lane_block(shape):
        zeros = (0,) * len(shape)
        return pl.BlockSpec((1,) + shape, lambda l, t, *_: (l,) + zeros)

    def state_map(l, t, cnt, rst, mode, lane_map, tile_map):
        return lane_map[l], 0, tile_map[l, t], 0

    def norm_map(l, t, cnt, rst, mode, lane_map, tile_map):
        return lane_map[l], 0, tile_map[l, t]

    table = pl.BlockSpec((1, r), lambda l, t, *_: (0, t))
    state_spec = pl.BlockSpec((1, kv, r, d), state_map)
    norm_spec = pl.BlockSpec((1, kv, r), norm_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # counts, reset, mode, lane, tile maps
        grid=(s, steps),
        in_specs=[table, table, table, lane_block((kv, gc, d)),
                  lane_block((kv, c, d)), lane_block((kv, c, d)),
                  lane_block((kv, gc, 1)), lane_block((kv, gc, c)),
                  lane_block((kv, c, 1)), lane_block((kv, 1, 128)),
                  state_spec, norm_spec],
        out_specs=[lane_block((kv, gc, d)), state_spec, norm_spec],
        scratch_shapes=[pltpu.VMEM((kv, gc, d), jnp.float32),
                        pltpu.VMEM((kv, gc, 1), jnp.float32)],
    )
    f32 = jnp.float32
    vmem = (2 * (2 * _padded_bytes((kv, r, d), f32)
                 + 2 * _padded_bytes((kv, r), f32)
                 + 3 * _padded_bytes((1, r), f32)
                 + _padded_bytes((kv, gc, d), q.dtype)
                 + 2 * _padded_bytes((kv, c, d), q.dtype)
                 + 2 * _padded_bytes((kv, gc, 1), f32)
                 + _padded_bytes((kv, gc, c), f32)
                 + _padded_bytes((kv, c, 1), f32)
                 + _padded_bytes((kv, 1, 128), f32)
                 + _padded_bytes((kv, gc, d), f32))
            + 4 * _padded_bytes((d, r), f32)
            + 4 * _padded_bytes((kv * gc, r), f32)
            + 4 * _padded_bytes((r, d), f32))
    return pl.pallas_call(
        _retention_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, kv, gc, d), f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(norm.shape, f32)],
        # the state and the normaliser are rewritten where they lie
        # (operands 15 and 16, counting the five prefetched plans)
        input_output_aliases={15: 1, 16: 2},
        compiler_params=pltpu.CompilerParams(
            # an idle lane's steps revisit a neighbour's blocks: lanes
            # and tiles run in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(vmem + (8 << 20), 100 << 20))),
        name="power_retention_chunk",
        interpret=interpret,
    )(*plan, *tables, q, k, v, rq, wi, ek, keep, state, norm)


def _fetch_plan(counts, reset, steps):
    """What the grid fetches for each lane: (counts, reset, mode, the
    lane and the (S, steps) tiles whose state blocks a lane's steps
    map onto). A busy lane maps onto its own tiles; an idle one onto
    the last tile of the busy lane before it, else the first of the
    busy lane after it (which its steps then precede), so that the
    pipeline copies nothing for it; with no busy lane every step maps
    onto lane 0's first tile."""
    s = counts.shape[0]
    busy = counts > 0
    lanes = jax.lax.iota(jnp.int32, s)
    before = jax.lax.cummax(jnp.where(busy, lanes, -1))
    after = jax.lax.cummin(jnp.where(busy, lanes, s), reverse=True)
    lane_map = jnp.where(busy, lanes,
                         jnp.where(before >= 0, before,
                                   jnp.where(after < s, after, 0)))
    fixed = jnp.where(before >= 0, steps - 1, 0)
    tile_map = jnp.where(busy[:, None],
                         jax.lax.iota(jnp.int32, steps)[None],
                         fixed[:, None])
    mode = jnp.where(busy, 0, jnp.where(jnp.any(busy), 1, 2))
    return (counts.astype(jnp.int32), reset.astype(jnp.int32),
            mode.astype(jnp.int32), lane_map.astype(jnp.int32),
            tile_map.astype(jnp.int32))


def power_retention_chunk(q, k, v, a, state, norm, counts, reset,
                          interpret=None):
    """One chunk of every lane against its carried state:

        q:       (S, C, H, d) a query head's normed, rotated query, the
                 1 / sqrt(d) folded in, f32 or bf16
        k, v:    (S, C, K, d) a KV head's normed, rotated key and value
        a:       (S, C, K) log-gate a KV head, <= 0
        state:   (S, K, state_rows(d), d) float32, the key side's
                 expansion times the values, decayed
        norm:    (S, K, state_rows(d)) float32, the normaliser
        counts:  (S,) int32 valid columns (a prefix of the C)
        reset:   (S,) bool, the lane starts from the zero state
        returns  (o (S, C, H, d) float32, the new state, the new
                 normaliser)

    Query head i reads KV head i // (H / K). A padded column's output
    is unspecified and finite."""
    s, c, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    rows = state.shape[2]
    r = tile_rows(rows)
    if interpret is None:
        interpret = _interpret()
    gsum, g_end, live = _gates(a, counts)
    t_idx = jax.lax.iota(jnp.int32, c)
    seen = (t_idx[None, :, None] >= t_idx[None, None, :]) \
        & live[:, None, :]                                  # (S, t, u)
    diff = jnp.moveaxis(gsum[:, :, None, :] - gsum[:, None, :, :], 3, 1)
    wi = jnp.where(seen[:, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    wi = jnp.broadcast_to(wi[:, :, None], (s, kv, g, c, c)).reshape(
        s, kv, g * c, c)
    rq = jnp.broadcast_to(jnp.exp(jnp.swapaxes(gsum, 1, 2))[:, :, None],
                          (s, kv, g, c)).reshape(s, kv, g * c, 1)
    ek = jnp.where(live[..., None], jnp.exp(g_end[:, None] - gsum), 0.0)
    ek = jnp.swapaxes(ek, 1, 2)[..., None]                  # (S,K,C,1)
    keep = jnp.broadcast_to(jnp.exp(g_end)[..., None, None],
                            (s, kv, 1, 128))
    left, right, weight = expansion_layout(d)
    q2 = q.reshape(s, c, kv, g, d).transpose(0, 2, 3, 1, 4).reshape(
        s, kv, g * c, d)
    o, state, norm = _retention_call(
        _fetch_plan(counts, reset, rows // r),
        (left[None], right[None], weight[None]), q2,
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(v.astype(q.dtype), 1, 2),
        rq, wi, ek, keep, state, norm,
        rows_per_step=r, interpret=bool(interpret))
    o = o.reshape(s, kv, g, c, d).transpose(0, 3, 1, 2, 4).reshape(
        s, c, h, d)
    return o, state, norm
