"""The chunked gated delta rule (KDA: linear attention whose state
decays per key channel), for TPU (Pallas).

A head keeps a state `S` (d_k x d_v, float32) in place of a cache of
keys and values. A token moves it by

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

with `alpha_t = exp(g_t)` in (0, 1] a decay per key channel and
`beta_t` in [0, 2] a step size. `kda_chunk` applies the C columns a
lane feeds in one serving step to the lane's carried state in the
CHUNKWISE form: with `G_t` the running sum of `g` over the chunk,
`K+ = K exp(G)`, `Q+ = Q exp(G)`, `K- = K exp(-G) beta`,

    (I + tril(K+ K-^T, -1)) U = V - K+ S_0        (forward substitution)
    O   = Q+ S_0 + tril(Q+ K-^T) U
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G) beta)^T U

which is the recurrence exactly (the WY / UT transform of the C rank-one
updates). A lane's valid columns are a prefix: a padded column is
`g = 0, beta = 0` (the entry masks both itself), which leaves the state
as it was, so a prefill chunk, a chunk that ends a prompt and a decode
lane's single column take this one path, and a lane with no valid
column gets its state back bitwise. A lane that starts a request
(`reset`) starts from the zero state inside the call.

`exp(-G)` is formed on its own, so the form is exact while a chunk's
summed decay stays inside float32's range: `-G_C <= 80` (clamped
there, finite and wrong past it). At 16 columns that is a decay of
e^-5 a token, a channel that forgets everything at once.

The kernel's grid is (lane, block of heads); the state block is read,
rewritten and aliased to its output, float32 throughout, and every
product has operands in the activations' type with float32
accumulation. `kda_chunk_reference` is the same chunk in plain
`jax.numpy`, the kernel's spec and the dispatcher's fallback
(`serving/kv_cache.kda_chunk`); `kda_recurrence` is the token-by-token
rule the tests hold both to. Off-TPU the kernel runs under the Pallas
interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged import _interpret, _mxu_precision, _padded_bytes

__all__ = ["kda_chunk", "kda_chunk_reference", "kda_recurrence"]

TRACE_COUNT = 0
MAX_CHUNK_DECAY = 80.0      # the largest -G a chunk may sum to


def _masked_gates(g, beta, counts):
    """(G (S, C, H, dk) the running sum of the valid columns' log
    decays, beta with the padded columns' at 0)."""
    c = g.shape[1]
    live = (jnp.arange(c)[None, :] < counts[:, None])[..., None]
    g = jnp.where(live[..., None], g.astype(jnp.float32), 0.0)
    return (jnp.cumsum(g, axis=1),
            jnp.where(live, beta.astype(jnp.float32), 0.0))


def kda_recurrence(q, k, v, g, beta, state, counts, reset):
    """The rule token by token (`lax.scan` over the chunk's columns),
    float32 at the highest precision: what the chunk forms compute.
    Shapes as `kda_chunk`."""
    c = q.shape[1]
    hi = jax.lax.Precision.HIGHEST
    state = jnp.swapaxes(state, 2, 3)                   # (S,H,dk,dv)
    s0 = jnp.where(reset[:, None, None, None], 0.0,
                   state.astype(jnp.float32))

    def step(s, xs):
        qt, kt, vt, gt, bt, t = xs
        live = (t < counts)[:, None, None, None]
        sd = s * jnp.exp(gt.astype(jnp.float32))[..., None]
        pred = jnp.einsum("shkv,shk->shv", sd, kt, precision=hi)
        new = sd + bt[..., None, None] * jnp.einsum(
            "shk,shv->shkv", kt, vt - pred, precision=hi)
        s = jnp.where(live, new, s)
        return s, jnp.einsum("shkv,shk->shv", s, qt, precision=hi)

    f32 = [jnp.moveaxis(a.astype(jnp.float32), 1, 0)
           for a in (q, k, v, g, beta)]
    s, o = jax.lax.scan(step, s0, (*f32, jnp.arange(c)))
    return jnp.moveaxis(o, 0, 1), jnp.swapaxes(jnp.where(
        (counts > 0)[:, None, None, None], s, state), 2, 3)


def kda_chunk_reference(q, k, v, g, beta, state, counts, reset):
    """`kda_chunk` in plain `jax.numpy`: the chunkwise form over all
    lanes and heads at once, float32 at the highest precision."""
    c = q.shape[1]
    hi = jax.lax.Precision.HIGHEST
    gsum, beta = _masked_gates(g, beta, counts)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    state = jnp.swapaxes(state, 2, 3)                   # (S,H,dk,dv)
    s0 = jnp.where(reset[:, None, None, None], 0.0,
                   state.astype(jnp.float32))
    e_neg = jnp.exp(jnp.minimum(-gsum, MAX_CHUNK_DECAY))
    kp, qp = k * jnp.exp(gsum), q * jnp.exp(gsum)
    kmb = k * e_neg * beta[..., None]
    t_idx = jnp.arange(c)
    strict = (t_idx[:, None] > t_idx[None, :])[None, None]
    a = jnp.where(strict, jnp.einsum("sthk,suhk->shtu", kp, kmb,
                                     precision=hi), 0.0)
    p = jnp.where((t_idx[:, None] >= t_idx[None, :])[None, None],
                  jnp.einsum("sthk,suhk->shtu", qp, kmb, precision=hi),
                  0.0)
    rhs = v - jnp.einsum("schk,shkv->schv", kp, s0, precision=hi)
    u = jax.scipy.linalg.solve_triangular(
        jnp.eye(c) + a, jnp.moveaxis(rhs, 1, 2), lower=True,
        unit_diagonal=True)                                 # (S,H,C,dv)
    o = jnp.einsum("schk,shkv->schv", qp, s0, precision=hi) \
        + jnp.einsum("shtu,shuv->sthv", p, u, precision=hi)
    g_end = gsum[:, -1]                                     # (S, H, dk)
    kt = k * jnp.exp(g_end[:, None] - gsum) * beta[..., None]
    new = s0 * jnp.exp(g_end)[..., None] + jnp.einsum(
        "schk,shcv->shkv", kt, u, precision=hi)
    return o, jnp.swapaxes(jnp.where(
        (counts > 0)[:, None, None, None], new, state), 2, 3)


def _kda_kernel(count_ref, reset_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                s_ref, o_ref, s_out_ref):
    """Grid step (lane, head block): q, k, g (1, HB, C, dk); v
    (1, HB, C, dv); b (1, HB, C, 1); the state block (1, HB, dv, dk),
    TRANSPOSED (value channel by key channel) so that a key channel's
    decay runs along the lanes."""
    lane = pl.program_id(0)
    cdt = q_ref.dtype
    prec = _mxu_precision(cdt)
    hb, c, _dk = q_ref.shape[1:]

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                          precision=prec,
                          preferred_element_type=jnp.float32)

    kept = s_ref[0]
    s0 = jnp.where(reset_ref[lane] > 0, 0.0, kept)          # (HB,dv,dk)
    gsum = g_ref[0]
    beta = b_ref[0]                                         # (HB,C,1)
    k = k_ref[0].astype(jnp.float32)
    e_pos = jnp.exp(gsum)
    kp = k * e_pos
    qp = q_ref[0].astype(jnp.float32) * e_pos
    kb = k * beta
    kmb = kb * jnp.exp(jnp.minimum(-gsum, MAX_CHUNK_DECAY))
    g_end = gsum[:, c - 1:c, :]                             # (HB,1,dk)
    # nt[h, s, t] = K+_t . K-_s: column t is what row t of the solve
    # subtracts
    row = jax.lax.broadcasted_iota(jnp.int32, (hb, c, c), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (hb, c, c), 2)
    nt = jnp.where(row < col, dot("hsk,htk->hst", kmb, kp), 0.0)
    pm = jnp.where(col <= row, dot("htk,hsk->hts", qp, kmb), 0.0)
    u = v_ref[0].astype(jnp.float32) - dot("hck,hvk->hcv", kp, s0)
    urow = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    for t in range(1, c):
        corr = jnp.sum(nt[:, :, t:t + 1] * u, axis=1, keepdims=True)
        u = u - jnp.where(urow == t, corr, 0.0)
    o_ref[0] = (dot("hck,hvk->hcv", qp, s0)
                + dot("hts,hsv->htv", pm, u)).astype(o_ref.dtype)
    new = s0 * jnp.exp(g_end) + dot("hcv,hck->hvk", u,
                                    kb * jnp.exp(g_end - gsum))
    s_out_ref[0] = jnp.where(count_ref[lane] > 0, new, kept)


@functools.partial(jax.jit, static_argnames=("heads_per_step",
                                             "interpret"))
def _kda_call(q, k, v, gsum, beta, state, counts, reset, *,
              heads_per_step, interpret):
    s, h, c, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_step

    def block(shape):
        return pl.BlockSpec((1, hb) + shape,
                            lambda l, j, cnt, rst: (l, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # counts, reset
        grid=(s, h // hb),
        in_specs=[block((c, dk)), block((c, dk)), block((c, dv)),
                  block((c, dk)), block((c, 1)), block((dv, dk))],
        out_specs=[block((c, dv)), block((dv, dk))],
    )
    vmem = 2 * (3 * _padded_bytes((hb, c, dk), q.dtype)
                + _padded_bytes((hb, c, dk), jnp.float32)
                + _padded_bytes((hb, c, 1), jnp.float32)
                + _padded_bytes((hb, c, dv), jnp.float32)
                + 2 * _padded_bytes((hb, dv, dk), jnp.float32)) \
        + 16 * _padded_bytes((hb, c, dk), jnp.float32) \
        + 3 * _padded_bytes((hb, dv, dk), jnp.float32)
    return pl.pallas_call(
        _kda_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, h, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the state is rewritten where it lies (operand 7, counting the
        # two prefetched scalars)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=int(min(vmem + (8 << 20), 100 << 20))),
        name="kda_chunk",
        interpret=interpret,
    )(counts, reset, q, k, v, gsum, beta, state)


def kda_chunk(q, k, v, g, beta, state, counts, reset,
              heads_per_step=None, interpret=None):
    """One chunk of every lane against its carried state:

        q, k:    (S, C, H, dk) a head's L2-normalised query (scaled)
                 and key, f32 or bf16
        v:       (S, C, H, dv)
        g:       (S, C, H, dk) log decay a key channel, <= 0
        beta:    (S, C, H) step size
        state:   (S, H, dv, dk) float32, a lane's carried state,
                 kept TRANSPOSED (value channel by key channel): a key
                 channel's decay then runs along the lanes, and the
                 kernel rewrites the array where it lies
        counts:  (S,) int32 valid columns (a prefix of the C)
        reset:   (S,) bool, the lane starts from the zero state
        returns  (o (S, C, H, dv) float32, the new state)

    A padded column's output is unspecified and finite."""
    global TRACE_COUNT
    TRACE_COUNT += 1
    h = q.shape[2]
    if heads_per_step is None:
        heads_per_step = next(n for n in (8, 4, 2, 1) if h % n == 0)
    if interpret is None:
        interpret = _interpret()
    gsum, beta = _masked_gates(g, beta, counts)

    def heads_first(a):
        return jnp.swapaxes(a, 1, 2)

    o, new = _kda_call(
        heads_first(q), heads_first(k), heads_first(v),
        heads_first(gsum), heads_first(beta)[..., None],
        state, counts.astype(jnp.int32), reset.astype(jnp.int32),
        heads_per_step=int(heads_per_step), interpret=bool(interpret))
    return heads_first(o), new
