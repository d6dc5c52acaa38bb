"""What a decoder block is made of, as the ONE fused step
(`engine._fused_step_body`) reads it: a `StepSpec` names, layer by
layer, the norm, the attention with its cache geometry, and the MLP;
the tables below hold each kind's arithmetic over (S, C) ragged lanes.

`gpt2-xl` is one spec (LayerNorm, learned positions, multi-head
attention over a K-beside-V pool, GELU MLP, head tied to the
embedding); the latent-attention / mixture-of-experts family
(`serving/latent_moe.py`) another (RMS norm, rotary positions on part
of each head, latent attention over a one-row-a-token pool, gated or
expert MLP, untied head); the linear-attention / mixture-of-experts
family (`serving/linear_moe.py`) a third (no positions at all, a
pattern of gated grouped-query layers over a K-beside-V pool and
delta-rule layers over a state a lane, expert MLPs, untied head). A
new family adds entries here and a spec; the step, the scheduler and
the cache manager do not change.

Every attention kind takes (ctx, hn, lp, layer_pools) and returns (the
residual's addend, the layer's rewritten pools); every MLP kind takes
(ctx, hn, lp) and returns (the addend, its counts or None). `ctx` is
what the step computed once for all layers (`StepContext`).
"""

import collections

import numpy as np

import jax
import jax.numpy as jnp

from ..models.gpt import _ln
from .kv_cache import (fuse_kv, kda_chunk, paged_attention,
                       paged_latent_attention, write_block_kv,
                       write_block_kv_quant)
from .moe import expert_share, step_stats

__all__ = ["LayerSpec", "StepSpec", "StepContext", "NORMS", "ATTENTIONS",
           "MLPS", "STATE_ATTENTIONS", "STATE_STATS", "fold_counts",
           "state_counts", "rms_norm", "rotary_angles",
           "rotate_interleaved", "short_conv"]

# one layer: the names of its norm, attention and MLP kinds
LayerSpec = collections.namedtuple("LayerSpec", "norm attention mlp")

# a model's block, layer by layer, and what surrounds the layers:
# `positions` "learned" (a table added to the embedding), "rotary"
# (turned into the attention's queries and keys) or "none" (the layers
# order the tokens themselves); `tied_head` whether
# the head is the embedding transposed or params["head"]; `heads`,
# `kv_heads`, `head_dim` as THIS caller sees them (H/tp inside a
# shard_map); `dims` whatever else a kind reads (the latent ranks, the
# routing constants)
StepSpec = collections.namedtuple(
    "StepSpec", "layers positions tied_head heads kv_heads head_dim "
                "norm_eps dims")

# what the step computes once and every layer reads: the spec, the grid
# (s, c), the residual's dtype, each column's position / write block /
# write row, validity, the tables, the row-parallel reduction, whether
# a shard_map is around, the int8-weight accessor, the rotary angles,
# and what a state layer reads: each lane's count of valid columns (a
# prefix of its C) and whether the lane STARTS a request in this step
# (position 0 in its first valid column: the layer zeroes its state)
# (None for a caller that builds a context for a kind that keeps none)
StepContext = collections.namedtuple(
    "StepContext", "spec s c xdt pos bidx off valid tables reduce_fn "
                   "in_shard_map w angles counts starts",
    defaults=(None, None))


def rms_norm(x, scale, eps):
    """x / rms(x) * scale, computed in float32, in x's type."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary_angles(pos, dim, theta):
    """(cos, sin) (..., dim / 2) f32 of pos x theta^(-2i / dim)."""
    # an iota, not an arange: JAX folds a float arange of constants into
    # a numpy table, and under JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS such a
    # table is hoisted out of the step as an argument it cannot place
    inv = jnp.power(jnp.float32(theta),
                    -2.0 * jax.lax.iota(jnp.float32, dim // 2) / dim)
    ang = pos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def rotate_interleaved(x, cos, sin):
    """Rotary embedding in the INTERLEAVED convention: the pair
    (x[2i], x[2i+1]) is turned by the i-th angle. x (..., dim); cos,
    sin broadcastable to (..., dim / 2). Float32 inside, x's type
    out."""
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# -- norms: (spec, x, params, name) -> normed x -------------------------

def _layer_norm(spec, x, p, name):
    return _ln(x, p[name + "_s"], p[name + "_b"])


def _rms_norm(spec, x, p, name):
    return rms_norm(x, p[name + "_s"], spec.norm_eps)


NORMS = {"layer_norm": _layer_norm, "rms_norm": _rms_norm}


# -- attentions ---------------------------------------------------------

def _attention_mha(ctx, hn, lp, pools):
    """Multi-head (or grouped-query) attention with biased projections
    over a K-beside-V pool (N, H_kv, bs, 2 * head_dim), dense or int8
    with its scale pools."""
    s, c, w = ctx.s, ctx.c, ctx.w
    h_count, kv_count, d = (ctx.spec.heads, ctx.spec.kv_heads,
                            ctx.spec.head_dim)
    kvp = pools["kv"]
    ks, vs = pools.get("k_scale"), pools.get("v_scale")
    q = (hn @ w(lp, "wq") + lp["bq"]).reshape(s, c, h_count, d)
    k = (hn @ w(lp, "wk") + lp["bk"]).reshape(s, c, kv_count, d)
    v = (hn @ w(lp, "wv") + lp["bv"]).reshape(s, c, kv_count, d)
    if ks is not None:
        kvp, ks, vs = write_block_kv_quant(kvp, ks, vs, k, v, ctx.bidx,
                                           ctx.off)
    else:
        kvp = write_block_kv(kvp, fuse_kv(k, v), ctx.bidx, ctx.off)
    o = paged_attention(q.transpose(0, 2, 1, 3), kvp, ctx.tables,
                        ctx.pos, k_scale=ks, v_scale=vs,
                        in_shard_map=ctx.in_shard_map)
    o = o.transpose(0, 2, 1, 3).reshape(s, c, h_count * d)
    layer = {"kv": kvp}
    if ks is not None:
        layer["k_scale"], layer["v_scale"] = ks, vs
    return (ctx.reduce_fn(o @ w(lp, "wo")) + lp["bo"]).astype(ctx.xdt), \
        layer


def _attention_latent(ctx, hn, lp, pools):
    """Multi-head latent attention in its ABSORBED form over a latent
    pool (N, 1, bs, W): the cache row of a token is `[N(c_kv) | rotated
    k_rope | 0]`; a head's query is `[q_nope W_uk^T | rotated q_rope |
    0]`, scored against the row at 1 / sqrt(nope + rope); the
    probabilities sum c_kv, which W_uv expands a head. Prefill chunks
    and decode tokens take this one path."""
    s, c = ctx.s, ctx.c
    spec, dm = ctx.spec, ctx.spec.dims
    heads, lora = spec.heads, dm["kv_lora_rank"]
    nope, rope, vd = dm["qk_nope"], dm["qk_rope"], dm["v_head_dim"]
    pool = pools["kv"]
    width = pool.shape[-1]
    cos, sin = ctx.angles
    cq = rms_norm(hn @ lp["wq_a"], lp["q_norm_s"], spec.norm_eps)
    q = (cq @ lp["wq_b"]).reshape(s, c, heads, nope + rope)
    kv = hn @ lp["wkv_a"]
    ckv = rms_norm(kv[..., :lora], lp["kv_norm_s"], spec.norm_eps)
    k_rope = rotate_interleaved(kv[..., lora:], cos, sin)
    q_rope = rotate_interleaved(q[..., nope:], cos[:, :, None],
                                sin[:, :, None])
    row = jnp.concatenate(
        [ckv, k_rope, jnp.zeros((s, c, width - lora - rope), ckv.dtype)],
        axis=-1)
    pool = write_block_kv(pool, row[:, :, None, :], ctx.bidx, ctx.off)
    wkv_b = lp["wkv_b"].reshape(lora, heads, nope + vd)
    q_abs = jnp.einsum("schn,lhn->schl", q[..., :nope],
                       wkv_b[..., :nope])
    q_lat = jnp.concatenate(
        [q_abs, q_rope,
         jnp.zeros((s, c, heads, width - lora - rope), q_abs.dtype)],
        axis=-1)
    o_lat = paged_latent_attention(
        q_lat, pool, ctx.tables, ctx.pos, value_width=lora,
        scale=1.0 / np.sqrt(nope + rope))
    o = jnp.einsum("schl,lhv->schv", o_lat.astype(hn.dtype),
                   wkv_b[..., nope:]).reshape(s, c, heads * vd)
    return (o @ lp["wo"]).astype(ctx.xdt), {"kv": pool}


def _attention_gqa_gated(ctx, hn, lp, pools):
    """Grouped-query attention with NO positional encoding and no
    bias, over a K-beside-V pool (N, H_kv, bs, 2 * head_dim): the
    causal order is all the position there is. The heads' outputs are
    multiplied by `sigmoid(h w_gate)`, one gate a value channel, before
    the output projection."""
    s, c = ctx.s, ctx.c
    heads, kv_heads, d = (ctx.spec.heads, ctx.spec.kv_heads,
                          ctx.spec.head_dim)
    q = (hn @ lp["wq"]).reshape(s, c, heads, d)
    kv = (hn @ lp["wkv"]).reshape(s, c, 2, kv_heads, d)
    pool = write_block_kv(pools["kv"], fuse_kv(kv[:, :, 0], kv[:, :, 1]),
                          ctx.bidx, ctx.off)
    o = paged_attention(q.transpose(0, 2, 1, 3), pool, ctx.tables,
                        ctx.pos, in_shard_map=ctx.in_shard_map)
    o = o.transpose(0, 2, 1, 3).reshape(s, c, heads * d)
    gate = jax.nn.sigmoid((hn @ lp["w_gate"]).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate).astype(hn.dtype)
    return (o @ lp["wo"]).astype(ctx.xdt), {"kv": pool}


def short_conv(z, carried, taps, counts):
    """A causal depthwise convolution over a lane's chunk with the rows
    it carries from the chunk before: z (S, C, ch) the chunk's
    pre-activation rows, carried (S, K - 1, ch) the last K - 1 rows
    before them, taps (K, ch). Returns (y (S, C, ch) float32 with
    y_t = sum_j taps[j] z_{t - (K - 1) + j}, the rows to carry on: the
    last K - 1 before column `counts`, so that a lane with no valid
    column carries on what it had)."""
    k = taps.shape[0]
    c = z.shape[1]
    full = jnp.concatenate([carried.astype(z.dtype), z], axis=1)
    w = taps.astype(jnp.float32)
    y = sum(full[:, j:j + c].astype(jnp.float32) * w[j]
            for j in range(k))
    keep = counts[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    return y, jnp.take_along_axis(full, keep[..., None], axis=1)


def _l2_normalised(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _attention_kda(ctx, hn, lp, pools):
    """Gated delta-rule linear attention with a decay per key channel
    (KDA) over a STATE a lane, `pools["state"]` (S, H, dv, dk) float32
    and `pools["conv"]` (S, 3, channels): q, k and v go through a short
    causal convolution (whose last rows the lane carries) and SiLU, q
    and k are L2-normalised a head, the decay is `-exp(A_log) *
    softplus(low-rank(h) + dt_bias)` and the step size `2 sigmoid(h
    w_beta)`; `kda_chunk` moves the state by the lane's valid columns
    and gives each column's read of it, which is RMS-normed a head,
    gated by `sigmoid(low-rank(h))` and projected. A lane that starts a
    request starts from zero state and zero carried rows HERE, in the
    step; a lane with no valid column keeps both as they were."""
    s, c = ctx.s, ctx.c
    spec, dm = ctx.spec, ctx.spec.dims
    heads, dk, dv = dm["kda_heads"], dm["kda_key_dim"], dm["kda_value_dim"]
    carried = jnp.where(ctx.starts[:, None, None], 0, pools["conv"])
    y, conv = short_conv(hn @ lp["wqkv"], carried, lp["conv_w"],
                         ctx.counts)
    y = jax.nn.silu(y)
    q = _l2_normalised(y[..., :heads * dk].reshape(s, c, heads, dk)) \
        * (dk ** -0.5)
    k = _l2_normalised(
        y[..., heads * dk:2 * heads * dk].reshape(s, c, heads, dk))
    v = y[..., 2 * heads * dk:].reshape(s, c, heads, dv)
    dt = ((hn @ lp["w_fa"]) @ lp["w_fb"]).astype(jnp.float32) \
        + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(dt).reshape(s, c, heads, dk)
    beta = 2.0 * jax.nn.sigmoid((hn @ lp["w_beta"]).astype(jnp.float32))
    xdt = hn.dtype
    o, state = kda_chunk(q.astype(xdt), k.astype(xdt), v.astype(xdt), g,
                         beta, pools["state"], ctx.counts, ctx.starts)
    o = rms_norm(o, lp["o_norm_s"], spec.norm_eps)         # a head, f32
    gate = jax.nn.sigmoid(
        ((hn @ lp["w_ga"]) @ lp["w_gb"]).astype(jnp.float32))
    o = (o.reshape(s, c, heads * dv) * gate).astype(xdt)
    return (o @ lp["wo"]).astype(ctx.xdt), {"state": state, "conv": conv}


ATTENTIONS = {"mha": _attention_mha, "latent": _attention_latent,
              "gqa_gated": _attention_gqa_gated, "kda": _attention_kda}

# the kinds whose cache is a state a lane, and what a step counts of
# them (`state_counts`)
STATE_ATTENTIONS = ("kda",)
STATE_STATS = ("lane_calls", "columns", "resets")


def state_counts(ctx, layers):
    """A step's counts over its `layers` state layers (`STATE_STATS`,
    int32): (lane, layer) pairs with a valid column, each of which
    reads and writes one state; valid columns x layers; and the lanes
    that began a request in this step."""
    return jnp.stack([
        layers * jnp.sum(ctx.counts > 0, dtype=jnp.int32),
        layers * jnp.sum(ctx.counts, dtype=jnp.int32),
        jnp.sum(ctx.starts, dtype=jnp.int32)])


# -- MLPs ---------------------------------------------------------------

def _mlp_gelu(ctx, hn, lp):
    w = ctx.w
    f = jax.nn.gelu(hn @ w(lp, "f0w") + lp["f0b"], approximate=False)
    return (ctx.reduce_fn(f @ w(lp, "f1w")) + lp["f1b"]).astype(
        ctx.xdt), None


def _mlp_gated(ctx, hn, lp):
    f = jax.nn.silu(hn @ lp["w_gate"]) * (hn @ lp["w_up"])
    return (f @ lp["w_down"]).astype(ctx.xdt), None


def _mlp_experts(ctx, hn, lp):
    """This chip's share of a routed expert layer (`moe.expert_share`):
    padded columns are routed nowhere."""
    dm = ctx.spec.dims
    out, stats = expert_share(
        hn.reshape(ctx.s * ctx.c, -1), lp, ctx.valid.reshape(-1),
        k=dm["experts_per_tok"], scaling=dm["routed_scaling"],
        normalize=dm["norm_topk_prob"], offset=dm["expert_offset"])
    return out.reshape(hn.shape).astype(ctx.xdt), stats


MLPS = {"gelu": _mlp_gelu, "gated": _mlp_gated, "experts": _mlp_experts}

# a step's counts from those of its layers that count: one kind counts
# today, the expert layer its routing (`moe.MOE_STATS`), and a model of
# that kind names the vector's entries (`step_counters`)
fold_counts = step_stats
