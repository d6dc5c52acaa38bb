"""What a decoder block is made of, as the ONE fused step
(`engine._fused_step_body`) reads it: a `StepSpec` names, layer by
layer, the norm, the attention with its cache geometry, and the MLP;
the tables below hold each kind's arithmetic over (S, C) ragged lanes.

`gpt2-xl` is one spec (LayerNorm, learned positions, multi-head
attention over a K-beside-V pool, GELU MLP, head tied to the
embedding); the latent-attention / mixture-of-experts family
(`serving/latent_moe.py`) another (RMS norm, rotary positions on part
of each head, latent attention over a one-row-a-token pool, gated or
expert MLP, untied head). A new family adds entries here and a spec;
the step, the scheduler and the cache manager do not change.

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
from .kv_cache import (fuse_kv, paged_attention, paged_latent_attention,
                       write_block_kv, write_block_kv_quant)
from .moe import expert_share, step_stats

__all__ = ["LayerSpec", "StepSpec", "StepContext", "NORMS", "ATTENTIONS",
           "MLPS", "fold_counts", "rms_norm", "rotary_angles",
           "rotate_interleaved"]

# one layer: the names of its norm, attention and MLP kinds
LayerSpec = collections.namedtuple("LayerSpec", "norm attention mlp")

# a model's block, layer by layer, and what surrounds the layers:
# `positions` "learned" (a table added to the embedding) or "rotary"
# (turned into the attention's queries and keys); `tied_head` whether
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
# a shard_map is around, the int8-weight accessor, the rotary angles
StepContext = collections.namedtuple(
    "StepContext", "spec s c xdt pos bidx off valid tables reduce_fn "
                   "in_shard_map w angles")


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


ATTENTIONS = {"mha": _attention_mha, "latent": _attention_latent}


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
