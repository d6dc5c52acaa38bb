"""What a decoder block is made of, as the ONE fused step
(`step._fused_step_body`) reads it: a `StepSpec` names, layer by
layer, the norm, the attention and the MLP; the tables below hold each
kind's arithmetic over (S, C) ragged lanes, what each attention kind
keeps in the cache (`cache_geometry`) and the names of what a step
counts (`step_counters`).

`gpt2-xl` is one spec (LayerNorm, learned positions, multi-head
attention over a K-beside-V pool, GELU MLP, head tied to the
embedding); the latent-attention / mixture-of-experts family
(`serving/latent_moe.py`) another (RMS norm, rotary positions on part
of each head, latent attention over a one-row-a-token pool, gated or
expert MLP, untied head); the linear-attention / mixture-of-experts
family (`serving/linear_moe.py`) a third (no positions at all, a
pattern of gated grouped-query layers over a K-beside-V pool and
delta-rule layers over a state a lane, expert MLPs, untied head); the
window / mixture-of-experts family (`serving/smallthinker.py`) a
fourth (grouped-query layers that are either windowed with half-split
rotary positions over a ring a lane, or full with none, softmax-routed
ReLU-gated experts whose router reads the attention's input, untied
head); the power-retention family (`serving/brumby.py`) a fifth (every
layer power retention over a state a lane, with normalised q and k and
half-split rotary positions, a dense gated MLP, untied head). A new
family is a config, its `init_params` and a spec
(`model.ServingModel`); a new kind adds its entries here. The step, the
engine, the scheduler and the cache manager do not change unless a
cache's lifetime is new.

Every attention kind takes (ctx, hn, lp, layer_pools) and returns (the
residual's addend, the layer's rewritten pools); every MLP kind takes
(ctx, hn, lp, attn_in), attn_in being what the layer's attention read
(a router placed before the attention reads it), and returns (the
addend, its counts or None). `ctx` is what the step computed once for
all layers (`StepContext`).
"""

import collections
import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..models.gpt import _ln
from ..ops.pallas.paged import latent_row_width
from ..ops.pallas.retention import state_rows
from .kv_cache import (fuse_kv, kda_chunk, paged_attention,
                       paged_latent_attention, power_retention_chunk,
                       write_block_kv, write_block_kv_quant)
from .moe import MOE_STATS, expert_share, step_stats

__all__ = ["LayerSpec", "StepSpec", "StepContext", "NORMS", "ATTENTIONS",
           "MLPS", "CACHE_GEOMETRY", "STATE_ATTENTIONS", "STATE_STATS",
           "RING_ATTENTIONS", "WINDOW_STATS", "cache_geometry",
           "step_counters", "fold_counts", "state_counts",
           "window_counts", "rms_norm", "qk_rms_norm", "rotary_angles",
           "rotate_interleaved", "rotate_half", "short_conv"]

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
# (position 0 in its first valid column: the layer zeroes its state),
# and what a window layer reads: its ring's table and write targets
# (`kv_cache.ring_table`) (None for a caller that builds a context for
# a kind that keeps none)
StepContext = collections.namedtuple(
    "StepContext", "spec s c xdt pos bidx off valid tables reduce_fn "
                   "in_shard_map w angles counts starts ring_tables "
                   "ring_bidx",
    defaults=(None, None, None, None))


def rms_norm(x, scale, eps):
    """x / rms(x) * scale, computed in float32, in x's type."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def qk_rms_norm(q, k, lp, eps):
    """Queries and keys (..., heads, head_dim) RMS-normed a head, over
    its head_dim channels, by the layer's `q_norm_s` and `k_norm_s`
    (head_dim,) scales: float32 out."""
    return (rms_norm(q.astype(jnp.float32), lp["q_norm_s"], eps),
            rms_norm(k.astype(jnp.float32), lp["k_norm_s"], eps))


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


def rotate_half(x, cos, sin):
    """Rotary embedding in the HALF-SPLIT convention: the pair (x[i],
    x[i + dim / 2]) is turned by the i-th angle. Shapes and types as
    `rotate_interleaved`."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    lo, hi = x32[..., :half], x32[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


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


def _attention_gqa(ctx, hn, lp, pools, gated=False, windowed=False):
    """Grouped-query attention with no bias, `q = h wq`, `[k | v] = h
    wkv`, over a K-beside-V pool (N, H_kv, bs, 2 * head_dim):

    * full (`gqa`, `gqa_gated`): no positional encoding, the causal
      order is all the position there is; the pool is the allocator's
      blocks, walked by `paged_attention`;
    * windowed (`gqa_window`): q and k turned by half-split rotary
      angles (`rotate_half`) over the whole head, a key visible only
      within `dims["window"]` positions of the query; the pool is the
      lane's ring (`ctx.ring_tables`), walked by
      `paged_attention(window=)`.

    `gated` (`gqa_gated`) multiplies the heads' outputs by `sigmoid(h
    w_gate)`, one gate a value channel, before the output
    projection."""
    s, c = ctx.s, ctx.c
    heads, kv_heads, d = (ctx.spec.heads, ctx.spec.kv_heads,
                          ctx.spec.head_dim)
    q = (hn @ lp["wq"]).reshape(s, c, heads, d)
    kv = (hn @ lp["wkv"]).reshape(s, c, 2, kv_heads, d)
    k, v = kv[:, :, 0], kv[:, :, 1]
    tables, bidx, window = ctx.tables, ctx.bidx, None
    if windowed:
        cos, sin = (a[:, :, None] for a in ctx.angles)
        q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
        tables, bidx = ctx.ring_tables, ctx.ring_bidx
        window = ctx.spec.dims["window"]
    pool = write_block_kv(pools["kv"], fuse_kv(k, v), bidx, ctx.off)
    o = paged_attention(q.transpose(0, 2, 1, 3), pool, tables, ctx.pos,
                        in_shard_map=ctx.in_shard_map, window=window)
    o = o.transpose(0, 2, 1, 3).reshape(s, c, heads * d)
    if gated:
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


def _attention_power(ctx, hn, lp, pools):
    """Power retention over a STATE a lane, `pools["state"]` (S, H_kv,
    D, head_dim) and `pools["norm"]` (S, H_kv, D) float32, D the rows of
    the degree-2 expansion (`ops/pallas/retention.py`): `q = h wq`,
    `[k | v] = h wkv`, q and k RMS-normed a head (`qk_rms_norm`) and
    turned by half-split rotary angles over the whole head, q scaled by
    1 / sqrt(head_dim); a log-gate a KV head `logsigmoid(h wa + ba)`;
    `power_retention_chunk` moves the state by the lane's valid columns
    and gives each column's normalised read of it, which `wo`
    projects. A lane that starts a request starts from the zero state
    HERE, in the step; a lane with no valid column keeps its state as
    it was."""
    s, c = ctx.s, ctx.c
    spec = ctx.spec
    heads, kv_heads, d = spec.heads, spec.kv_heads, spec.head_dim
    xdt = hn.dtype
    q = (hn @ lp["wq"]).reshape(s, c, heads, d)
    kv = (hn @ lp["wkv"]).reshape(s, c, 2, kv_heads, d)
    q, k = qk_rms_norm(q, kv[:, :, 0], lp, spec.norm_eps)
    cos, sin = (x[:, :, None] for x in ctx.angles)
    q = rotate_half(q, cos, sin) * (d ** -0.5)
    k = rotate_half(k, cos, sin)
    a = jax.nn.log_sigmoid((hn @ lp["wa"]).astype(jnp.float32)
                           + lp["ba"].astype(jnp.float32))
    o, state, norm = power_retention_chunk(
        q.astype(xdt), k.astype(xdt), kv[:, :, 1], a, pools["state"],
        pools["norm"], ctx.counts, ctx.starts)
    o = o.reshape(s, c, heads * d).astype(xdt)
    return (o @ lp["wo"]).astype(ctx.xdt), {"state": state, "norm": norm}


ATTENTIONS = {
    "mha": _attention_mha, "latent": _attention_latent,
    "gqa_gated": functools.partial(_attention_gqa, gated=True),
    "gqa": _attention_gqa,
    "gqa_window": functools.partial(_attention_gqa, windowed=True),
    "kda": _attention_kda, "power": _attention_power}


def _kv_pool(spec):
    return (spec.kv_heads, 2 * spec.head_dim)


def _latent_pool(spec):
    return (1, latent_row_width(spec.dims["kv_lora_rank"],
                                spec.dims["qk_rope"]))


def _kda_state(spec):
    dm = spec.dims
    heads, dk, dv = dm["kda_heads"], dm["kda_key_dim"], dm["kda_value_dim"]
    return {"state": ((heads, dv, dk), jnp.float32),
            "conv": ((dm["conv_taps"] - 1, heads * (2 * dk + dv)), None)}


def _power_state(spec):
    rows = state_rows(spec.head_dim)
    return {"state": ((spec.kv_heads, rows, spec.head_dim), jnp.float32),
            "norm": ((spec.kv_heads, rows), jnp.float32)}


# what a layer of each attention kind keeps in the cache, as
# `PagedKVCache(geometry=)` takes it: a paged block's (rows, width) —
# K beside V a KV head, or a latent layer's one row a token — or what a
# lane owns: a window (`{"window": W}`, which the engine sizes into a
# ring of blocks) or a state's arrays as (shape, dtype), None being the
# cache's dtype
CACHE_GEOMETRY = {
    "mha": _kv_pool, "gqa": _kv_pool, "gqa_gated": _kv_pool,
    "latent": _latent_pool,
    "gqa_window": lambda spec: {"window": spec.dims["window"]},
    "kda": _kda_state, "power": _power_state}


def cache_geometry(spec):
    """A model's cache, layer by layer (`CACHE_GEOMETRY`)."""
    return [CACHE_GEOMETRY[layer.attention](spec) for layer in spec.layers]


# the kinds whose cache is a state a lane, and what a step counts of
# them (`state_counts`); the prefix each kind gives the first two
# counts' names
STATE_ATTENTIONS = ("kda", "power")
STATE_STATS = ("lane_calls", "columns", "resets")
STATE_COUNTER_PREFIX = {"kda": "kda_", "power": "retention_"}
# the kinds whose cache is a ring a lane (`kv_cache.ring_table`), and
# what a step counts of them (`window_counts`)
RING_ATTENTIONS = ("gqa_window",)
WINDOW_STATS = ("lane_calls", "key_positions")


def state_counts(ctx, layers):
    """A step's counts over its `layers` state layers (`STATE_STATS`,
    int32): (lane, layer) pairs with a valid column, each of which
    reads and writes one state; valid columns x layers; and the lanes
    that began a request in this step."""
    return jnp.stack([
        layers * jnp.sum(ctx.counts > 0, dtype=jnp.int32),
        layers * jnp.sum(ctx.counts, dtype=jnp.int32),
        jnp.sum(ctx.starts, dtype=jnp.int32)])


def window_counts(ctx, layers):
    """A step's counts over its `layers` window layers (`WINDOW_STATS`,
    int32): (lane, layer) pairs with a valid column, each a walk of the
    lane's ring; and the key positions those walks needed, summed over
    the layers: from the lane's first query position less the window
    (never below 0) to its last."""
    window = ctx.spec.dims["window"]
    first = ctx.pos[:, 0]
    keys = jnp.where(
        ctx.counts > 0,
        first + ctx.counts - jnp.maximum(first - window + 1, 0), 0)
    return jnp.stack([
        layers * jnp.sum(ctx.counts > 0, dtype=jnp.int32),
        layers * jnp.sum(keys, dtype=jnp.int32)])


# -- MLPs ---------------------------------------------------------------

def _mlp_gelu(ctx, hn, lp, attn_in):
    w = ctx.w
    f = jax.nn.gelu(hn @ w(lp, "f0w") + lp["f0b"], approximate=False)
    return (ctx.reduce_fn(f @ w(lp, "f1w")) + lp["f1b"]).astype(
        ctx.xdt), None


def _mlp_gated(ctx, hn, lp, attn_in):
    f = jax.nn.silu(hn @ lp["w_gate"]) * (hn @ lp["w_up"])
    return (f @ lp["w_down"]).astype(ctx.xdt), None


def _mlp_experts(ctx, hn, lp, attn_in):
    """This chip's share of a routed expert layer (`moe.expert_share`):
    padded columns are routed nowhere. `dims` may say how the router
    scores (`scoring`, sigmoid by default), the experts' gate
    activation (`expert_act`, silu by default), and that the router
    reads the attention's input (`router_input`: "attention")."""
    dm = ctx.spec.dims
    router_h = None
    if dm.get("router_input") == "attention":
        router_h = attn_in.reshape(ctx.s * ctx.c, -1)
    out, stats = expert_share(
        hn.reshape(ctx.s * ctx.c, -1), lp, ctx.valid.reshape(-1),
        k=dm["experts_per_tok"], scaling=dm["routed_scaling"],
        normalize=dm["norm_topk_prob"], offset=dm["expert_offset"],
        scoring=dm.get("scoring", "sigmoid"),
        activation=dm.get("expert_act", "silu"), router_h=router_h)
    return out.reshape(hn.shape).astype(ctx.xdt), stats


MLPS = {"gelu": _mlp_gelu, "gated": _mlp_gated, "experts": _mlp_experts}

# a step's counts from those of its layers that count: one kind counts
# today, the expert layer its routing (`moe.MOE_STATS`)
COUNTING_MLPS = ("experts",)
fold_counts = step_stats
# the counts that also feed a registry counter of the same name
_MOE_FED = ("assignments", "assignments_held")


def step_counters(spec):
    """The names of the count vector a step of `spec` returns last, in
    the order `step._fused_step_body` appends them: for each count (its
    name in the iteration record, the registry counter it feeds or
    None). The expert layers' routing; the state layers' (`kda_` or
    `retention_`, as the kind says); the window layers'. Empty for a
    spec with no layer that counts."""
    attentions = [layer.attention for layer in spec.layers]
    names = ()
    if any(layer.mlp in COUNTING_MLPS for layer in spec.layers):
        names += tuple(
            ("moe_" + name,
             f"serving.moe.{name}" if name in _MOE_FED else None)
            for name in MOE_STATS)
    state = [kind for kind in attentions if kind in STATE_ATTENTIONS]
    if state:
        prefix = STATE_COUNTER_PREFIX[state[0]]
        names += ((prefix + "lane_calls", None),
                  (prefix + "columns", "serving.state.columns"),
                  ("state_resets", "serving.state.resets"))
    if any(kind in RING_ATTENTIONS for kind in attentions):
        names += tuple(("window_" + name, None) for name in WINDOW_STATS)
    return names
