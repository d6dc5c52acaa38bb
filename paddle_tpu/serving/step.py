"""The ONE fused prefill/decode step every serving model runs, over
(S, C) ragged lanes with paged KV: `_fused_step_body` walks a
`blocks.StepSpec` layer by layer through the kinds `blocks.py` holds,
`_step_tail` turns the final residual into ids and log-probs (greedy,
and sampled where compiled in), and `single_device_step` is the step of
one device as `GenerationServer` jits it. `model.ServingModel` builds
it; the speculative draft step (`spec_decode.py`) runs the same body.
"""

import jax
import jax.numpy as jnp

from . import kv_cache as _kvc
from .blocks import (ATTENTIONS, MLPS, NORMS, RING_ATTENTIONS,
                     STATE_ATTENTIONS, StepContext, fold_counts,
                     rotary_angles, state_counts, window_counts)
from .decode_strategies import gumbel_noise
from .kv_cache import NEG_INF, NULL_BLOCK

__all__ = ["single_device_step"]


def _sample_rows(base, rng, temperature, do_top_k, top_p):
    """Stochastic token choice over (S, V) log-prob rows INSIDE the one
    fused step: temperature scale, top-k / nucleus filtering
    (inference.decoding._filter_logits semantics), Gumbel-argmax draw
    from per-lane counter keys. Every control is DATA — (S,) arrays, 0
    meaning top-k off and 2.0 meaning top-p off — so sampled, greedy,
    and mixed batches all share one jit signature. Returns
    (sampled ids (S,), their logp under the filtered distribution)."""
    s, v = base.shape
    scaled = base / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    k_eff = jnp.clip(jnp.where(do_top_k > 0, do_top_k, v), 1, v)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], -1)
    filt = jnp.where(scaled < kth, jnp.float32(NEG_INF), scaled)
    # nucleus over the top-k survivors (softmax subtracts the row max,
    # so the NEG_INF entries contribute exp(-huge) = 0, never NaN)
    sd = -jnp.sort(-filt, axis=-1)
    cum = jnp.cumsum(jax.nn.softmax(sd, axis=-1), axis=-1)
    keep = jnp.concatenate([jnp.ones((s, 1), bool),
                            cum[:, :-1] < top_p[:, None]], axis=-1)
    thresh = jnp.min(jnp.where(keep, sd, jnp.inf), axis=-1,
                     keepdims=True)
    filt = jnp.where(filt < thresh, jnp.float32(NEG_INF), filt)
    samp = jnp.argmax(filt + gumbel_noise(rng, v, xp=jnp), axis=-1)
    samp_lp = jnp.take_along_axis(
        jax.nn.log_softmax(filt, axis=-1), samp[:, None], -1)[:, 0]
    return samp.astype(jnp.int32), samp_lp


def _sampled_where_asked(logp, nxt, chosen, rng, temperature, do_sample,
                         top_k, top_p):
    """The step's (ids, their logp) from the greedy `(nxt, chosen)`: a
    lane whose `do_sample` is set takes `_sample_rows`' draw over its
    `logp` row instead. The draw (two sorts of the whole vocabulary, a
    nucleus sum, the noise) runs under ONE device-side branch on
    `do_sample.any()`, so a step whose lanes are all greedy pays for
    none of it; the predicate is data the step already takes, so there
    is still one executable. Over per-column `(S, C, ...)` inputs the
    draw is column 0's. Everything a branch reads is an operand."""
    per_column = logp.ndim == 3

    def sampled(logp, nxt, chosen, rng, temperature, do_sample, top_k,
                top_p):
        samp, samp_lp = _sample_rows(logp[:, 0] if per_column else logp,
                                     rng, temperature, top_k, top_p)
        if per_column:
            nxt = nxt.at[:, 0].set(jnp.where(do_sample, samp, nxt[:, 0]))
            chosen = chosen.at[:, 0].set(
                jnp.where(do_sample, samp_lp, chosen[:, 0]))
        else:
            nxt = jnp.where(do_sample, samp, nxt)
            chosen = jnp.where(do_sample, samp_lp, chosen)
        return nxt, chosen

    def greedy(logp, nxt, chosen, *controls):
        return nxt, chosen

    return jax.lax.cond(jnp.any(do_sample), sampled, greedy, logp,
                        nxt.astype(jnp.int32), chosen, rng, temperature,
                        do_sample, top_k, top_p)


def _fused_step_body(params, spec, block_size, reduce_fn, pools, tokens,
                     positions, valid, tables, per_column=False,
                     sampling=False, mask=None, rng=None,
                     temperature=None, do_sample=None, top_k=None,
                     top_p=None, in_shard_map=False):
    """The ONE fused prefill/decode step body, over (S, C) ragged lanes
    with paged KV, for every model family: `spec` (`blocks.StepSpec`)
    says, layer by layer, which norm, which attention over which cache
    geometry and which MLP a block is made of, how positions enter and
    whether the head is tied; `blocks.py` holds each kind's
    arithmetic. The skeleton around the layers is the same for all:
    embedding, write targets (masked lanes to the NULL block), the
    layer loop with its two residual adds, the final norm, the
    last-column gather, log-softmax in float32, and the greedy and
    sampled tails.

    It is shared by the single-device and tensor-parallel fused steps
    exactly like gpt._prefill_forward: `spec.heads` is the QUERY head
    count THIS caller sees (H, or H/tp inside shard_map over
    head-sharded params and pools), `spec.kv_heads` the KV head count
    (equal for MHA; H_kv or H_kv/tp for grouped-query attention, where
    wk/wv project to kv_heads * head_dim columns and the
    paged_attention dispatcher groups the query heads onto the shared
    KV heads), and `reduce_fn`
    finishes the row-parallel o-proj / ffn-down contractions (identity
    single-device; one psum per sub-block under tp — the partial sums
    those matmuls leave are the ONLY cross-shard state the step has);
    `in_shard_map` is that same fact handed on to the paged_attention
    dispatcher, which cannot see it from inside the trace.

    `per_column=False` (plain serving): each lane's LAST valid column
    is gathered before the lm-head projection — one (S, H) @ (H, V)
    gemm, returns (pools, next_ids (S,), next_logps (S,)).
    `per_column=True` (speculative verify): every column is projected —
    (S*C, H) @ (H, V) — and a third `fed_logps` output carries the
    target logp of each NEXT fed column's token (the draft under
    verification; rejection-mode acceptance needs p_target(draft)).
    Rows of the wide gemm are independent dot products, so a column's
    outputs are bitwise the last-column gather's (the spec parity tests
    pin this); plain servers keep the narrow gemm — C x fewer lm-head
    FLOPs on the decode hot path.

    A spec with layers that count (an MLP kind that returns counts
    beside its addend: the expert layer its routing) returns ONE more
    output, last: the step's counts, an int32 vector folded over those
    layers (`blocks.fold_counts`), named from the spec by
    `blocks.step_counters`. A spec with state layers (`STATE_ATTENTIONS`)
    appends theirs (`blocks.state_counts`) to that vector, and one with
    window layers (`RING_ATTENTIONS`) theirs (`blocks.window_counts`).

    A window layer's pool is the lanes' rings: its table comes from
    lane and position (`kv_cache.ring_table`, R read off the first such
    layer's pool), computed once for all such layers. Each MLP kind is
    handed what its layer's attention read, for a router placed before
    the attention.

    Quantized serving rides the same body: a layer dict
    carrying "k_scale"/"v_scale" pools takes the quantize-at-write path
    and hands the scales to the paged_attention dispatcher (which fuses
    the dequant into the Pallas kernel's gather); a layer dict carrying
    "<w>@q8"/"<w>@scale" weight entries (GPTServingModel.quantize_int8)
    gets its matmul weight dequantized INLINE — int8 codes times the
    per-output-channel f32 scale, cast to the activation dtype — so the
    step reads half the weight bytes from HBM and the jit signature
    budget is untouched (the dequant is part of the one compiled
    step, not a second executable)."""
    s, c = tokens.shape
    wdt = params["word_emb"].dtype     # activation/compute dtype

    def w(container, name):
        # int8 weight entry -> inline dequant; plain entry -> as-is
        q8 = container.get(name + "@q8")
        if q8 is None:
            return container[name]
        return (q8.astype(jnp.float32)
                * container[name + "@scale"]).astype(wdt)

    pos = jnp.where(valid, positions, 0)
    x = params["word_emb"][tokens]
    angles = None
    if spec.positions == "learned":
        x = x + params["pos_emb"][pos]
    elif spec.positions == "rotary":
        angles = rotary_angles(pos, spec.dims["qk_rope"],
                               spec.dims["rope_theta"])
    # write targets: masked lanes route to the NULL block
    bidx = jnp.take_along_axis(tables, pos // block_size, axis=1)
    bidx = jnp.where(valid, bidx, NULL_BLOCK)
    off = jnp.where(valid, pos % block_size, 0)
    # what a state layer reads: a lane's valid columns are a prefix, and
    # a lane whose first one holds position 0 starts a request
    lane_cols = jnp.sum(valid, axis=1, dtype=jnp.int32)
    starts = valid[:, 0] & (positions[:, 0] == 0)
    ring = ring_bidx = None
    ring_layers = [i for i, layer in enumerate(spec.layers)
                   if layer.attention in RING_ATTENTIONS]
    if ring_layers:
        ring = _kvc.ring_table(
            tables, (pools[ring_layers[0]]["kv"].shape[0] - 1) // s)
        ring_bidx = jnp.where(
            valid, jnp.take_along_axis(ring, pos // block_size, axis=1),
            NULL_BLOCK)
    ctx = StepContext(spec, s, c, x.dtype, pos, bidx, off, valid, tables,
                      reduce_fn, in_shard_map, w, angles, lane_cols,
                      starts, ring, ring_bidx)
    new_pools, counts = [], []
    for i, layer in enumerate(spec.layers):
        lp = params[f"l{i}"]
        norm = NORMS[layer.norm]
        hn = norm(spec, x, lp, "ln1")
        a, layer_pools = ATTENTIONS[layer.attention](ctx, hn, lp,
                                                     pools[i])
        x = x + a
        f, stats = MLPS[layer.mlp](ctx, norm(spec, x, lp, "ln2"), lp, hn)
        x = x + f
        new_pools.append(layer_pools)
        if stats is not None:
            counts.append(stats)
    x = NORMS[spec.layers[-1].norm](spec, x, params, "lnf")
    head = params["word_emb"].T if spec.tied_head else params["head"]
    out = _step_tail(x, head, tokens, valid, new_pools, per_column,
                     sampling, mask, rng, temperature, do_sample, top_k,
                     top_p)
    state_layers = sum(layer.attention in STATE_ATTENTIONS
                       for layer in spec.layers)
    vectors = ([fold_counts(counts)] if counts else []) + (
        [state_counts(ctx, state_layers)] if state_layers else []) + (
        [window_counts(ctx, len(ring_layers))] if ring_layers else [])
    if vectors:
        out += (jnp.concatenate(vectors),)
    return out


def _step_tail(x, head, tokens, valid, new_pools, per_column, sampling,
               mask, rng, temperature, do_sample, top_k, top_p):
    """From the final-normed residual to the step's outputs: the head
    over each lane's last valid column (or every column), log-softmax
    in float32, the greedy choice and, where compiled in, the sampled
    one."""
    s, c = tokens.shape
    if not per_column:
        # next token comes from each lane's LAST valid column only
        last = jnp.clip(valid.sum(1) - 1, 0, c - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = xl @ head
        logitsf = logits.astype(jnp.float32)
        if sampling:
            # guided-decoding constraint mask (S, V): additive 0 /
            # NEG_INF rows, all-zero for unconstrained lanes — data,
            # never shape, so the one-signature invariant holds
            logitsf = logitsf + mask
        logp = jax.nn.log_softmax(logitsf)
        nxt = jnp.argmax(logp, axis=-1)
        chosen = jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]
        if not sampling:
            return new_pools, nxt.astype(jnp.int32), chosen
        nxt, chosen = _sampled_where_asked(
            logp, nxt, chosen, rng, temperature, do_sample, top_k, top_p)
        # 4th output: the full logp rows — fork-time host sampling and
        # beam re-ranking read these (the host transfer is paid only
        # when the plan says a group needs them)
        return new_pools, nxt, chosen, logp
    logits = (x.reshape(s * c, -1) @ head).reshape(
        s, c, head.shape[1])
    logitsf = logits.astype(jnp.float32)
    if sampling:
        logitsf = logitsf + mask        # (S, C, V) per-column masks
    logp = jax.nn.log_softmax(logitsf)
    nxt = jnp.argmax(logp, axis=-1)                         # (S, C)
    chosen = jnp.take_along_axis(logp, nxt[..., None], -1)[..., 0]
    # target logp of the NEXT FED column's token — the draft under
    # verification at this column; rejection-sampled acceptance needs
    # p_target(draft). The last column's value wraps and is meaningless.
    nt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    fed = jnp.take_along_axis(logp, nt[..., None], -1)[..., 0]
    if not sampling:
        return new_pools, nxt.astype(jnp.int32), chosen, fed
    # sampled lanes run 1-column (the scheduler plans no drafts for
    # them), so the stochastic draw applies to column 0 only
    nxt, chosen = _sampled_where_asked(
        logp, nxt, chosen, rng, temperature, do_sample, top_k, top_p)
    return new_pools, nxt, chosen, fed, logp


def single_device_step(params, spec, block_size, per_column=False,
                       sampling=False):
    """The fused step of one device over `params` and `spec`, as
    `GenerationServer` jits it: (pools, tokens, positions, valid,
    tables[, mask, rng, temperature, do_sample, top_k, top_p]) ->
    `_fused_step_body`'s outputs. What every family's
    `build_fused_step` returns without a mesh."""
    controls = ("mask", "rng", "temperature", "do_sample", "top_k",
                "top_p") if sampling else ()

    def fused(pools, tokens, positions, valid, tables, *ctl):
        return _fused_step_body(
            params, spec, block_size, lambda z: z, pools, tokens,
            positions, valid, tables, per_column=per_column,
            sampling=sampling, **dict(zip(controls, ctl, strict=True)))

    return fused
