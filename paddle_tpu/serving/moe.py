"""The expert layer of a served mixture-of-experts decoder: a router
over ALL of a layer's experts, and this chip's share of their work.

A chip of an expert-parallel deployment holds experts
`[offset, offset + held)` of each layer. `expert_share` routes every
token over all the layer's experts (`route`), computes the terms of

    y = sum_k w_k E_k(h) + E_shared(h)

whose expert it holds (`ops/pallas/moe.moe_experts`), adds the shared
experts, which every chip computes alike, and LEAVES OUT the terms of
experts it does not hold: on a deployment the other chips add theirs
in the exchange, which this layer does not run and nothing stands in
for. There is no capacity and no dropped token: an expert takes as
many tiles as its tokens need. (`parallel/moe.py` is the training-side
sketch: top-1 gating with a capacity drop and an all_to_all.)
"""

import jax
import jax.numpy as jnp

from ..ops.pallas.moe import moe_experts

__all__ = ["route", "expert_share", "held_selection", "MOE_STATS",
           "step_stats"]

# what `expert_share` counts, in the order of its stats vector
MOE_STATS = ("assignments", "assignments_held", "expert_tokens_max",
             "experts_touched")


def step_stats(layers):
    """A step's counts from its expert layers' (`MOE_STATS` each):
    assignments and touched experts summed over the layers, the
    fullest expert's tokens their maximum."""
    per = jnp.stack(layers)                             # (layers, 4)
    return jnp.stack([per[:, 0].sum(), per[:, 1].sum(), per[:, 2].max(),
                      per[:, 3].sum()])


def route(h, router_w, router_b, k, scaling, normalize=True):
    """Sigmoid routing with a selection-only correction bias (the
    `noaux_tc` method with one group): scores `s = sigmoid(h W_g)` in
    float32 at the highest matmul precision; the `k` experts with the
    largest `s + b` are chosen (ties go to the lower index, as
    `jax.lax.top_k` breaks them); their weights are `s` of the chosen,
    WITHOUT `b`, over their sum (+1e-20) where `normalize`, times
    `scaling`. h (T, H) -> (ids (T, k) int32, weights (T, k) f32)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + router_b.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scaling


def held_selection(ids, weights, live, offset, held):
    """(sel (T, held) bool, comb (T, held) f32): which held experts
    each LIVE token chose, and with what weight. A token chooses an
    expert at most once, so the sum over k places one weight."""
    local = ids - offset                                    # (T, k)
    hit = (local[..., None] == jnp.arange(held)) & live[:, None, None]
    return hit.any(axis=1), jnp.sum(
        jnp.where(hit, weights[..., None], 0.0), axis=1)


def _gated_mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def expert_share(h, lp, live, *, k, scaling, normalize, offset):
    """This chip's part of an expert layer's output for h (T, H), in
    h's type, and the layer's counts (`MOE_STATS`, int32): the routed
    terms of the held experts `lp["exp_gu"]` / `lp["exp_down"]`
    (experts `[offset, offset + held)` of the layer), plus the shared
    expert. `live` (T,) marks the columns that carry a token: a padded
    column is routed nowhere and counts nowhere."""
    held = lp["exp_gu"].shape[0]
    ids, weights = route(h, lp["router_w"], lp["router_b"], k, scaling,
                         normalize)
    sel, comb = held_selection(ids, weights, live, offset, held)
    routed = moe_experts(h, sel, comb, lp["exp_gu"], lp["exp_down"])
    shared = _gated_mlp(h, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    per_expert = jnp.sum(sel, axis=0, dtype=jnp.int32)
    stats = jnp.stack([
        jnp.sum(live, dtype=jnp.int32) * k, jnp.sum(per_expert),
        jnp.max(per_expert), jnp.sum(per_expert > 0, dtype=jnp.int32)])
    return (routed + shared.astype(jnp.float32)).astype(h.dtype), stats
