"""Speculative decoding: draft proposals verified by the fused step.

A small draft model proposes k tokens per iteration; the target
verifies all of them in ONE chunked, prefill-shaped fused-step call —
the exact machinery chunked prefill already exercises, so speculation
adds NO new target-side compute shape. Per accepted token the target
pays 1/q-th of a fused step instead of a whole one; on TPU, where
decode is bandwidth-bound and the chunk columns are nearly free, that
is a direct inter-token-latency win.

The verify call feeds q = min(k+1, chunk) columns per decode lane:
``[committed_token, d_1, ..., d_{q-1}]`` at positions
``pos .. pos+q-1``. Column i's per-column output is the target's own
next-token choice after fed column i, so greedy acceptance is a pure
host-side comparison: accept the longest prefix with ``d_i ==
target_choice_i``, then commit the target's next token after it —
every committed id IS the target's greedy choice under the same
context, which makes the stream BITWISE identical to plain greedy
decode (tests pin this, mid-stream cancel included). KV hygiene falls
out of the layout: rejected-draft writes land at positions past the
committed horizon and are overwritten by the next iteration's feed
before anything can attend to them (causal masking covers the same
step).

The draft step is ONE jitted function for the server lifetime (the
second and last entry in the compiled-signature budget —
``get_stats()["compiled_step_signatures"] <= 2``):

    draft(pools, tokens (S, C), positions (S, C), valid (S, C),
          tables (S, M), spec_go (S,), limits (S,))
        -> (pools, proposals (S, k), proposal_logps (S, k))

It first mirrors the scheduler's plan feed (prefill chunks, and each
decode lane's committed token) against the DRAFT pools — the draft's
KV must track the target's context, including prompt prefill — then
rolls out k-1 more single-token micro-steps per decode lane
(`spec_go`). Rollout writes are masked past each lane's reserved
horizon (`limits`): positions beyond prompt+max_new_tokens route to
the NULL block instead of clamping into a neighbour's last real block.

The draft pools live in a sibling PagedKVCache sharing the target
pool's block ids (one host allocation drives both; copy-on-write
copies both), so shared-prefix blocks carry the draft's KV for those
tokens too — prefix caching and speculation compose.

``mode="rejection"`` (experimental, flagged): accept draft i with
probability min(1, p_target(d_i)/p_draft(d_i)) using the fused step's
fed-token logps and the draft's proposal logps; on the first rejection
the target's argmax is committed as the correction token. That greedy
correction stands in for the rejection-sampling paper's residual
resampling (which needs the full target distribution on the host) —
a documented deviation, see docs/serving.md. Greedy mode is exact.
"""

import jax.numpy as jnp

from .step import _fused_step_body

__all__ = ["SpecDecodeConfig", "build_draft_step"]


class SpecDecodeConfig:
    """Engine-facing spec-decode settings: the draft model (a
    `ServingModel` whose cache is paged by block), k proposals per
    iteration, the acceptance mode, and the rejection-mode RNG seed."""

    def __init__(self, draft_model, k=3, mode="greedy", seed=0):
        if k < 1:
            raise ValueError(f"spec k must be >= 1, got {k}")
        if mode not in ("greedy", "rejection"):
            raise ValueError(
                f"spec mode {mode!r}: expected 'greedy' or 'rejection'")
        self.draft_model = draft_model
        self.k = int(k)
        self.mode = mode
        self.seed = int(seed)


def build_draft_step(model, block_size, k):
    """One compiled draft step (see module docstring): sync pass over
    the plan feed + k-1 rollout micro-steps, all inside one jit so the
    server lifetime holds exactly one draft signature."""
    params, spec = model.params, model.step_spec()

    def _ident(z):
        return z

    def draft_step(pools, tokens, positions, valid, tables, spec_go,
                   limits):
        # sync pass: prefill chunks and committed decode tokens write
        # their DRAFT KV; the last-column output (all the draft ever
        # needs — no per-column projection here) is each decode lane's
        # first proposal d_1
        pools, cur, cur_lp = _fused_step_body(
            params, spec, block_size, _ident,
            pools, tokens, positions, valid, tables)[:3]
        s, c = tokens.shape
        last = jnp.clip(valid.sum(1) - 1, 0, c - 1)
        base = jnp.take_along_axis(positions, last[:, None], 1)[:, 0] + 1
        props, plps = [cur], [cur_lp]
        for i in range(1, k):
            # feed proposal d_i at its position; the write is masked
            # for non-speculating lanes and past each lane's reserved
            # horizon (NULL block, never a clamped real block)
            pos_i = base + i - 1
            v_i = (spec_go & (pos_i < limits))[:, None]
            pools, cur, cur_lp = _fused_step_body(
                params, spec, block_size, _ident,
                pools, cur[:, None], pos_i[:, None].astype(jnp.int32),
                v_i, tables)[:3]
            props.append(cur)
            plps.append(cur_lp)
        return pools, jnp.stack(props, 1), jnp.stack(plps, 1)

    return draft_step
