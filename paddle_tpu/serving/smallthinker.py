"""`SmallThinkerServingModel`: a decoder of sliding-window and full
grouped-query layers over mixtures of experts
(`models/smallthinker.py`) as a `ServingModel`.

The block is a spec (`blocks.StepSpec`): RMS norm, `gqa_window`
(half-split rotary, a window, over a ring a lane) or `gqa` (no
positions, full, over the allocator's blocks) as the configuration's
`window_layers` say, the expert layer with softmax scoring, ReLU-gated
experts and its router on the attention's input, untied head. A window
layer's cache (`blocks.cache_geometry`) is `{"window": W}`, which the
engine sizes into a ring of blocks a lane (`kv_cache.ring_blocks_for`).

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, fork groups, a
mesh and int8 pools are refused for this model by name (ROADMAP R3).
"""

from ..models.smallthinker import init_params
from .blocks import LayerSpec, StepSpec
from .model import ServingModel

__all__ = ["SmallThinkerServingModel"]


class SmallThinkerServingModel(ServingModel):
    init_params = staticmethod(init_params)

    def step_spec(self):
        cfg = self.cfg
        return StepSpec(
            layers=tuple(
                LayerSpec("rms_norm", "gqa_window" if cfg.is_window_layer(i)
                          else "gqa", "experts")
                for i in range(cfg.num_layers)),
            positions="rotary", tied_head=False, heads=cfg.num_heads,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            norm_eps=cfg.rms_norm_eps,
            dims={"qk_rope": cfg.head_dim, "rope_theta": cfg.rope_theta,
                  "window": cfg.sliding_window,
                  "experts_per_tok": cfg.num_experts_per_tok,
                  "routed_scaling": 1.0,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "expert_offset": cfg.expert_offset,
                  "scoring": "softmax", "expert_act": "relu",
                  "router_input": "attention"})
