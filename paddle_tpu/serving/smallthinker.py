"""`SmallThinkerServingModel`: a decoder of sliding-window and full
grouped-query layers over mixtures of experts
(`models/smallthinker.py`) behind the engine's model interface, the
same one the other families give: config facts, `params`,
`step_spec()` and `build_fused_step(block_size)`.

    cfg = SmallThinkerConfig(num_layers=12)
    model = SmallThinkerServingModel.from_seed(cfg, seed, jnp.bfloat16)
    srv = GenerationServer(model, num_slots=16, chunk=16,
                           block_size=16, max_context=16384)

The block is a spec (`blocks.StepSpec`): RMS norm, `gqa_window`
(half-split rotary, a window, over a ring a lane) or `gqa` (no
positions, full, over the allocator's blocks) as the configuration's
`window_layers` say, the expert layer with softmax scoring, ReLU-gated
experts and its router on the attention's input, untied head. The
cache holds both kinds (`kv_geometry`): a full layer's pool is
(num_blocks, H_kv, block_size, 2 * head_dim); a window layer's is
`{"window": W}`, which the engine sizes into a ring of blocks a lane
(`kv_cache.ring_blocks_for`).

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, fork groups, a
mesh and int8 pools are refused for this model by name (ROADMAP R3).
"""

import jax.numpy as jnp

from ..models.smallthinker import init_params
from .blocks import WINDOW_STATS, LayerSpec, StepSpec
from .engine import single_device_step
from .moe import MOE_STATS

__all__ = ["SmallThinkerServingModel"]


class SmallThinkerServingModel:
    # its fused step returns the routing counts (`moe.MOE_STATS`) and
    # the window layers' (`blocks.WINDOW_STATS`) as a last output: each
    # count's name in the iteration record, and the registry counter it
    # feeds where it has one
    step_counters = tuple(
        ("moe_" + name, f"serving.moe.{name}" if name in (
            "assignments", "assignments_held") else None)
        for name in MOE_STATS) + tuple(
            ("window_" + name, None) for name in WINDOW_STATS)

    def __init__(self, params, cfg, dtype=None):
        self.params = params
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.max_position = cfg.max_position
        self.kv_dtype = dtype or params["word_emb"].dtype
        self.kv_geometry = [
            {"window": cfg.sliding_window} if cfg.is_window_layer(i)
            else (cfg.num_kv_heads, 2 * cfg.head_dim)
            for i in range(cfg.num_layers)]

    @classmethod
    def from_seed(cls, cfg, seed, dtype=jnp.float32):
        """Seeded parameters made on the device in `dtype`
        (`models/smallthinker.init_params`)."""
        return cls(init_params(cfg, seed, dtype), cfg, dtype=dtype)

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

    def build_fused_step(self, block_size, per_column=False,
                         sampling=False):
        return single_device_step(self.params, self.step_spec(),
                                  block_size, per_column, sampling)
