"""`LinearMoEServingModel`: a decoder of gated grouped-query and
delta-rule (KDA) layers over mixtures of experts
(`models/linear_moe.py`) behind the engine's model interface, the same
one `GPTServingModel` and `LatentMoEServingModel` give: config facts,
`params`, `step_spec()` and `build_fused_step(block_size)`.

    cfg = LinearMoEConfig(n_routed_experts_held=20, ...)
    model = LinearMoEServingModel.from_seed(cfg, seed, jnp.bfloat16)
    srv = GenerationServer(model, num_slots=16, chunk=16,
                           block_size=16, max_context=8192)

The block is a spec (`blocks.StepSpec`) whose layers come from the
configuration's `gqa_layers`: RMS norm, no positions, `gqa_gated` over
a K-beside-V pool or `kda` over a state a lane, the expert layer's
share, untied head. The cache holds both kinds (`kv_geometry`): a
grouped-query layer's pool is (num_blocks, H_kv, block_size, 2 *
head_dim); a KDA layer's is `{"state": (lanes, H, dv, dk) float32,
"conv": (lanes, taps - 1, channels)}`, a lane's recurrent state and
the rows its short convolution carries, which the step zeroes itself
where a lane starts a request.

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, a mesh and
int8 pools are refused for this model by name (ROADMAP R5).
"""

import jax.numpy as jnp

from ..models.linear_moe import init_params
from .blocks import STATE_STATS, LayerSpec, StepSpec
from .engine import single_device_step
from .moe import MOE_STATS

__all__ = ["LinearMoEServingModel"]


class LinearMoEServingModel:
    # its fused step returns the routing counts (`moe.MOE_STATS`) and
    # the state layers' (`blocks.STATE_STATS`) as a last output: each
    # count's name in the iteration record, and the registry counter it
    # feeds where it has one
    step_counters = tuple(
        ("moe_" + name, f"serving.moe.{name}" if name in (
            "assignments", "assignments_held") else None)
        for name in MOE_STATS) + (
            ("kda_lane_calls", None),
            ("kda_columns", "serving.state.columns"),
            ("state_resets", "serving.state.resets"))
    assert len(step_counters) == len(MOE_STATS) + len(STATE_STATS)

    def __init__(self, params, cfg, dtype=None):
        self.params = params
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        # the grouped-query layers' geometry; a KDA layer's is in
        # `kv_geometry` and the spec's `dims`
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.max_position = cfg.max_position
        self.kv_dtype = dtype or params["word_emb"].dtype
        state = {"state": ((cfg.kda_heads, cfg.kda_head_dim,
                            cfg.kda_head_dim), jnp.float32),
                 "conv": ((cfg.short_conv_kernel_size - 1,
                           cfg.kda_channels), None)}
        self.kv_geometry = [
            (cfg.num_kv_heads, 2 * cfg.head_dim) if cfg.is_gqa_layer(i)
            else state for i in range(cfg.num_layers)]

    @classmethod
    def from_seed(cls, cfg, seed, dtype=jnp.float32):
        """Seeded parameters made on the device in `dtype`
        (`models/linear_moe.init_params`)."""
        return cls(init_params(cfg, seed, dtype), cfg, dtype=dtype)

    def step_spec(self):
        cfg = self.cfg
        return StepSpec(
            layers=tuple(
                LayerSpec("rms_norm", "gqa_gated" if cfg.is_gqa_layer(i)
                          else "kda", "experts")
                for i in range(cfg.num_layers)),
            positions="none", tied_head=False, heads=cfg.num_heads,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            norm_eps=cfg.rms_norm_eps,
            dims={"kda_heads": cfg.kda_heads,
                  "kda_key_dim": cfg.kda_head_dim,
                  "kda_value_dim": cfg.kda_head_dim,
                  "experts_per_tok": cfg.num_experts_per_tok,
                  "routed_scaling": cfg.routed_scaling_factor,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "expert_offset": cfg.expert_offset})

    def build_fused_step(self, block_size, per_column=False,
                         sampling=False):
        return single_device_step(self.params, self.step_spec(),
                                  block_size, per_column, sampling)
