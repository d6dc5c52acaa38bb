"""`LinearMoEServingModel`: a decoder of gated grouped-query and
delta-rule (KDA) layers over mixtures of experts
(`models/linear_moe.py`) as a `ServingModel`.

The block is a spec (`blocks.StepSpec`) whose layers come from the
configuration's `gqa_layers`: RMS norm, no positions, `gqa_gated` over
a K-beside-V pool or `kda` over a state a lane, the expert layer's
share, untied head. A KDA layer's cache (`blocks.cache_geometry`) is
`{"state": (lanes, H, dv, dk) float32, "conv": (lanes, taps - 1,
channels)}`, a lane's recurrent state and the rows its short
convolution carries, which the step zeroes itself where a lane starts
a request.

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, a mesh and
int8 pools are refused for this model by name (ROADMAP R5).
"""

from ..models.linear_moe import init_params
from .blocks import LayerSpec, StepSpec
from .model import ServingModel

__all__ = ["LinearMoEServingModel"]


class LinearMoEServingModel(ServingModel):
    init_params = staticmethod(init_params)

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
                  "conv_taps": cfg.short_conv_kernel_size,
                  "experts_per_tok": cfg.num_experts_per_tok,
                  "routed_scaling": cfg.routed_scaling_factor,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "expert_offset": cfg.expert_offset})
