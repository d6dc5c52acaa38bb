"""`LatentMoEServingModel`: a latent-attention / mixture-of-experts
decoder (`models/latent_moe.py`) as a `ServingModel`.

The block is a spec (`blocks.StepSpec`): RMS norm, rotary positions on
the rope part of each head, latent attention over a one-row-a-token
pool, a gated MLP in the leading dense layers and the expert layer's
share after them, untied head. A layer's pool is (num_blocks, 1,
block_size, W), `W = latent_row_width(kv_lora_rank,
qk_rope_head_dim)` (`blocks.cache_geometry`).

One device: the deployment's expert axis is across chips, each a
replica of everything but its experts, and the exchange between them
is not run (ROADMAP Reach, R1).
"""

from ..models.latent_moe import init_params
from .blocks import LayerSpec, StepSpec
from .model import ServingModel

__all__ = ["LatentMoEServingModel"]


class LatentMoEServingModel(ServingModel):
    init_params = staticmethod(init_params)

    def step_spec(self):
        cfg = self.cfg
        attn = ("rms_norm", "latent")
        # kv_heads: the published key / value heads, one a query head,
        # each expanded from the latent row the pool keeps
        return StepSpec(
            layers=tuple(
                LayerSpec(*attn, "experts" if cfg.is_expert_layer(i)
                          else "gated") for i in range(cfg.num_layers)),
            positions="rotary", tied_head=False, heads=cfg.num_heads,
            kv_heads=cfg.num_heads, head_dim=cfg.qk_head_dim,
            norm_eps=cfg.rms_norm_eps,
            dims={"kv_lora_rank": cfg.kv_lora_rank,
                  "qk_nope": cfg.qk_nope_head_dim,
                  "qk_rope": cfg.qk_rope_head_dim,
                  "v_head_dim": cfg.v_head_dim,
                  "rope_theta": cfg.rope_theta,
                  "experts_per_tok": cfg.num_experts_per_tok,
                  "routed_scaling": cfg.routed_scaling_factor,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "expert_offset": cfg.expert_offset})
