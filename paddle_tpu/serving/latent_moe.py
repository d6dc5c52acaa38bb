"""`LatentMoEServingModel`: a latent-attention / mixture-of-experts
decoder (`models/latent_moe.py`) behind the engine's model interface,
the same one `GPTServingModel` gives: config facts, `params`,
`step_spec()` and `build_fused_step(block_size)`.

    cfg = LatentMoEConfig(n_routed_experts_held=16, ...)
    model = LatentMoEServingModel.from_seed(cfg, seed, jnp.bfloat16)
    srv = GenerationServer(model, num_slots=16, chunk=16,
                           block_size=16, max_context=4096)

The block is a spec (`blocks.StepSpec`): RMS norm, rotary positions on
the rope part of each head, latent attention over a one-row-a-token
pool, a gated MLP in the leading dense layers and the expert layer's
share after them, untied head. The cache is told the latent geometry
(`kv_geometry`): a layer's pool is (num_blocks, 1, block_size, W),
`W = latent_row_width(kv_lora_rank, qk_rope_head_dim)`.

One device: the deployment's expert axis is across chips, each a
replica of everything but its experts, and the exchange between them
is not run (ROADMAP Reach, R1).
"""

import jax.numpy as jnp

from ..models.latent_moe import init_params
from ..ops.pallas.paged import latent_row_width
from .blocks import LayerSpec, StepSpec
from .engine import single_device_step
from .moe import MOE_STATS

__all__ = ["LatentMoEServingModel"]


class LatentMoEServingModel:
    # its fused step returns the routing counts (`moe.MOE_STATS`) as a
    # last output: each count's name in the iteration record, and the
    # registry counter it feeds where it has one
    step_counters = tuple(
        ("moe_" + name, f"serving.moe.{name}" if name in (
            "assignments", "assignments_held") else None)
        for name in MOE_STATS)

    def __init__(self, params, cfg, dtype=None):
        self.params = params
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = self.num_kv_heads = cfg.num_heads
        # a query head's width; the cache rows are `kv_geometry`'s
        self.head_dim = cfg.qk_head_dim
        self.max_position = cfg.max_position
        self.kv_dtype = dtype or params["word_emb"].dtype
        self.kv_geometry = [
            (1, latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim))
        ] * cfg.num_layers

    @classmethod
    def from_seed(cls, cfg, seed, dtype=jnp.float32):
        """Seeded parameters made on the device in `dtype`
        (`models/latent_moe.init_params`)."""
        return cls(init_params(cfg, seed, dtype), cfg, dtype=dtype)

    def step_spec(self):
        cfg = self.cfg
        attn = ("rms_norm", "latent")
        return StepSpec(
            layers=tuple(
                LayerSpec(*attn, "experts" if cfg.is_expert_layer(i)
                          else "gated") for i in range(cfg.num_layers)),
            positions="rotary", tied_head=False, heads=cfg.num_heads,
            kv_heads=1, head_dim=cfg.qk_head_dim,
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

    def build_fused_step(self, block_size, per_column=False,
                         sampling=False):
        return single_device_step(self.params, self.step_spec(),
                                  block_size, per_column, sampling)
