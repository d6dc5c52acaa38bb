"""`BrumbyServingModel`: a decoder of power-retention layers over a
dense gated MLP (`models/brumby.py`) as a `ServingModel`.

The block is a spec (`blocks.StepSpec`): RMS norm, `power` over a
state a lane, the gated (SwiGLU) MLP, half-split rotary positions over
the whole head, untied head. No layer has a paged pool: every layer's
cache (`blocks.cache_geometry`) is `{"state": (H_kv, D, head_dim)
float32, "norm": (H_kv, D) float32}` a lane, D the rows of the
degree-2 expansion (`ops/pallas/retention.state_rows`), which the step
zeroes itself where a lane starts a request.

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, fork groups, a
mesh and int8 pools are refused for this model by name (ROADMAP R5).
"""

from ..models.brumby import init_params
from .blocks import LayerSpec, StepSpec
from .model import ServingModel

__all__ = ["BrumbyServingModel"]


class BrumbyServingModel(ServingModel):
    init_params = staticmethod(init_params)

    def step_spec(self):
        cfg = self.cfg
        return StepSpec(
            layers=tuple(LayerSpec("rms_norm", "power", "gated")
                         for _ in range(cfg.num_layers)),
            positions="rotary", tied_head=False, heads=cfg.num_heads,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            norm_eps=cfg.rms_norm_eps,
            dims={"qk_rope": cfg.head_dim, "rope_theta": cfg.rope_theta})
