"""`BrumbyServingModel`: a decoder of power-retention layers over a
dense gated MLP (`models/brumby.py`) behind the engine's model
interface, the same one the other families give: config facts,
`params`, `step_spec()` and `build_fused_step(block_size)`.

    cfg = BrumbyConfig(num_layers=8)
    model = BrumbyServingModel.from_seed(cfg, seed, jnp.bfloat16)
    srv = GenerationServer(model, num_slots=16, chunk=16,
                           block_size=16, max_context=32768)

The block is a spec (`blocks.StepSpec`): RMS norm, `power` over a
state a lane, the gated (SwiGLU) MLP, half-split rotary positions over
the whole head, untied head. No layer has a paged pool: every layer's
cache (`kv_geometry`) is `{"state": (H_kv, D, head_dim) float32,
"norm": (H_kv, D) float32}` a lane, D the rows of the degree-2
expansion (`ops/pallas/retention.state_rows`), which the step zeroes
itself where a lane starts a request.

One device, and nothing that copies cache by block: the prefix cache,
the host tier, speculative decoding, the chain handoff, fork groups, a
mesh and int8 pools are refused for this model by name (ROADMAP R5).
"""

import jax.numpy as jnp

from ..models.brumby import init_params
from ..ops.pallas.retention import state_rows
from .blocks import STATE_STATS, LayerSpec, StepSpec
from .engine import single_device_step

__all__ = ["BrumbyServingModel"]


class BrumbyServingModel:
    # its fused step returns the state layers' counts
    # (`blocks.STATE_STATS`) as a last output: each count's name in the
    # iteration record, and the registry counter it feeds where it has
    # one
    step_counters = (("retention_lane_calls", None),
                     ("retention_columns", "serving.state.columns"),
                     ("state_resets", "serving.state.resets"))
    assert len(step_counters) == len(STATE_STATS)

    def __init__(self, params, cfg, dtype=None):
        self.params = params
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.max_position = cfg.max_position
        self.kv_dtype = dtype or params["word_emb"].dtype
        rows = state_rows(cfg.head_dim)
        self.kv_geometry = [
            {"state": ((cfg.num_kv_heads, rows, cfg.head_dim), jnp.float32),
             "norm": ((cfg.num_kv_heads, rows), jnp.float32)}
            for _ in range(cfg.num_layers)]

    @classmethod
    def from_seed(cls, cfg, seed, dtype=jnp.float32):
        """Seeded parameters made on the device in `dtype`
        (`models/brumby.init_params`)."""
        return cls(init_params(cfg, seed, dtype), cfg, dtype=dtype)

    def step_spec(self):
        cfg = self.cfg
        return StepSpec(
            layers=tuple(LayerSpec("rms_norm", "power", "gated")
                         for _ in range(cfg.num_layers)),
            positions="rotary", tied_head=False, heads=cfg.num_heads,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            norm_eps=cfg.rms_norm_eps,
            dims={"qk_rope": cfg.head_dim, "rope_theta": cfg.rope_theta})

    def build_fused_step(self, block_size, per_column=False,
                         sampling=False):
        return single_device_step(self.params, self.step_spec(),
                                  block_size, per_column, sampling)
