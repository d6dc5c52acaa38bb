"""Decoder whose layers mix sliding-window and full grouped-query
attention, every one followed by a softmax-routed mixture of ReLU-gated
experts whose router reads the attention's input (the SmallThinker
family's block, whose keys `SmallThinkerConfig` follows):
configuration, parameter names and seeded initialisation, for serving
(`serving/smallthinker.py`).

One layer, with `N` an RMS norm (learned scale, computed in float32)
and `h = N(x)`; no bias anywhere, no q/k norm:

* attention: `q = h wq` (`num_heads` of `head_dim`), `[k | v] = h wkv`
  (`num_kv_heads`; query head j reads KV head `j // (heads /
  kv_heads)`), scores at `1 / sqrt(head_dim)`, then `wo`.
  - a WINDOW layer (the layers `window_layers` name): q and k turned
    by rotary angles `p * rope_theta^(-2i / head_dim)` in the
    half-split convention (the pair (x_i, x_{i + head_dim / 2})), over
    the whole head; key j visible to query i iff 0 <= i - j <
    `sliding_window`;
  - a FULL layer (every other): no positional encoding; key j visible
    iff j <= i.
* router, before the attention: `r = softmax(h W_r)` over all
  `n_routed_experts` in float32; the `num_experts_per_tok` largest are
  chosen and weighted `r_e / sum of the chosen` (`norm_topk_prob`).
* MLP: `m = sum_e w_e ((relu(y gate_e) * (y up_e)) down_e)` over the
  chosen experts, `y = N(x + attention)`: sparse ReGLU, no shared
  expert.

A chip may hold `n_routed_experts_held` of a layer's experts from
`expert_offset` on (the cell holds all of them). The parameters are
made ON THE DEVICE at the serving type, tensor by tensor from the seed
(`models/latent_moe.py` says why).
"""

import jax
import jax.numpy as jnp

from .latent_moe import _normal, _normal_per_expert

__all__ = ["SmallThinkerConfig", "smallthinker_tiny", "init_params",
           "param_shapes"]


class SmallThinkerConfig:
    vocab_size = 151936
    hidden_size = 2560
    num_layers = 52
    window_layers = None            # default: every layer but each 4th
    num_heads = 28
    num_kv_heads = 4
    head_dim = 128
    sliding_window = 4096
    rope_theta = 1.5e6
    rms_norm_eps = 1e-6
    moe_intermediate_size = 768
    n_routed_experts = 64
    num_experts_per_tok = 6
    norm_topk_prob = True
    # this chip's share of each expert layer
    n_routed_experts_held = 64
    expert_offset = 0
    max_position = 16384
    initializer_range = 0.02

    def __init__(self, **kw):
        for k, v in kw.items():
            if not hasattr(type(self), k):
                raise TypeError(f"SmallThinkerConfig has no field {k!r}")
            setattr(self, k, v)
        if self.window_layers is None:
            self.window_layers = tuple(i for i in range(self.num_layers)
                                       if i % 4)
        self.window_layers = tuple(int(i) for i in self.window_layers)
        if not all(0 <= i < self.num_layers for i in self.window_layers):
            raise ValueError(
                f"window_layers {self.window_layers} are not among the "
                f"{self.num_layers} layers")
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.n_routed_experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.n_routed_experts_held}) are not among the "
                f"{self.n_routed_experts} routed experts")

    def is_window_layer(self, i):
        return i in self.window_layers


def smallthinker_tiny(**kw):
    """Two periods of (full, window, window, window), 64 wide, a window
    of 16, 16 experts of which 4 a token: the size of the CPU tests."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
                num_kv_heads=2, head_dim=16, sliding_window=16,
                rope_theta=1e4, moe_intermediate_size=32,
                n_routed_experts=16, num_experts_per_tok=4,
                n_routed_experts_held=16, max_position=128)
    base.update(kw)
    return SmallThinkerConfig(**base)


def param_shapes(cfg):
    """{name: shape} of the top level and of a layer (window and full
    layers have the same): the names `init_params` makes, the fused
    step reads and the plain reference knows the program by."""
    h = cfg.hidden_size
    moe, held = cfg.moe_intermediate_size, cfg.n_routed_experts_held
    q_width = cfg.num_heads * cfg.head_dim
    return {
        "top": {"word_emb": (cfg.vocab_size, h), "lnf_s": (h,),
                "head": (h, cfg.vocab_size)},
        "layer": {
            "ln1_s": (h,), "ln2_s": (h,),
            "wq": (h, q_width),
            # keys and values side by side: one product
            "wkv": (h, 2 * cfg.num_kv_heads * cfg.head_dim),
            "wo": (q_width, h),
            "router_w": (h, cfg.n_routed_experts),
            # the held experts' gate and up side by side: one product
            "exp_gu": (held, h, 2 * moe), "exp_down": (held, moe, h)},
    }


def init_params(cfg, seed, dtype=jnp.float32):
    """The parameter tree, made on the default device from `seed`, in
    `dtype`: matrices normal at `initializer_range`, norm scales one.
    Expert e of a layer is the same tensor on whichever chip holds
    it."""
    root = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    shapes = param_shapes(cfg)
    std = cfg.initializer_range

    def make(key, name, shape):
        if name.endswith("_s"):
            return jnp.ones(shape, dtype)
        if name in ("exp_gu", "exp_down"):
            return _normal_per_expert(key, std, cfg.expert_offset, shape,
                                      dtype)
        return _normal(key, std, shape, dtype)

    def group(key, table):
        return {name: make(jax.random.fold_in(key, j), name, shape)
                for j, (name, shape) in enumerate(sorted(table.items()))}

    params = group(jax.random.fold_in(root, 0), shapes["top"])
    for i in range(cfg.num_layers):
        params[f"l{i}"] = group(jax.random.fold_in(root, i + 1),
                                shapes["layer"])
    return params
