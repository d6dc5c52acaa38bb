"""Decoder whose every layer is POWER RETENTION (attention whose weight
is a gated power of the query-key product, normalised by the sum of its
weights) over a dense SwiGLU MLP (the Brumby family's block, whose keys
`BrumbyConfig` follows): configuration, parameter names and seeded
initialisation, for serving (`serving/brumby.py`).

One layer, with `N` an RMS norm (learned scale, computed in float32)
and `h = N(x)`; no bias but the gate's:

* retention: `q = Nq(h wq)` (`num_heads` of `head_dim`), `[k | v] = h
  wkv` (`num_kv_heads`), `k = Nk(k)`, with `Nq`, `Nk` an RMS norm over a
  head's `head_dim` channels (`q_norm_s`, `k_norm_s`); q and k turned
  by half-split rotary angles at `rope_theta` over the whole head; a
  log-gate a KV head a token `a = logsigmoid(h wa + ba)`. Query head
  `i` reads KV head `i // (heads / kv_heads)`; with `A_t` the running
  sum of `a`, key `s <= t` weighs

      w(t, s) = exp(A_t - A_s) (q_t . k_s / sqrt(head_dim))^DEGREE

  and `o_t = sum_s w(t, s) v_s / (sum_s w(t, s) + RETENTION_EPS)`, then
  `wo`.
* MLP: `(silu(y w_gate) * (y w_up)) w_down`, `y = N(x + retention)`.

The gate's bias `ba` is not drawn: it is set so that the KV heads'
decays, at a zero input, have half-lives spread evenly in log from
`gate_half_life_min` to `gate_half_life_max` tokens. With random
weights and no bias every head would forget within a few tokens, and a
state carried wrongly would not show.

The parameters are made ON THE DEVICE at the serving type, tensor by
tensor from the seed (`models/latent_moe.py` says why).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe import _normal

__all__ = ["BrumbyConfig", "brumby_tiny", "init_params", "param_shapes",
           "gate_bias", "DEGREE", "RETENTION_EPS"]

DEGREE = 2              # the power of the query-key product
RETENTION_EPS = 1e-6    # added to the sum of the weights


class BrumbyConfig:
    vocab_size = 151936
    hidden_size = 5120
    num_layers = 40
    num_heads = 40
    num_kv_heads = 8
    head_dim = 128
    intermediate_size = 17408
    rope_theta = 1e6
    rms_norm_eps = 1e-6
    max_position = 32768
    initializer_range = 0.02
    gate_half_life_min = 16.0
    gate_half_life_max = 16384.0

    def __init__(self, **kw):
        for k, v in kw.items():
            if not hasattr(type(self), k):
                raise TypeError(f"BrumbyConfig has no field {k!r}")
            setattr(self, k, v)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_kv_heads} KV heads do not divide "
                f"{self.num_heads} query heads")


def brumby_tiny(**kw):
    """Two layers 64 wide, 4 query heads on 2 KV heads of 16: the size
    of the CPU tests."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, intermediate_size=96,
                rope_theta=1e4, max_position=128)
    base.update(kw)
    return BrumbyConfig(**base)


def gate_bias(cfg):
    """(num_kv_heads,) float32: `b` with `logsigmoid(b) = ln(1/2) / T`,
    the half-lives `T` spread evenly in log over [gate_half_life_min,
    gate_half_life_max]."""
    k = cfg.num_kv_heads
    span = np.geomspace(cfg.gate_half_life_min, cfg.gate_half_life_max,
                        k) if k > 1 else np.array([cfg.gate_half_life_min])
    a = np.log(0.5) / span
    return (a - np.log(-np.expm1(a))).astype(np.float32)


def param_shapes(cfg):
    """{name: shape} of the top level and of a layer: the names
    `init_params` makes, the fused step reads and the plain reference
    knows the program by."""
    h, d = cfg.hidden_size, cfg.head_dim
    q_width = cfg.num_heads * d
    return {
        "top": {"word_emb": (cfg.vocab_size, h), "lnf_s": (h,),
                "head": (h, cfg.vocab_size)},
        "layer": {
            "ln1_s": (h,), "ln2_s": (h,),
            "wq": (h, q_width),
            # keys and values side by side: one product
            "wkv": (h, 2 * cfg.num_kv_heads * d),
            "q_norm_s": (d,), "k_norm_s": (d,),
            "wa": (h, cfg.num_kv_heads), "ba": (cfg.num_kv_heads,),
            "wo": (q_width, h),
            "w_gate": (h, cfg.intermediate_size),
            "w_up": (h, cfg.intermediate_size),
            "w_down": (cfg.intermediate_size, h)},
    }


def init_params(cfg, seed, dtype=jnp.float32):
    """The parameter tree, made on the default device from `seed`, in
    `dtype`: matrices normal at `initializer_range`, norm scales one,
    the gate's bias `gate_bias` in float32 whatever `dtype` is."""
    root = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    shapes = param_shapes(cfg)
    std = cfg.initializer_range
    bias = jnp.asarray(gate_bias(cfg))

    def make(key, name, shape):
        if name.endswith("_s"):
            return jnp.ones(shape, dtype)
        if name == "ba":
            return bias
        return _normal(key, std, shape, dtype)

    def group(key, table):
        return {name: make(jax.random.fold_in(key, j), name, shape)
                for j, (name, shape) in enumerate(sorted(table.items()))}

    params = group(jax.random.fold_in(root, 0), shapes["top"])
    for i in range(cfg.num_layers):
        params[f"l{i}"] = group(jax.random.fold_in(root, i + 1),
                                shapes["layer"])
    return params
