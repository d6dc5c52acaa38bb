"""Decoder whose layers alternate softmax and LINEAR attention, every
one followed by a sigmoid-routed mixture of experts (the Solar Open 2 /
Kimi Linear family's block, whose keys `LinearMoEConfig` follows):
configuration, parameter names and seeded initialisation, for serving
(`serving/linear_moe.py`). No positional encoding anywhere: causal
order is all the position there is.

One layer, with `N` an RMS norm (learned scale, computed in float32)
and `h = N(x)`:

* a grouped-query layer (the layers `gqa_layers` name): `q = h wq`
  (`num_heads` of `head_dim`), `[k | v] = h wkv` (`num_kv_heads`),
  causal softmax at `1 / sqrt(head_dim)`, the heads' outputs times
  `sigmoid(h w_gate)`, one gate a value channel, then `wo`.
* a KDA layer (every other): `[q~ | k~ | v~] = h wqkv` (`kda_heads` of
  `kda_head_dim` each); each channel through a causal convolution of
  `short_conv_kernel_size` taps (`conv_w`, zeros before the request)
  and SiLU; q and k L2-normalised a head (eps 1e-6), q scaled by
  `kda_head_dim^-1/2`; decay `g = -exp(a_log) * softplus((h w_fa) w_fb
  + dt_bias)` a head and key channel, step `beta = 2 sigmoid(h
  w_beta)` a head; the state of a head (d_k x d_v, zero at the
  request's start) moves by the gated delta rule

      S' = Diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
      o_t = S^T q_t

  and `o` is RMS-normed a head (`o_norm_s`), times `sigmoid((h w_ga)
  w_gb)`, through `wo`.
* MLP of every layer: `n_routed_experts` experts `(silu(h gate) * (h
  up)) down`, `num_experts_per_tok` a token by `serving/moe.route`,
  and `n_shared_experts` shared ones every token takes.

A chip of an expert-parallel deployment holds `n_routed_experts_held`
of a layer's experts, from `expert_offset` on; everything else is
replicated. The parameters are made at that share, ON THE DEVICE at the
serving type, tensor by tensor from the seed (`models/latent_moe.py`
says why).
"""

import functools
import math

import jax
import jax.numpy as jnp

from .latent_moe import _normal, _normal_per_expert

__all__ = ["LinearMoEConfig", "linear_moe_tiny", "init_params",
           "param_shapes"]


class LinearMoEConfig:
    vocab_size = 196608
    hidden_size = 4096
    num_layers = 48
    gqa_layers = None               # default: every fourth, from 0
    num_heads = 64
    num_kv_heads = 8
    head_dim = 128
    kda_heads = 64
    kda_head_dim = 128              # a head's key AND value width
    short_conv_kernel_size = 4
    kda_gate_rank = 128             # of the two low-rank gate pairs
    rms_norm_eps = 1e-5
    moe_intermediate_size = 1280
    n_routed_experts = 320
    num_experts_per_tok = 8
    n_shared_experts = 1
    routed_scaling_factor = 1.0
    norm_topk_prob = True
    # this chip's share of each expert layer
    n_routed_experts_held = 320
    expert_offset = 0
    max_position = 8192
    initializer_range = 0.02
    router_bias_range = 0.05
    # the decay's initialisers: exp(a_log) uniform in `decay_rate`,
    # softplus(dt_bias) log-uniform in `decay_dt`
    decay_rate = (1.0, 16.0)
    decay_dt = (0.001, 0.1)

    def __init__(self, **kw):
        for k, v in kw.items():
            if not hasattr(type(self), k):
                raise TypeError(f"LinearMoEConfig has no field {k!r}")
            setattr(self, k, v)
        if self.gqa_layers is None:
            self.gqa_layers = tuple(range(0, self.num_layers, 4))
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers)
        if not all(0 <= i < self.num_layers for i in self.gqa_layers):
            raise ValueError(
                f"gqa_layers {self.gqa_layers} are not among the "
                f"{self.num_layers} layers")
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.n_routed_experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.n_routed_experts_held}) are not among the "
                f"{self.n_routed_experts} routed experts")

    def is_gqa_layer(self, i):
        return i in self.gqa_layers

    @property
    def kda_channels(self):
        """The q, k and v streams of a KDA layer side by side: what the
        short convolution runs over."""
        return 3 * self.kda_heads * self.kda_head_dim


def linear_moe_tiny(**kw):
    """Two periods of (grouped-query, KDA, KDA, KDA) less one, 64 wide,
    16 experts of which 4 a token: the size of the CPU tests."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=5,
                gqa_layers=(0, 4), num_heads=4, num_kv_heads=2,
                head_dim=16, kda_heads=4, kda_head_dim=16,
                kda_gate_rank=8, moe_intermediate_size=32,
                n_routed_experts=16, num_experts_per_tok=4,
                n_routed_experts_held=16, max_position=128)
    base.update(kw)
    return LinearMoEConfig(**base)


def param_shapes(cfg):
    """{name: shape} of the top level and of a layer of each kind
    (`gqa` or `kda`): the names `init_params` makes, the fused step
    reads and the plain reference knows the program by."""
    h = cfg.hidden_size
    moe, held = cfg.moe_intermediate_size, cfg.n_routed_experts_held
    shared = moe * cfg.n_shared_experts
    mlp = {
        "ln1_s": (h,), "ln2_s": (h,),
        "router_w": (h, cfg.n_routed_experts),
        "router_b": (cfg.n_routed_experts,),
        "shared_gate": (h, shared), "shared_up": (h, shared),
        "shared_down": (shared, h),
        # the held experts' gate and up side by side: one product
        "exp_gu": (held, h, 2 * moe), "exp_down": (held, moe, h),
    }
    q_width = cfg.num_heads * cfg.head_dim
    kh, kd, rank = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    return {
        "top": {"word_emb": (cfg.vocab_size, h), "lnf_s": (h,),
                "head": (h, cfg.vocab_size)},
        "gqa": dict(
            mlp, wq=(h, q_width),
            # keys and values side by side: one product
            wkv=(h, 2 * cfg.num_kv_heads * cfg.head_dim),
            w_gate=(h, q_width), wo=(q_width, h)),
        "kda": dict(
            mlp, wqkv=(h, cfg.kda_channels),
            conv_w=(cfg.short_conv_kernel_size, cfg.kda_channels),
            w_fa=(h, rank), w_fb=(rank, kh * kd), a_log=(kh,),
            dt_bias=(kh * kd,), w_beta=(h, kh), w_ga=(h, rank),
            w_gb=(rank, kh * kd), o_norm_s=(kd,), wo=(kh * kd, h)),
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _uniform(key, lo, hi, shape, dtype):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def init_params(cfg, seed, dtype=jnp.float32):
    """The parameter tree, made on the default device from `seed`, in
    `dtype`: matrices normal at `initializer_range`, norm scales one,
    the router's correction bias normal at `router_bias_range`, the
    convolutions' taps uniform in +-1/sqrt(taps) (a depthwise
    convolution's usual start), `a_log` and `dt_bias` as `decay_rate`
    and `decay_dt` say, these three and the bias in float32 whatever
    `dtype` is. Expert e of a layer is the same tensor on whichever
    chip holds it."""
    root = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    shapes = param_shapes(cfg)
    std = cfg.initializer_range
    taps = cfg.short_conv_kernel_size

    def make(key, name, shape):
        if name.endswith("_s"):
            return jnp.ones(shape, dtype)
        if name == "router_b":
            return _normal(key, cfg.router_bias_range, shape, jnp.float32)
        if name in ("exp_gu", "exp_down"):
            return _normal_per_expert(key, std, cfg.expert_offset, shape,
                                      dtype)
        if name == "conv_w":
            return _uniform(key, -taps ** -0.5, taps ** -0.5, shape,
                            dtype)
        if name == "a_log":
            return jnp.log(_uniform(key, *cfg.decay_rate, shape,
                                    jnp.float32))
        if name == "dt_bias":
            lo, hi = (math.log(x) for x in cfg.decay_dt)
            dt = jnp.exp(_uniform(key, lo, hi, shape, jnp.float32))
            return dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)
        return _normal(key, std, shape, dtype)

    def group(key, table):
        return {name: make(jax.random.fold_in(key, j), name, shape)
                for j, (name, shape) in enumerate(sorted(table.items()))}

    params = group(jax.random.fold_in(root, 0), shapes["top"])
    for i in range(cfg.num_layers):
        kind = "gqa" if cfg.is_gqa_layer(i) else "kda"
        params[f"l{i}"] = group(jax.random.fold_in(root, i + 1),
                                shapes[kind])
    return params
