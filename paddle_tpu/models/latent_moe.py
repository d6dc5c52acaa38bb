"""Decoder with multi-head LATENT attention and a sigmoid-routed
mixture of experts (the DeepSeek-V3 family's block, whose keys
`LatentMoEConfig` follows): configuration, parameter names and seeded
initialisation, for serving (`serving/latent_moe.py`).

One layer, with `N` an RMS norm (learned scale, computed in float32)
and `h = N(x)`:

* latent attention: `c_q = N(h wq_a)`, `q = c_q wq_b` (heads of
  `[q_nope | q_rope]`); `[c_kv | k_rope] = h wkv_a`, `c_kv = N(c_kv)`;
  rotary on `q_rope` and on the one `k_rope` a token; `[k_nope | v] =
  c_kv wkv_b` a head; scores over `[q_nope . k_nope + q_rope . k_rope]
  / sqrt(nope + rope)`; output through `wo`. No biases.
* MLP: `(silu(h w_gate) * (h w_up)) w_down`, the first
  `first_k_dense` layers; after them `n_routed_experts` experts of
  that form, `num_experts_per_tok` a token by `serving/moe.route`, and
  `n_shared_experts` shared ones every token takes.

A chip of an expert-parallel deployment holds `n_routed_experts_held`
of a layer's experts, from `expert_offset` on; everything else is
replicated. The parameters are made at that share.

Parameters are made ON THE DEVICE at the serving type, tensor by
tensor from the seed: a float32 scope of the whole model (the path
`GPTServingModel.from_scope` takes) is four bytes a parameter and does
not fit beside nothing at the sizes this family is served at.
"""

import functools

import jax
import jax.numpy as jnp

__all__ = ["LatentMoEConfig", "latent_moe_tiny", "init_params",
           "param_shapes"]


class LatentMoEConfig:
    vocab_size = 129280
    hidden_size = 2048
    num_layers = 40
    num_heads = 32
    q_lora_rank = 1536
    kv_lora_rank = 512
    qk_nope_head_dim = 128
    qk_rope_head_dim = 64
    v_head_dim = 128
    rope_theta = 32e6
    rms_norm_eps = 1e-6
    intermediate_size = 7168        # the leading dense layers' width
    first_k_dense = 1
    moe_intermediate_size = 768
    n_routed_experts = 256
    num_experts_per_tok = 8
    n_shared_experts = 1
    routed_scaling_factor = 2.5
    norm_topk_prob = True
    # this chip's share of each expert layer
    n_routed_experts_held = 256
    expert_offset = 0
    max_position = 4096
    initializer_range = 0.02
    router_bias_range = 0.05

    def __init__(self, **kw):
        for k, v in kw.items():
            if not hasattr(type(self), k):
                raise TypeError(f"LatentMoEConfig has no field {k!r}")
            setattr(self, k, v)
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.n_routed_experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.n_routed_experts_held}) are not among the "
                f"{self.n_routed_experts} routed experts")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_expert_layer(self, i):
        return i >= self.first_k_dense


def latent_moe_tiny(**kw):
    """1 dense + 3 expert layers, 64 wide, 16 experts of which 4 a
    token: the size of the CPU tests."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
                moe_intermediate_size=32, n_routed_experts=16,
                num_experts_per_tok=4, n_routed_experts_held=16,
                max_position=128)
    base.update(kw)
    return LatentMoEConfig(**base)


def param_shapes(cfg):
    """{name: shape} of the top level and {name: shape} of a layer
    (`dense` or `experts`): the names `init_params` makes, the fused
    step reads and the plain reference knows the program by."""
    h, heads = cfg.hidden_size, cfg.num_heads
    attn = {
        "ln1_s": (h,), "ln2_s": (h,),
        "wq_a": (h, cfg.q_lora_rank), "q_norm_s": (cfg.q_lora_rank,),
        "wq_b": (cfg.q_lora_rank, heads * cfg.qk_head_dim),
        "wkv_a": (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm_s": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank,
                  heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (heads * cfg.v_head_dim, h),
    }
    inner, moe = cfg.intermediate_size, cfg.moe_intermediate_size
    shared = moe * cfg.n_shared_experts
    held = cfg.n_routed_experts_held
    return {
        "top": {"word_emb": (cfg.vocab_size, h), "lnf_s": (h,),
                "head": (h, cfg.vocab_size)},
        "dense": dict(attn, w_gate=(h, inner), w_up=(h, inner),
                      w_down=(inner, h)),
        "experts": dict(
            attn, router_w=(h, cfg.n_routed_experts),
            router_b=(cfg.n_routed_experts,),
            shared_gate=(h, shared), shared_up=(h, shared),
            shared_down=(shared, h),
            # the held experts' gate and up side by side: one product
            exp_gu=(held, h, 2 * moe), exp_down=(held, moe, h)),
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(dtype)


@functools.partial(jax.jit, static_argnames=("first", "shape", "dtype"))
def _normal_per_expert(key, scale, first, shape, dtype):
    """(E, ...) of which row e is `_normal` under the key folded from
    expert `first + e`'s GLOBAL index."""
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
        first + jnp.arange(shape[0]))
    return jax.vmap(lambda k: _normal(k, scale, shape[1:], dtype))(keys)


def init_params(cfg, seed, dtype=jnp.float32):
    """The parameter tree, made on the default device from `seed`, in
    `dtype`: matrices normal at `initializer_range`, norm scales one,
    and the router's correction bias `router_b` normal at
    `router_bias_range`, in float32 whatever `dtype` is (a trained one
    is small and not zero; a zero one would let a program that drops it
    pass). The held experts' weights depend on which experts are held:
    expert e of a layer is the same tensor on whichever chip holds
    it."""
    root = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    shapes = param_shapes(cfg)
    std = cfg.initializer_range

    def make(key, name, shape):
        if name.endswith("_s"):
            return jnp.ones(shape, dtype)
        if name == "router_b":
            return _normal(key, cfg.router_bias_range, shape, jnp.float32)
        if name in ("exp_gu", "exp_down"):
            return _normal_per_expert(key, std, cfg.expert_offset, shape,
                                      dtype)
        return _normal(key, std, shape, dtype)

    def group(key, table):
        return {name: make(jax.random.fold_in(key, j), name, shape)
                for j, (name, shape) in enumerate(sorted(table.items()))}

    params = group(jax.random.fold_in(root, 0), shapes["top"])
    for i in range(cfg.num_layers):
        kind = "experts" if cfg.is_expert_layer(i) else "dense"
        params[f"l{i}"] = group(jax.random.fold_in(root, i + 1),
                                shapes[kind])
    return params
