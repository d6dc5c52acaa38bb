"""Plain reference of the JoyAI-LLM-Flash forward pass (the DeepSeek-V3
family's block): straight `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, the EXPANDED attention form,
a full causal forward with no cache, no kernel and no batching. It
knows the program only by its parameter names and its configuration's
fields (`paddle_tpu/models/latent_moe.py`), and imports nothing of its
serving or kernel code.

With `x` a layer's input, `N` RMS norm (eps `rms_norm_eps`, learned
scale) and `h = N(x)`:

* latent attention: `c_q = N(h wq_a)`; `q = c_q wq_b`, heads of
  `[q_nope | q_rope]`; `[c_kv | k_rope] = h wkv_a`; `c_kv = N(c_kv)`;
  rotary on `q_rope` and on the one `k_rope` a token, pairs
  `(2i, 2i+1)` turned by `pos x theta^(-2i/rope)` (`rope_interleave`);
  `[k_nope | v] = c_kv wkv_b` a head; scores
  `(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)`, causal
  softmax, `o = sum p v`, output `concat(o) wo`. No biases.
* layer 0 and every expert: `(silu(h gate) * (h up)) down`.
* expert layers: `s = sigmoid(h router_w)`; the `k` largest of
  `s + router_b` are chosen; weights are `s` of the chosen, without
  the bias, over their sum (+1e-20), times `routed_scaling_factor`;
  `y = sum_k w_k E_k(h) + E_shared(h)`, of which THIS chip's share is
  the terms of the experts it holds, `[expert_offset, expert_offset +
  n_routed_experts_held)`, plus the shared expert: the other terms are
  left out here as in the program, and that partial `y` goes on.
* `x + attention`, `x + MLP`; final `N`, untied head, log-softmax.

Left out, as the configuration's `departures` say: the multi-token
prediction layer.

Parameters arrive in the type they are served in (bf16) and are upcast
one layer at a time, attention runs a head at a time and the head over
the rows asked for, so that the reference fits beside the served
weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_ROWS = 256         # rows the head is computed for in one call


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rotate(x, pos, theta):
    """Interleaved rotary: pair (x[2i], x[2i+1]) by pos * theta^(-2i/d).
    x (T, ..., d), pos (T,)."""
    d = x.shape[-1]
    inv = jnp.power(jnp.float32(theta),
                    -2.0 * jax.lax.iota(jnp.float32, d // 2) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attention(h, lp, dims):
    heads, nope, rope, vd, lora, theta, eps = dims
    t = h.shape[0]
    pos = jnp.arange(t)
    cq = _rms(h @ lp["wq_a"], lp["q_norm_s"], eps)
    q = (cq @ lp["wq_b"]).reshape(t, heads, nope + rope)
    kv = h @ lp["wkv_a"]
    ckv = _rms(kv[:, :lora], lp["kv_norm_s"], eps)
    k_rope = _rotate(kv[:, lora:], pos, theta)              # (T, rope)
    q_rope = _rotate(q[..., nope:], pos, theta)
    kvb = (ckv @ lp["wkv_b"]).reshape(t, heads, nope + vd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(args):
        qn, qr, kn, v = args                                # (T, .)
        s = (qn @ kn.T + qr @ k_rope.T) / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ v

    o = jax.lax.map(one_head, (
        q[..., :nope].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        kvb[..., :nope].transpose(1, 0, 2),
        kvb[..., nope:].transpose(1, 0, 2)))                # (H, T, vd)
    return o.transpose(1, 0, 2).reshape(t, heads * vd) @ lp["wo"]


def _experts(h, lp, k, scaling, normalize, offset):
    s = jax.nn.sigmoid(h @ lp["router_w"])
    _, ids = jax.lax.top_k(s + lp["router_b"], k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if normalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * scaling
    inner = lp["exp_down"].shape[1]

    def add_expert(e, y):
        # the weight each token gives held expert e (0: not chosen)
        we = jnp.sum(jnp.where(ids == offset + e, w, 0.0), axis=-1)
        gu = lp["exp_gu"][e]
        return y + we[:, None] * _gated(h, gu[:, :inner], gu[:, inner:],
                                        lp["exp_down"][e])

    y = jax.lax.fori_loop(0, lp["exp_gu"].shape[0], add_expert,
                          jnp.zeros_like(h))
    return y + _gated(h, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"])


@functools.partial(jax.jit, static_argnames=("dims", "moe"))
def _block(x, lp, dims, moe):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    eps = dims[-1]
    x = x + _attention(_rms(x, lp["ln1_s"], eps), lp, dims)
    h = _rms(x, lp["ln2_s"], eps)
    if moe is None:
        return x + _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + _experts(h, lp, *moe)


@functools.partial(jax.jit, static_argnames=("eps", "rows"))
def _head(x, start, lnf_s, head, eps, rows):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = _rms(x, lnf_s.astype(jnp.float32), eps)
    return jax.nn.log_softmax(x @ head.astype(jnp.float32), axis=-1)


def forward_logprobs(params, cfg, ids, pad_to, first_row=0, n_rows=None):
    """log P(next token | ids[:t+1]) for the positions t in
    [first_row, first_row + n_rows) of one sequence (all of them by
    default): an (n_rows, vocab) float32 numpy array. The sequence is
    padded to `pad_to` so that every call shares one compiled shape;
    the causal mask keeps the padding out of the real rows. The head
    runs over `HEAD_ROWS` rows a call (a row is 517 KB)."""
    n = len(ids)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = ids
    dims = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank, float(cfg.rope_theta),
            float(cfg.rms_norm_eps))
    moe = (cfg.num_experts_per_tok, float(cfg.routed_scaling_factor),
           bool(cfg.norm_topk_prob), cfg.expert_offset)
    last = n if n_rows is None else first_row + n_rows
    rows = min(HEAD_ROWS, pad_to)
    with jax.default_matmul_precision("highest"):
        x = params["word_emb"][padded].astype(jnp.float32)
        for i in range(cfg.num_layers):
            x = _block(x, params[f"l{i}"], dims=dims,
                       moe=moe if cfg.is_expert_layer(i) else None)
        out = []
        for lo in range(first_row, last, rows):
            start = min(lo, pad_to - rows)
            logp = _head(x, start, params["lnf_s"], params["head"],
                         eps=dims[-1], rows=rows)
            out.append(np.asarray(logp[lo - start:min(last, lo + rows)
                                       - start]))
    return np.concatenate(out)
