"""Plain reference of the Solar-Open2 forward pass (gated grouped-query
layers and KDA delta-rule layers over mixtures of experts): straight
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, the
KDA layers as the recurrence TOKEN BY TOKEN (`lax.scan` over the
positions: no chunk form, no kernel, no carried state, no cache), a
full causal forward of one sequence. It knows the program only by its
parameter names and its configuration's fields
(`paddle_tpu/models/linear_moe.py`), and imports nothing of its
serving or kernel code.

With `x` a layer's input, `N` RMS norm (eps `rms_norm_eps`, learned
scale) and `h = N(x)`; no bias and no positional encoding anywhere:

* grouped-query layer (`cfg.gqa_layers`): `q = h wq` (heads of
  `head_dim`), `[k | v] = h wkv` (`num_kv_heads`; query head j reads
  KV head `j // (heads / kv_heads)`), scores `q . k / sqrt(head_dim)`,
  causal softmax, the heads' outputs times `sigmoid(h w_gate)`, `wo`.
* KDA layer: `[q~ | k~ | v~] = h wqkv`; `conv(z)_t = sum_j conv_w[j]
  z_{t-3+j}` a channel (zeros before the sequence), then SiLU; a head's
  q and k times `rsqrt(sum of squares + 1e-6)`, q times `d_k^-1/2`;
  `g_t = -exp(a_log) softplus((h w_fa) w_fb + dt_bias)` a head and key
  channel; `beta_t = 2 sigmoid(h w_beta)` a head; from `S = 0`,

      S' = Diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
      o_t = S^T q_t;

  `o_t` RMS-normed a head (`o_norm_s`), times `sigmoid((h w_ga)
  w_gb)`, through `wo`.
* every layer's MLP: `s = sigmoid(h router_w)`; the `k` largest of
  `s + router_b` are chosen; weights are `s` of the chosen over their
  sum (+1e-20), times `routed_scaling_factor`; THIS chip's share is the
  terms of the experts it holds, `[expert_offset, expert_offset +
  n_routed_experts_held)`, plus the shared expert `(silu(h gate) * (h
  up)) down`: the other terms are left out here as in the program.
* `x + attention`, `x + MLP`; final `N`, untied head, log-softmax.

Parameters arrive in the type they are served in (bf16). So that 8,191
positions fit beside the served weights and cache, everything a token
computes alone (the projections, the experts) runs over `ROWS` rows at
a time with its matrices upcast one at a time (an expert at a time),
attention a head at a time, the scan over `HEAD_GROUP` heads at a
time, and the head over the rows asked for.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024             # rows a token-wise stage takes in one piece
HEAD_GROUP = 16         # heads the token-by-token scan carries at once
HEAD_ROWS = 256         # rows the head is computed for in one call


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _f32(a):
    return a.astype(jnp.float32)


def _by_rows(fn, x):
    """fn over x (T, ...) in pieces of ROWS rows (T divides, or is
    smaller)."""
    t = x.shape[0]
    if t <= ROWS or t % ROWS:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((t // ROWS, ROWS) + x.shape[1:]))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((t,) + a.shape[2:]), out)


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _experts(h, lp, k, scaling, normalize, offset):
    def rows(h):
        s = jax.nn.sigmoid(h @ _f32(lp["router_w"]))
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
            return y + we[:, None] * _gated(
                h, gu[:, :inner], gu[:, inner:], lp["exp_down"][e])

        y = jax.lax.fori_loop(0, lp["exp_gu"].shape[0], add_expert,
                              jnp.zeros_like(h))
        return y + _gated(h, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
    return _by_rows(rows, h)


def _gqa(h, lp, heads, kv_heads, d):
    t = h.shape[0]
    q = _by_rows(lambda r: r @ _f32(lp["wq"]), h).reshape(t, heads, d)
    kv = _by_rows(lambda r: r @ _f32(lp["wkv"]), h).reshape(
        t, 2, kv_heads, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    rep = heads // kv_heads

    def one_head(args):
        qh, j = args                                        # (T, d)
        kh, vh = kv[:, 0, j // rep], kv[:, 1, j // rep]
        s = (qh @ kh.T) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ vh

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), jnp.arange(heads)))
    o = o.transpose(1, 0, 2).reshape(t, heads * d)
    gate = _by_rows(lambda r: jax.nn.sigmoid(r @ _f32(lp["w_gate"])), h)
    return _by_rows(lambda r: r @ _f32(lp["wo"]), o * gate)


def _conv_silu(z, taps):
    """Causal depthwise convolution, zeros before the sequence, then
    SiLU. z (T, ch), taps (K, ch)."""
    k, t = taps.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, z.shape[1])), z])
    return jax.nn.silu(sum(padded[j:j + t] * taps[j] for j in range(k)))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(h, lp, heads, d, eps, state_dtype):
    t = h.shape[0]
    group = min(HEAD_GROUP, heads)
    wqkv = lp["wqkv"].reshape(-1, 3, heads // group, group * d)
    conv_w = _f32(lp["conv_w"]).reshape(-1, 3, heads // group, group * d)
    w_fb = lp["w_fb"].reshape(-1, heads // group, group * d)
    fa = _by_rows(lambda r: r @ _f32(lp["w_fa"]), h)
    ga = _by_rows(lambda r: r @ _f32(lp["w_ga"]), h)
    beta = 2.0 * jax.nn.sigmoid(
        _by_rows(lambda r: r @ _f32(lp["w_beta"]), h))      # (T, H)
    rate = jnp.exp(lp["a_log"])                             # (H,)

    def one_group(n):
        def stream(i):
            z = _by_rows(lambda r: r @ _f32(wqkv[:, i, n]), h)
            return _conv_silu(z, conv_w[:, i, n]).reshape(t, group, d)

        q = _unit(stream(0)) * d ** -0.5
        k = _unit(stream(1))
        v = stream(2)
        dt = fa @ _f32(w_fb[:, n]) + jax.lax.dynamic_slice_in_dim(
            lp["dt_bias"], n * group * d, group * d)
        g = -jax.lax.dynamic_slice_in_dim(rate, n * group, group)[
            :, None] * jax.nn.softplus(dt).reshape(t, group, d)
        b = jax.lax.dynamic_slice_in_dim(beta, n * group, group, axis=1)

        def token(s, xs):                       # s (group, d_k, d_v)
            qt, kt, vt, gt, bt = xs
            s = s * jnp.exp(gt)[..., None]
            pred = jnp.einsum("hkv,hk->hv", s, kt)
            s = s + bt[:, None, None] * kt[..., None] \
                * (vt - pred)[:, None, :]
            # a control may hold the state in a narrower type
            s = s.astype(state_dtype).astype(jnp.float32)
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(token, jnp.zeros((group, d, d)),
                            (q, k, v, g, b))
        return _rms(o, lp["o_norm_s"], eps).reshape(t, group * d)

    o = jax.lax.map(one_group, jnp.arange(heads // group))
    o = o.transpose(1, 0, 2).reshape(t, heads * d)
    gate = _by_rows(lambda r: jax.nn.sigmoid(r @ _f32(lp["w_gb"])), ga)
    return _by_rows(lambda r: r @ _f32(lp["wo"]), o * gate)


@functools.partial(jax.jit, static_argnames=("dims", "moe", "gqa",
                                             "state_dtype"))
def _block(x, lp, dims, moe, gqa, state_dtype):
    heads, kv_heads, d, kda_heads, kda_d, eps = dims
    small = {n: _f32(a) for n, a in lp.items() if a.ndim == 1}
    lp = {**lp, **small}
    h = _rms(x, lp["ln1_s"], eps)
    x = x + (_gqa(h, lp, heads, kv_heads, d) if gqa
             else _kda(h, lp, kda_heads, kda_d, eps, state_dtype))
    return x + _experts(_rms(x, lp["ln2_s"], eps), lp, *moe)


@functools.partial(jax.jit, static_argnames=("eps", "rows"))
def _head(x, start, lnf_s, head, eps, rows):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = _rms(x, lnf_s.astype(jnp.float32), eps)
    return jax.nn.log_softmax(x @ head.astype(jnp.float32), axis=-1)


def forward_logprobs(params, cfg, ids, pad_to, first_row=0, n_rows=None,
                     state_dtype=jnp.float32):
    """log P(next token | ids[:t+1]) for the positions t in
    [first_row, first_row + n_rows) of one sequence (all of them by
    default): an (n_rows, vocab) float32 numpy array. The sequence is
    padded to `pad_to` so that every call shares one compiled shape;
    causality keeps the padding out of the real rows."""
    n = len(ids)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = ids
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.kda_heads,
            cfg.kda_head_dim, float(cfg.rms_norm_eps))
    moe = (cfg.num_experts_per_tok, float(cfg.routed_scaling_factor),
           bool(cfg.norm_topk_prob), cfg.expert_offset)
    last = n if n_rows is None else first_row + n_rows
    rows = min(HEAD_ROWS, pad_to)
    with jax.default_matmul_precision("highest"):
        x = params["word_emb"][padded].astype(jnp.float32)
        for i in range(cfg.num_layers):
            x = _block(x, params[f"l{i}"], dims=dims, moe=moe,
                       gqa=cfg.is_gqa_layer(i), state_dtype=state_dtype)
        out = []
        for lo in range(first_row, last, rows):
            start = min(lo, pad_to - rows)
            logp = _head(x, start, params["lnf_s"], params["head"],
                         eps=dims[-1], rows=rows)
            out.append(np.asarray(logp[lo - start:min(last, lo + rows)
                                       - start]))
    return np.concatenate(out)
