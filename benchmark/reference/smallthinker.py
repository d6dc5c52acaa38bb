"""Plain reference of the SmallThinker forward pass (sliding-window and
full grouped-query layers over softmax-routed ReGLU experts): straight
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, an
explicit window mask, a full causal forward of one sequence (no kernel,
no cache, no batching). It knows the program only by its parameter
names and its configuration's fields (`paddle_tpu/models/
smallthinker.py`), and imports nothing of its serving or kernel code.

With `x` a layer's input, `N` RMS norm (eps `rms_norm_eps`, learned
scale) and `h = N(x)`; no bias and no q/k norm anywhere:

* attention: `q = h wq` (heads of `head_dim`), `[k | v] = h wkv`
  (`num_kv_heads`; query head j reads KV head `j // (heads /
  kv_heads)`), scores `q . k / sqrt(head_dim)`, softmax over the
  visible keys, `wo`:
  - window layer (`cfg.window_layers`): q and k turned by the angles
    `t * rope_theta^(-2i / head_dim)` in the HALF-SPLIT convention, the
    pair (x_i, x_{i + head_dim / 2}); key j visible to query t iff
    0 <= t - j < `sliding_window`;
  - full layer: no positional encoding; key j visible iff j <= t.
* router, on the SAME `h` the attention reads: `r = softmax(h
  router_w)`; the `k` largest chosen, weights `r` of the chosen over
  their sum (`norm_topk_prob`); THIS chip's share is the terms of the
  experts it holds, `[expert_offset, expert_offset +
  n_routed_experts_held)`.
* MLP on `y = N(x + attention)` (`ln2_s`): `sum_e w_e ((relu(y gate_e)
  * (y up_e)) down_e)`, no shared expert.
* `x + attention`, `+ MLP`; final `N`, untied head, log-softmax.

Parameters arrive in the type they are served in (bf16). So that 16,384
positions fit beside the served weights and cache, the sequence is
padded to one length for every call but only its first `n` rows are
computed: the token-wise stages `ROWS` rows at a time, attention a head
and `Q_ROWS` queries at a time (a window layer over the `window +
Q_ROWS` keys that can be visible to those queries, a full layer over
all; a head's queries and output projection a head at a time), an
expert over the tokens that chose it, `EXPERT_ROWS` at a time, with its
matrices upcast one expert at a time, and the head over the rows asked
for, an eighth of its columns upcast at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024             # rows a token-wise stage takes in one piece
Q_ROWS = 512            # queries attention takes in one piece
EXPERT_ROWS = 256       # tokens an expert takes in one piece
HEAD_ROWS = 256         # rows the head is computed for in one call
HEAD_PIECES = 8         # pieces of the vocabulary its columns come in


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _f32(a):
    return a.astype(jnp.float32)


def _by_rows(fn, x, n, width):
    """fn over the first n rows of x (T, ...) in pieces of ROWS rows
    (T a multiple of ROWS, or smaller): (T, width) float32, zero past
    the pieces that hold row n - 1."""
    t = x.shape[0]
    rows = min(ROWS, t)
    assert t % rows == 0, (t, rows)

    def piece(i, out):
        lo = i * rows
        part = fn(jax.lax.dynamic_slice_in_dim(x, lo, rows))
        return jax.lax.dynamic_update_slice_in_dim(out, part, lo, 0)

    return jax.lax.fori_loop(0, (n + rows - 1) // rows, piece,
                             jnp.zeros((t, width), jnp.float32))


def _rotate_half(x, theta):
    """x (T, heads, d) turned by the angles of positions 0..T-1, the
    pair (x_i, x_{i + d/2}) by the i-th."""
    t, _, d = x.shape
    # iotas, not numpy tables: a float table in a jitted function is a
    # constant that JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS hoists out
    inv = jnp.power(jnp.float32(theta),
                    -2.0 * jax.lax.iota(jnp.float32, d // 2) / d)
    ang = jax.lax.iota(jnp.float32, t)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(h, lp, n, heads, kv_heads, d, window, theta):
    """A head at a time: its queries from h, its output through its
    rows of wo, summed over the heads (no (T, heads, d) array is
    held)."""
    t, hid = h.shape
    kv = _by_rows(lambda r: r @ _f32(lp["wkv"]), h, n,
                  2 * kv_heads * d).reshape(t, 2, kv_heads, d)
    k, v = kv[:, 0], kv[:, 1]
    if window is not None:
        k = _rotate_half(k, theta)
    wq = lp["wq"].reshape(hid, heads, d)
    wo = lp["wo"].reshape(heads, d, hid)
    rep = heads // kv_heads
    qr = min(Q_ROWS, t)
    assert t % qr == 0, (t, qr)
    # a window layer's queries [lo, lo + qr) can see keys no older than
    # lo - window + 1: `span` keys from there hold them all
    span = t if window is None else min(t, window + qr)

    def one_head(j, out):
        q = _by_rows(lambda r: r @ _f32(wq[:, j]), h, n, d)
        if window is not None:
            q = _rotate_half(q[:, None], theta)[:, 0]
        kh, vh = k[:, j // rep], v[:, j // rep]

        def piece(i, o):
            lo = i * qr
            first = jnp.clip(lo + qr - span, 0, t - span)
            qb = jax.lax.dynamic_slice_in_dim(q, lo, qr)
            kb = jax.lax.dynamic_slice_in_dim(kh, first, span)
            vb = jax.lax.dynamic_slice_in_dim(vh, first, span)
            s = (qb @ kb.T) / np.sqrt(d)
            qpos = lo + jax.lax.iota(jnp.int32, qr)[:, None]
            kpos = first + jax.lax.iota(jnp.int32, span)[None]
            visible = kpos <= qpos
            if window is not None:
                visible &= qpos - kpos < window
            p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jax.lax.dynamic_update_slice_in_dim(o, p @ vb, lo, 0)

        o = jax.lax.fori_loop(0, (n + qr - 1) // qr, piece,
                              jnp.zeros((t, d), jnp.float32))
        return out + _by_rows(lambda r: r @ _f32(wo[j]), o, n, hid)

    return jax.lax.fori_loop(0, heads, one_head,
                             jnp.zeros((t, hid), jnp.float32))


def _experts(h, y, lp, n, k, normalize, offset):
    """The held experts' terms for y (T, H), routed by h."""
    t = h.shape[0]
    r = jax.nn.softmax(
        _by_rows(lambda x: x @ _f32(lp["router_w"]), h, n,
                 lp["router_w"].shape[1]), axis=-1)
    _, ids = jax.lax.top_k(r, k)
    w = jnp.take_along_axis(r, ids, axis=-1)
    if normalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    live = jax.lax.iota(jnp.int32, t) < n
    inner = lp["exp_down"].shape[1]
    rows = min(EXPERT_ROWS, t)

    def one_expert(e, out):
        # the weight each token gives held expert e (0: not chosen)
        hit = ids == offset + e
        we = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)
        chose = hit.any(axis=-1) & live
        idx = jnp.nonzero(chose, size=t, fill_value=t)[0]
        gu, down = _f32(lp["exp_gu"][e]), _f32(lp["exp_down"][e])

        def piece(i, out):
            pick = jax.lax.dynamic_slice_in_dim(idx, i * rows, rows)
            ye = y.at[pick].get(mode="fill", fill_value=0.0)
            f = (jax.nn.relu(ye @ gu[:, :inner]) * (ye @ gu[:, inner:])
                 ) @ down
            wp = we.at[pick].get(mode="fill", fill_value=0.0)
            return out.at[pick].add(wp[:, None] * f, mode="drop")

        return jax.lax.fori_loop(
            0, (jnp.sum(chose) + rows - 1) // rows, piece, out)

    return jax.lax.fori_loop(0, lp["exp_gu"].shape[0], one_expert,
                             jnp.zeros_like(y))


@functools.partial(jax.jit, static_argnames=("dims", "window"))
def _block(x, n, lp, dims, window):
    heads, kv_heads, d, eps, theta, k, normalize, offset = dims
    small = {name: _f32(a) for name, a in lp.items() if a.ndim == 1}
    lp = {**lp, **small}
    h = _rms(x, lp["ln1_s"], eps)
    x = x + _attention(h, lp, n, heads, kv_heads, d, window, theta)
    return x + _experts(h, _rms(x, lp["ln2_s"], eps), lp, n, k,
                        normalize, offset)


@functools.partial(jax.jit, static_argnames=("eps", "rows"))
def _head(x, start, lnf_s, head, eps, rows):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = _rms(x, lnf_s.astype(jnp.float32), eps)
    v = head.shape[1]
    pieces = HEAD_PIECES if v % HEAD_PIECES == 0 else 1

    def piece(i):
        return x @ _f32(jax.lax.dynamic_slice_in_dim(
            head, i * (v // pieces), v // pieces, axis=1))

    logits = jax.lax.map(piece, jnp.arange(pieces))
    return jax.nn.log_softmax(
        logits.transpose(1, 0, 2).reshape(rows, v), axis=-1)


def forward_logprobs(params, cfg, ids, pad_to, first_row=0, n_rows=None):
    """log P(next token | ids[:t+1]) for the positions t in
    [first_row, first_row + n_rows) of one sequence (all of them by
    default): an (n_rows, vocab) float32 numpy array. The sequence is
    padded to `pad_to` so that every call shares one compiled shape;
    rows past the sequence are not computed, and causality keeps them
    out of the real rows."""
    n = len(ids)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = ids
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            float(cfg.rms_norm_eps), float(cfg.rope_theta),
            cfg.num_experts_per_tok, bool(cfg.norm_topk_prob),
            cfg.expert_offset)
    last = n if n_rows is None else first_row + n_rows
    rows = min(HEAD_ROWS, pad_to)
    with jax.default_matmul_precision("highest"):
        x = params["word_emb"][padded].astype(jnp.float32)
        for i in range(cfg.num_layers):
            window = (int(cfg.sliding_window) if cfg.is_window_layer(i)
                      else None)
            x = _block(x, jnp.int32(n), params[f"l{i}"], dims=dims,
                       window=window)
        out = []
        for lo in range(first_row, last, rows):
            start = min(lo, pad_to - rows)
            logp = _head(x, start, params["lnf_s"], params["head"],
                         eps=dims[3], rows=rows)
            out.append(np.asarray(logp[lo - start:min(last, lo + rows)
                                       - start]))
    return np.concatenate(out)
