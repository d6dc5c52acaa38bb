"""Plain reference of the Brumby forward pass (power retention over a
dense SwiGLU MLP) in its ATTENTION form: straight `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, a full causal forward of one
sequence (no kernel, no state, no expansion, no cache, no batching). It
knows the program only by its parameter names and its configuration's
fields (`paddle_tpu/models/brumby.py`), and imports nothing of its
serving or kernel code.

With `x` a layer's input, `N` RMS norm (eps `rms_norm_eps`, learned
scale) and `h = N(x)`; no bias but the gate's:

* retention: `q = Nq(h wq)` a head, `[k | v] = h wkv` a KV head, `k =
  Nk(k)` (`q_norm_s`, `k_norm_s` over a head's channels); q and k
  turned by the angles `t * rope_theta^(-2i / head_dim)` in the
  HALF-SPLIT convention, the pair (x_i, x_{i + head_dim / 2}); the
  log-gate `a_t = logsigmoid(h wa + ba)` a KV head, `A_t` its running
  sum; query head `i` reads KV head `i // (heads / kv_heads)`, and key
  `s <= t` weighs `w(t, s) = exp(A_t - A_s) (q_t . k_s / sqrt(d))^2`;
  `o_t = sum_s w(t, s) v_s / (sum_s w(t, s) + 1e-6)`, then `wo`.
* MLP on `y = N(x + retention)` (`ln2_s`): `(silu(y w_gate) * (y
  w_up)) w_down`.
* `x + retention`, `+ MLP`; final `N`, untied head, log-softmax.

`history`, where given, lets a query see only its last `history`
tokens: a control for the limits of `correct` (it cuts the state that
a program carries across chunks), never the model.

Parameters arrive in the type they are served in (bf16). So that 32,768
positions fit beside the served weights and state, the sequence is
padded to the shortest of `pad_to`, `pad_to / 2`, `pad_to / 4`, ...
(never under `MIN_PAD`) that holds it, so that calls share a few
compiled shapes and a block's float32 rows (3 GB at 32,768) are no
longer than they need be, and only its first `n` rows are computed:
the token-wise stages `ROWS` rows at a time, retention a head,
`Q_ROWS` queries and as many keys at a time over the keys that can be
visible to them, the MLP a `MLP_PIECES`-th of its width at a
time with its matrices upcast a piece at a time, and the head over the
rows asked for, an eighth of its columns upcast at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024             # rows a token-wise stage takes in one piece
Q_ROWS = 512            # queries (and keys) retention takes in one piece
MLP_PIECES = 8          # pieces of the MLP's width
HEAD_ROWS = 256         # rows the head is computed for in one call
HEAD_PIECES = 8         # pieces of the vocabulary its columns come in
EPS = 1e-6              # added to the sum of the weights
MIN_PAD = 4096          # the shortest padded length


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


def _retention(h, lp, n, heads, kv_heads, d, eps, theta, history):
    """A head at a time: its queries from h, its output through its
    rows of wo, summed over the heads (no (T, heads, d) array is
    held)."""
    t, hid = h.shape
    kv = _by_rows(lambda r: r @ _f32(lp["wkv"]), h, n,
                  2 * kv_heads * d).reshape(t, 2, kv_heads, d)
    k = _rotate_half(_rms(kv[:, 0], lp["k_norm_s"], eps), theta)
    v = kv[:, 1]
    acc = jnp.cumsum(jax.nn.log_sigmoid(
        _by_rows(lambda r: r @ _f32(lp["wa"]), h, n, kv_heads)
        + lp["ba"]), axis=0)                                # (T, K)
    wq = lp["wq"].reshape(hid, heads, d)
    wo = lp["wo"].reshape(heads, d, hid)
    rep = heads // kv_heads
    qr = min(Q_ROWS, t)
    assert t % qr == 0, (t, qr)

    def one_head(i, out):
        q = _by_rows(lambda r: r @ _f32(wq[:, i]), h, n, d)
        q = _rotate_half(_rms(q, lp["q_norm_s"], eps)[:, None],
                         theta)[:, 0] / np.sqrt(d)
        j = i // rep
        kh, vh, ah = (jax.lax.dynamic_index_in_dim(a, j, 1, False)
                      for a in (k, v, acc))

        def piece(b, o):
            lo = b * qr
            qb = jax.lax.dynamic_slice_in_dim(q, lo, qr)
            aq = jax.lax.dynamic_slice_in_dim(ah, lo, qr)
            qpos = lo + jax.lax.iota(jnp.int32, qr)

            def keys(kb, carry):
                num, den = carry
                klo = kb * qr
                kk = jax.lax.dynamic_slice_in_dim(kh, klo, qr)
                vv = jax.lax.dynamic_slice_in_dim(vh, klo, qr)
                ak = jax.lax.dynamic_slice_in_dim(ah, klo, qr)
                kpos = klo + jax.lax.iota(jnp.int32, qr)
                seen = kpos[None] <= qpos[:, None]
                if history is not None:
                    seen &= qpos[:, None] - kpos[None] < history
                w = jnp.where(seen, (qb @ kk.T) ** 2 * jnp.exp(
                    jnp.minimum(aq[:, None] - ak[None], 0.0)), 0.0)
                return num + w @ vv, den + w.sum(-1)

            first = 0 if history is None else \
                jnp.maximum(lo - history + 1, 0) // qr
            num, den = jax.lax.fori_loop(
                first, b + 1, keys, (jnp.zeros((qr, d), jnp.float32),
                                     jnp.zeros((qr,), jnp.float32)))
            return jax.lax.dynamic_update_slice_in_dim(
                o, num / (den[:, None] + EPS), lo, 0)

        o = jax.lax.fori_loop(0, (n + qr - 1) // qr, piece,
                              jnp.zeros((t, d), jnp.float32))
        return out + _by_rows(lambda r: r @ _f32(wo[i]), o, n, hid)

    return jax.lax.fori_loop(0, heads, one_head,
                             jnp.zeros((t, hid), jnp.float32))


def _mlp(y, lp, n):
    """(silu(y w_gate) * (y w_up)) w_down, its width in MLP_PIECES
    pieces, each piece's matrices upcast as it is used."""
    inner = lp["w_gate"].shape[1]
    pieces = MLP_PIECES if inner % MLP_PIECES == 0 else 1
    width = inner // pieces

    def rows(r):
        def piece(p, out):
            def cols(w):
                return _f32(jax.lax.dynamic_slice_in_dim(w, p * width,
                                                         width, axis=1))
            f = jax.nn.silu(r @ cols(lp["w_gate"])) * (r @ cols(lp["w_up"]))
            return out + f @ _f32(jax.lax.dynamic_slice_in_dim(
                lp["w_down"], p * width, width, axis=0))
        return jax.lax.fori_loop(0, pieces, piece, jnp.zeros_like(r))

    return _by_rows(rows, y, n, y.shape[1])


@functools.partial(jax.jit, static_argnames=("dims", "history"))
def _block(x, n, lp, dims, history):
    heads, kv_heads, d, eps, theta = dims
    small = {name: _f32(a) for name, a in lp.items() if a.ndim == 1}
    lp = {**lp, **small}
    h = _rms(x, lp["ln1_s"], eps)
    x = x + _retention(h, lp, n, heads, kv_heads, d, eps, theta, history)
    return x + _mlp(_rms(x, lp["ln2_s"], eps), lp, n)


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


def forward_logprobs(params, cfg, ids, pad_to, first_row=0, n_rows=None,
                     history=None):
    """log P(next token | ids[:t+1]) for the positions t in
    [first_row, first_row + n_rows) of one sequence (all of them by
    default): an (n_rows, vocab) float32 numpy array. The sequence is
    padded to `pad_to` so that every call shares one compiled shape;
    rows past the sequence are not computed, and causality keeps them
    out of the real rows."""
    n = len(ids)
    while pad_to // 2 >= max(n, MIN_PAD) and pad_to % 2 == 0:
        pad_to //= 2
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = ids
    dims = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            float(cfg.rms_norm_eps), float(cfg.rope_theta))
    last = n if n_rows is None else first_row + n_rows
    rows = min(HEAD_ROWS, pad_to)
    with jax.default_matmul_precision("highest"):
        x = params["word_emb"][padded].astype(jnp.float32)
        for i in range(cfg.num_layers):
            x = _block(x, jnp.int32(n), params[f"l{i}"], dims=dims,
                       history=history)
        out = []
        for lo in range(first_row, last, rows):
            start = min(lo, pad_to - rows)
            logp = _head(x, start, params["lnf_s"], params["head"],
                         eps=dims[3], rows=rows)
            out.append(np.asarray(logp[lo - start:min(last, lo + rows)
                                       - start]))
    return np.concatenate(out)
