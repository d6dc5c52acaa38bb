"""Plain reference of the GPT-2 forward pass: straight `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`, no kernel, no
cache, no batching. It knows the program only by its parameter names
(`models/gpt.py:load_params`).

Follows "Language Models are Unsupervised Multitask Learners" (Radford
et al., 2019) and the published GPT-2 code: learned token and position
embeddings, pre-norm blocks (x + attn(ln(x)); x + mlp(ln(x))), causal
softmax attention scaled by 1/sqrt(head_dim), a final layer norm and a
head tied to the token embedding.

Departure, noted in the configuration file: the activation is the exact
erf GELU, as the repo computes it, not GPT-2's tanh approximation.

Parameters arrive in the type they are served in (bf16) and are upcast
one layer at a time, so the reference fits beside the served weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale + bias


@functools.partial(jax.jit, static_argnames=("heads",))
def _block(x, lp, heads):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    t, m = x.shape
    d = m // heads
    h = _ln(x, lp["ln1_s"], lp["ln1_b"])
    q = (h @ lp["wq"] + lp["bq"]).reshape(t, heads, d).transpose(1, 0, 2)
    k = (h @ lp["wk"] + lp["bk"]).reshape(t, heads, d).transpose(1, 0, 2)
    v = (h @ lp["wv"] + lp["bv"]).reshape(t, heads, d).transpose(1, 0, 2)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
    a = a.transpose(1, 0, 2).reshape(t, m)
    x = x + a @ lp["wo"] + lp["bo"]
    h = _ln(x, lp["ln2_s"], lp["ln2_b"])
    f = jax.nn.gelu(h @ lp["f0w"] + lp["f0b"], approximate=False)
    return x + f @ lp["f1w"] + lp["f1b"]


@jax.jit
def _head(x, lnf_s, lnf_b, word_emb):
    x = _ln(x, lnf_s.astype(jnp.float32), lnf_b.astype(jnp.float32))
    return jax.nn.log_softmax(x @ word_emb.astype(jnp.float32).T, axis=-1)


def forward_logprobs(params, cfg, ids, pad_to, first_row=0, n_rows=None):
    """log P(next token | ids[:t+1]) for the positions t in
    [first_row, first_row + n_rows) of one sequence (all of them by
    default): an (n_rows, vocab) float32 numpy array. The sequence is
    padded to `pad_to` so that every call shares one compiled shape;
    the causal mask keeps the padding out of the real rows. Only the
    rows asked for leave the device (a row is 200 KB)."""
    n = len(ids)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = ids
    with jax.default_matmul_precision("highest"):
        x = (params["word_emb"][padded].astype(jnp.float32)
             + params["pos_emb"][:pad_to].astype(jnp.float32))
        for i in range(cfg.num_layers):
            x = _block(x, params[f"l{i}"], heads=cfg.num_heads)
        logp = _head(x, params["lnf_s"], params["lnf_b"],
                     params["word_emb"])
    last = n if n_rows is None else first_row + n_rows
    return np.asarray(logp[first_row:last])
