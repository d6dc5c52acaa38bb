"""Plain reference of BERT's pre-training loss (masked LM + next
sentence prediction): straight `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernel. It knows the
program only by its parameter names (`models/bert.py`) and its feed
schema.

Follows "BERT: Pre-training of Deep Bidirectional Transformers" (Devlin
et al., 2018) and google-research/bert `modeling.py` /
`run_pretraining.py`: token + segment + position embeddings, layer norm,
post-norm encoder layers (x = ln(x + attn(x)); x = ln(x + ffn(x))),
exact GELU, a tanh pooler over [CLS], an MLM head (dense + GELU + layer
norm, decoder tied to the token embedding plus a bias) averaged over the
weighted masked positions, and a two-way NSP head.

Departures, noted in the configuration file: layer-norm epsilon 1e-5
(the repo's default, published 1e-12); evaluated in test mode, where the
repo's dropout (Fluid's downgrade_in_infer) scales its input by 1 - p,
at the places the repo puts dropout: after the embedding layer norm,
on the attention block's output, and on the FFN's output.
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


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


@functools.partial(jax.jit, static_argnames=("heads", "keep_attn",
                                             "keep_hidden"))
def _layer(x, bias, lp, heads, keep_attn, keep_hidden):
    b, t, m = x.shape
    d = m // heads

    def split(y):
        return y.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

    q = split(x @ lp["q"] + lp["q_b"])
    k = split(x @ lp["k"] + lp["k_b"])
    v = split(x @ lp["v"] + lp["v_b"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d) + bias
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, m) @ lp["o"] + lp["o_b"]
    x = _ln(x + a * keep_attn, lp["ln0_w"], lp["ln0_b"])
    f = _gelu(x @ lp["ffn0_w"] + lp["ffn0_b"]) @ lp["ffn1_w"] \
        + lp["ffn1_b"]
    return _ln(x + f * keep_hidden, lp["ln1_w"], lp["ln1_b"])


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames=("keep_hidden",))
def _embed(e, src, sent, input_mask, keep_hidden):
    b, t = src.shape
    x = e["word"][src] + e["sent"][sent] + e["pos"][:t]
    x = _ln(x, e["ln_w"], e["ln_b"]) * keep_hidden
    bias = (input_mask.reshape(b, 1, 1, t) - 1.0) * 1e9
    return x, bias


@jax.jit
def _heads(x, h, mask_pos, mask_label, mask_weight, nsp_label):
    b, t, m = x.shape
    # masked LM head over the gathered positions
    g = x.reshape(b * t, m)[mask_pos.reshape(-1)]
    g = _gelu(g @ h["mlm_trans_w"] + h["mlm_trans_b"])
    g = _ln(g, h["mlm_ln_w"], h["mlm_ln_b"])
    logits = g @ h["word"].T + h["mlm_out_b"]
    w = mask_weight.reshape(-1)
    mlm = (_xent(logits, mask_label.reshape(-1)) * w).sum() \
        / (w.sum() + 1e-6)
    # next sentence prediction over the pooled [CLS]
    pooled = jnp.tanh(x[:, 0] @ h["pooled_fc_w"] + h["pooled_fc_b"])
    nsp = _xent(pooled @ h["nsp_fc_w"] + h["nsp_fc_b"],
                nsp_label.reshape(-1)).mean()
    return mlm + nsp


_LAYER_PARAMS = (
    ("q", "attn_q"), ("k", "attn_k"), ("v", "attn_v"), ("o", "attn_o"),
    ("q_b", "attn_q_b"), ("k_b", "attn_k_b"), ("v_b", "attn_v_b"),
    ("o_b", "attn_o_b"), ("ln0_w", "ln0_w"), ("ln0_b", "ln0_b"),
    ("ffn0_w", "ffn0_w"), ("ffn0_b", "ffn0_b"), ("ffn1_w", "ffn1_w"),
    ("ffn1_b", "ffn1_b"), ("ln1_w", "ln1_w"), ("ln1_b", "ln1_b"))
_HEAD_PARAMS = ("mlm_trans_w", "mlm_trans_b", "mlm_ln_w", "mlm_ln_b",
                "mlm_out_b", "pooled_fc_w", "pooled_fc_b", "nsp_fc_w",
                "nsp_fc_b")


def pretrain_loss(scope, c, feed):
    """The MLM + NSP loss of one batch at the parameters in `scope`, in
    test mode. `c` is the configuration file's dict."""
    def p(name):
        return jnp.asarray(scope.get(name), jnp.float32)

    def ints(name):
        return jnp.asarray(np.asarray(feed[name]), jnp.int32)

    keep_h = 1.0 - float(c["hidden_dropout_prob"])
    keep_a = 1.0 - float(c["attention_probs_dropout_prob"])
    with jax.default_matmul_precision("highest"):
        emb = {"word": p("word_embedding"), "sent": p("sent_embedding"),
               "pos": p("pos_embedding"), "ln_w": p("emb_ln_w"),
               "ln_b": p("emb_ln_b")}
        x, bias = _embed(emb, ints("src_ids"), ints("sent_ids"),
                         jnp.asarray(feed["input_mask"], jnp.float32),
                         keep_hidden=keep_h)
        for i in range(int(c["num_hidden_layers"])):
            lp = {k: p(f"enc{i}_{n}") for k, n in _LAYER_PARAMS}
            x = _layer(x, bias, lp, heads=int(c["num_attention_heads"]),
                       keep_attn=keep_a, keep_hidden=keep_h)
        head = {n: p(n) for n in _HEAD_PARAMS}
        head["word"] = emb["word"]
        loss = _heads(x, head, ints("mask_pos"), ints("mask_label"),
                      jnp.asarray(feed["mask_weight"], jnp.float32),
                      ints("nsp_label"))
    return float(loss)
