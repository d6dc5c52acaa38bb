"""GPT-style decoder-only language model (zoo extension).

The 1.5 book/models set stops at BERT/ERNIE encoders; this adds the
decoder-only family the same components support: pre-norm causal
transformer blocks (`layers.multi_head_attention(causal=True)` rides
the Pallas flash kernel / ring attention like every attention here),
weight-tied LM head, and KV-cache generation through
`inference/decoding.py`.

Train on the static-graph path (one fused XLA step); generate with
`build_kv_step` + `greedy_decode` on the SAME scope parameters — the
cached per-token forward is the training math re-expressed for O(1)
per-step decode, and `tests/models/test_gpt.py` pins the two paths
token-for-token.
"""

import numpy as np

import jax
import jax.numpy as jnp

from .. import layers
from ..core import framework
from ..core.param_attr import ParamAttr


class GPTConfig:
    vocab_size = 32000
    hidden_size = 768
    num_layers = 12
    num_heads = 12
    # grouped-query attention (serving tier): kv_heads < num_heads
    # shares each KV head across a group of num_heads/kv_heads query
    # heads; None means MHA. Only the fused serving step and the paged
    # KV pools consume this — the training graph stays full MHA.
    kv_heads = None
    inner_size = 3072
    max_position = 1024
    dropout = 0.1

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


def gpt_tiny():
    """4-layer/128-wide config for tests."""
    return GPTConfig(vocab_size=256, hidden_size=128, num_layers=4,
                     num_heads=4, inner_size=512, max_position=128,
                     dropout=0.0)


def _block(x, cfg, idx, segment_ids=None):
    """Pre-norm GPT-2 block: x + attn(ln(x)); x + ffn(ln(x))."""
    h = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"gpt{idx}_ln1_s"),
                          bias_attr=ParamAttr(name=f"gpt{idx}_ln1_b"))
    a = layers.multi_head_attention(
        h, num_heads=cfg.num_heads, d_model=cfg.hidden_size, causal=True,
        segment_ids=segment_ids, dropout_rate=cfg.dropout,
        param_attr=ParamAttr(name=f"gpt{idx}_attn"),
        bias_attr=ParamAttr(name=f"gpt{idx}_attn"))
    x = layers.elementwise_add(x, a)
    h = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"gpt{idx}_ln2_s"),
                          bias_attr=ParamAttr(name=f"gpt{idx}_ln2_b"))
    f = layers.fc(h, size=cfg.inner_size, num_flatten_dims=2, act="gelu",
                  param_attr=ParamAttr(name=f"gpt{idx}_ffn0_w"),
                  bias_attr=ParamAttr(name=f"gpt{idx}_ffn0_b"))
    f = layers.fc(f, size=cfg.hidden_size, num_flatten_dims=2,
                  param_attr=ParamAttr(name=f"gpt{idx}_ffn1_w"),
                  bias_attr=ParamAttr(name=f"gpt{idx}_ffn1_b"))
    if cfg.dropout:
        f = layers.dropout(f, cfg.dropout)
    return layers.elementwise_add(x, f)


def gpt_logits(tokens, cfg, seq_len, segment_ids=None, positions=None):
    """(B, T) int tokens -> (B, T, V) next-token logits (tied head).
    Packed mode (segment_ids + positions): causal attention additionally
    confined per document via the flash kernel's segment mask — the
    causal-pruning and segment-skip tile guards compose, so packed GPT
    skips both the upper triangle AND cross-document tiles."""
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=ParamAttr(name="gpt_word_emb"))
    if positions is not None:
        pos = layers.embedding(
            positions, size=[cfg.max_position, cfg.hidden_size],
            param_attr=ParamAttr(name="gpt_pos_emb"))
    else:
        pos_table = layers.create_parameter(
            [cfg.max_position, cfg.hidden_size], "float32",
            attr=ParamAttr(name="gpt_pos_emb"))
        pos = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq_len])
    x = layers.elementwise_add(emb, pos)
    if cfg.dropout:
        x = layers.dropout(x, cfg.dropout)
    for i in range(cfg.num_layers):
        x = _block(x, cfg, i, segment_ids=segment_ids)
    x = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name="gpt_lnf_s"),
                          bias_attr=ParamAttr(name="gpt_lnf_b"))
    word_emb = framework.default_main_program().global_block().var(
        "gpt_word_emb")
    return layers.matmul(x, word_emb, transpose_y=True)


def build_lm_net(cfg=None, seq_len=64):
    """Causal LM training graph. Feeds: tokens (B, T) int64.
    Returns (tokens_var, mean_loss, logits)."""
    cfg = cfg or GPTConfig()
    tokens = layers.data("tokens", shape=[seq_len], dtype="int64")
    logits = gpt_logits(tokens, cfg, seq_len)
    # next-token prediction: positions 0..T-2 predict tokens 1..T-1
    pred = layers.slice(logits, axes=[1], starts=[0], ends=[seq_len - 1])
    tgt = layers.slice(tokens, axes=[1], starts=[1], ends=[seq_len])
    pred2d = layers.reshape(pred, shape=[-1, cfg.vocab_size])
    tgt2d = layers.reshape(tgt, shape=[-1, 1])
    loss = layers.mean(layers.softmax_with_cross_entropy(pred2d, tgt2d))
    return tokens, loss, logits


def build_packed_lm_net(cfg=None, seq_len=64):
    """Packed causal LM: several documents share each row
    (reader.pack_sequences), attention is causal AND per-document, and
    the next-token loss only counts pairs inside one document — the
    cross-document boundary token and pad slots carry zero weight.
    Feeds: tokens, segment_ids, positions (B, T) int64.
    Returns (feeds dict, mean_loss). Loss normalization is by the real
    pair count, so the value is comparable to the unpacked net's."""
    cfg = cfg or GPTConfig()
    tokens = layers.data("tokens", shape=[seq_len], dtype="int64")
    segment_ids = layers.data("segment_ids", shape=[seq_len],
                              dtype="int64")
    positions = layers.data("positions", shape=[seq_len], dtype="int64")
    logits = gpt_logits(tokens, cfg, seq_len, segment_ids=segment_ids,
                        positions=positions)
    pred = layers.slice(logits, axes=[1], starts=[0], ends=[seq_len - 1])
    tgt = layers.slice(tokens, axes=[1], starts=[1], ends=[seq_len])
    seg_a = layers.slice(segment_ids, axes=[1], starts=[0],
                         ends=[seq_len - 1])
    seg_b = layers.slice(segment_ids, axes=[1], starts=[1], ends=[seq_len])
    # pair (t, t+1) counts iff both tokens are real and same-document
    w = layers.cast(layers.logical_and(
        layers.equal(seg_a, seg_b),
        layers.greater_than(seg_a, layers.zeros_like(seg_a))), "float32")
    pred2d = layers.reshape(pred, shape=[-1, cfg.vocab_size])
    tgt2d = layers.reshape(tgt, shape=[-1, 1])
    ce = layers.softmax_with_cross_entropy(pred2d, tgt2d)
    w2d = layers.reshape(w, shape=[-1, 1])
    loss = layers.elementwise_div(
        layers.reduce_sum(layers.elementwise_mul(ce, w2d)),
        layers.elementwise_add(
            layers.reduce_sum(w2d),
            layers.fill_constant([1], "float32", 1e-6)))
    return {"tokens": tokens, "segment_ids": segment_ids,
            "positions": positions}, loss


# ---------------------------------------------------------------------------
# KV-cache generation: the same math per token over scope params
# ---------------------------------------------------------------------------

def load_params(scope, cfg):
    """Pull the named parameters into a jax pytree for the cached step."""

    def get(name):
        v = scope.get(name)
        if v is None:
            raise KeyError(
                f"gpt.load_params: parameter {name!r} not in scope — run "
                f"the startup program (and train/load) with the same "
                f"gpt_* ParamAttr names before generating")
        return jnp.asarray(v)

    p = {"word_emb": get("gpt_word_emb"), "pos_emb": get("gpt_pos_emb"),
         "lnf_s": get("gpt_lnf_s"), "lnf_b": get("gpt_lnf_b")}
    for i in range(cfg.num_layers):
        p[f"l{i}"] = {
            "ln1_s": get(f"gpt{i}_ln1_s"), "ln1_b": get(f"gpt{i}_ln1_b"),
            "ln2_s": get(f"gpt{i}_ln2_s"), "ln2_b": get(f"gpt{i}_ln2_b"),
            "wq": get(f"gpt{i}_attn_q"), "wk": get(f"gpt{i}_attn_k"),
            "wv": get(f"gpt{i}_attn_v"), "wo": get(f"gpt{i}_attn_o"),
            "bq": get(f"gpt{i}_attn_q_b"), "bk": get(f"gpt{i}_attn_k_b"),
            "bv": get(f"gpt{i}_attn_v_b"), "bo": get(f"gpt{i}_attn_o_b"),
            "f0w": get(f"gpt{i}_ffn0_w"), "f0b": get(f"gpt{i}_ffn0_b"),
            "f1w": get(f"gpt{i}_ffn1_w"), "f1b": get(f"gpt{i}_ffn1_b"),
        }
    return p


def _ln(x, s, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def build_kv_step(params, cfg, max_len):
    """step_fn(ids_t (B,), cache, t) -> (logits (B, V), cache) for
    inference/decoding.greedy_decode / beam_decode. cache: per layer
    {"k","v"} of (B, H, max_len, D)."""
    from ..inference import decoding as dec
    h_, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads

    def step(ids_t, cache, t):
        b = ids_t.shape[0]
        x = params["word_emb"][ids_t] + params["pos_emb"][t]   # (B, M)
        bias = dec.cache_attention_bias(max_len, t)[0, 0]      # (1, L)
        for i in range(cfg.num_layers):
            lp = params[f"l{i}"]
            hn = _ln(x, lp["ln1_s"], lp["ln1_b"])
            q = (hn @ lp["wq"] + lp["bq"]).reshape(b, h_, 1, d)
            k = (hn @ lp["wk"] + lp["bk"]).reshape(b, h_, 1, d)
            v = (hn @ lp["wv"] + lp["bv"]).reshape(b, h_, 1, d)
            cache[i] = dec.update_kv_cache(cache[i], k, v, t)
            # scores + softmax deliberately in f32 (np scalar + f32 bias
            # promote); probs cast BACK to the cache dtype so a bf16
            # serving path keeps its activations/residual in bf16 —
            # without the cast, layer 0's f32 output silently promoted
            # every later layer to f32
            s = (jnp.einsum("bhd,bhld->bhl", q[:, :, 0], cache[i]["k"])
                 / np.sqrt(d)) + bias
            p = jax.nn.softmax(s, -1).astype(cache[i]["v"].dtype)
            o = jnp.einsum("bhl,bhld->bhd", p,
                           cache[i]["v"]).reshape(b, cfg.hidden_size)
            x = x + (o @ lp["wo"] + lp["bo"]).astype(x.dtype)
            hn = _ln(x, lp["ln2_s"], lp["ln2_b"])
            f = jax.nn.gelu(hn @ lp["f0w"] + lp["f0b"], approximate=False)
            x = x + (f @ lp["f1w"] + lp["f1b"])
        x = _ln(x, params["lnf_s"], params["lnf_b"])
        return x @ params["word_emb"].T, cache

    return step


def _cast_params(params, dtype):
    """Serving-dtype cast: f32 leaves -> dtype, everything else as-is
    (the shared policy of every decoder factory)."""
    if dtype is None:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a,
        params)


def gqa_slice_kv_params(params, cfg, kv_heads):
    """Derive a grouped-query-attention parameter tree from a trained
    MHA one: keep each query-head GROUP's first head's wk/wv columns
    (and bk/bv rows), shrinking both projections to kv_heads * head_dim
    outputs. Pair with ``GPTConfig(kv_heads=...)`` to serve the result.
    This is the cheap-ablation GQA conversion (mean-pooling the group
    is the published alternative) — tests use it because
    composing with `gqa_repeat_kv_params` is an EXACT round trip: the
    repeated tree projects bitwise-identical K/V to the sliced tree's
    shared heads, which is what makes a repeat-KV dense server the
    bitwise reference for a GQA paged server."""
    h = cfg.num_heads
    d = cfg.hidden_size // h
    if kv_heads < 1 or h % kv_heads:
        raise ValueError(
            f"kv_heads={kv_heads} must divide num_heads={h}")
    g = h // kv_heads

    def slc_w(w):
        return w.reshape(-1, kv_heads, g, d)[:, :, 0, :].reshape(
            w.shape[0], kv_heads * d)

    def slc_b(bvec):
        return bvec.reshape(kv_heads, g, d)[:, 0, :].reshape(
            kv_heads * d)

    out = dict(params)
    for i in range(cfg.num_layers):
        lp = dict(out[f"l{i}"])
        lp["wk"], lp["wv"] = slc_w(lp["wk"]), slc_w(lp["wv"])
        lp["bk"], lp["bv"] = slc_b(lp["bk"]), slc_b(lp["bv"])
        out[f"l{i}"] = lp
    return out


def gqa_repeat_kv_params(params, cfg, kv_heads):
    """Inverse of `gqa_slice_kv_params`: expand a GQA tree (wk/wv with
    kv_heads * head_dim outputs) back to full MHA width by repeating
    each KV head's column block across its query-head group. The
    expanded tree projects every query head's K/V bitwise-equal to its
    group's shared KV head, so a plain MHA server over this tree is the
    repeat-KV dense reference a GQA server must match id-for-id."""
    h = cfg.num_heads
    d = cfg.hidden_size // h
    if kv_heads < 1 or h % kv_heads:
        raise ValueError(
            f"kv_heads={kv_heads} must divide num_heads={h}")
    g = h // kv_heads

    def rep_w(w):
        return jnp.repeat(w.reshape(-1, kv_heads, d), g,
                          axis=1).reshape(w.shape[0], h * d)

    def rep_b(bvec):
        return jnp.repeat(bvec.reshape(kv_heads, d), g,
                          axis=0).reshape(h * d)

    out = dict(params)
    for i in range(cfg.num_layers):
        lp = dict(out[f"l{i}"])
        lp["wk"], lp["wv"] = rep_w(lp["wk"]), rep_w(lp["wv"])
        lp["bk"], lp["bv"] = rep_b(lp["bk"]), rep_b(lp["bv"])
        out[f"l{i}"] = lp
    return out


def _prefill_forward(lp_all, prompt_ids, cfg, max_len, h_count,
                     reduce_fn):
    """The ONE prefill body (math identical to build_kv_step's), shared
    by the single-chip and tensor-parallel prefills: `h_count` is the
    head count THIS caller computes (H, or H/tp inside shard_map) and
    `reduce_fn` finishes the row-parallel o-proj / ffn-down matmuls
    (identity single-chip; one psum per block pair under tp)."""
    from ..ops.pallas import flash

    d = cfg.hidden_size // cfg.num_heads
    b, p = prompt_ids.shape
    x = lp_all["word_emb"][prompt_ids] + lp_all["pos_emb"][:p][None]
    blk = min(128, p)
    cache = []
    for i in range(cfg.num_layers):
        lp = lp_all[f"l{i}"]
        hn = _ln(x, lp["ln1_s"], lp["ln1_b"])

        def heads(w, bias):
            return (hn @ w + bias).reshape(b, p, h_count, d).transpose(
                0, 2, 1, 3)

        q = heads(lp["wq"], lp["bq"])
        k = heads(lp["wk"], lp["bk"])
        v = heads(lp["wv"], lp["bv"])
        o = flash.flash_attention(q, k, v, causal=True,
                                  scale=1.0 / np.sqrt(d),
                                  block_q=blk, block_k=blk)
        o = o.transpose(0, 2, 1, 3).reshape(b, p, h_count * d)
        x = x + (reduce_fn(o @ lp["wo"]) + lp["bo"]).astype(x.dtype)
        hn = _ln(x, lp["ln2_s"], lp["ln2_b"])
        f = jax.nn.gelu(hn @ lp["f0w"] + lp["f0b"], approximate=False)
        x = x + (reduce_fn(f @ lp["f1w"]) + lp["f1b"])
        # park this layer's K/V at positions 0..P-1: zero-pad the time
        # axis out to the cache length
        pad = ((0, 0), (0, 0), (0, max_len - p), (0, 0))
        cache.append({"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)})
    x = _ln(x, lp_all["lnf_s"], lp_all["lnf_b"])
    return cache, x @ lp_all["word_emb"].T


def build_prefill(params, cfg, max_len):
    """prefill(prompt_ids (B, P)) -> (cache, logits (B, P, V)):
    process the WHOLE prompt in one parallel forward (the flash kernel
    over (B, H, P, D) — MXU-shaped work) and write K/V for positions
    0..P-1 into a max_len cache. The serving complement of
    build_kv_step: a P-token prompt costs ONE forward instead of P
    sequential cache steps; inference/decoding.greedy_decode then
    continues from start_t=P. Math identical to build_kv_step's
    (tests/models/test_gpt_prefill.py pins cache and logits)."""

    def prefill(prompt_ids):
        return _prefill_forward(params, prompt_ids, cfg, max_len,
                                cfg.num_heads, lambda z: z)

    return prefill


def make_prompt_decoder(params, cfg, prompt_len, max_len, eos_id=None,
                        dtype=None, beam_size=None, length_penalty=0.6):
    """Jit-compiled prompt-conditioned decoder (compile ONCE, serve
    many requests of the same (B, P) shape): parallel prefill of the
    prompt (ONE flash forward), then KV-cache continuation — greedy by
    default, beam search with `beam_size`.

    decode(prompt_ids (B, P)) -> greedy: (gen_ids (B, max_len - P),
    scores (B,)) — scores sum the generated tokens' log-probs, matching
    a token-by-token teacher-forced rollout exactly; beam:
    (ids (B, K, max_len - P), scores (B, K)) best-first, via the
    start_t = P - 1 trick (see beam_decode)."""
    from ..inference import decoding as dec

    p = int(prompt_len)
    gen = max_len - p
    if gen <= 0:
        raise ValueError(f"max_len={max_len} must exceed the prompt "
                         f"length {p}")
    params = _cast_params(params, dtype)
    prefill = build_prefill(params, cfg, max_len)
    step = build_kv_step(params, cfg, max_len)

    return jax.jit(_prompt_continuation(prefill, step, p, gen, eos_id,
                                        beam_size, length_penalty))


def _select_first(logits_last, temperature, top_k, top_p, key):
    """First generated token from the prefill's last-position logits:
    argmax when temperature is None/<=0, else filtered categorical.
    Returns (first, score0, key) — ONE implementation for the greedy
    and sampled prompt paths."""
    from ..inference import decoding as dec

    logits = logits_last.astype(jnp.float32)
    if temperature is None or temperature <= 0.0:
        filtered = logits
        first = jnp.argmax(filtered, axis=-1)
    else:
        filtered = dec._filter_logits(logits / temperature, top_k=top_k,
                                      top_p=top_p)
        key, sub = jax.random.split(key)
        first = jax.random.categorical(sub, filtered, axis=-1)
    logp = jax.nn.log_softmax(filtered)
    score0 = jnp.take_along_axis(logp, first[:, None], -1)[:, 0]
    return first, score0, key


def _stitch_prompt_output(first, score0, ids, scores, gen, eos_id):
    """Prepend the first token and apply the first-step-EOS patch —
    the drift-prone tail every prompt decoder must share."""
    out = jnp.concatenate([first[:, None], ids], axis=1)
    if eos_id is not None:
        done0 = first == eos_id
        # tokens after a first-step EOS must read as EOS too
        out = jnp.where(jnp.logical_and(done0[:, None],
                                        jnp.arange(gen)[None] > 0),
                        eos_id, out)
        scores = jnp.where(done0, 0.0, scores)
    return out, score0 + scores


def _prompt_continuation(prefill, step, p, gen, eos_id, beam_size,
                         length_penalty):
    """Shared continuation over any prefill(prompt) -> (cache, logits)
    — single-chip and tp prompt decoders run EXACTLY this logic (drift
    here would break their pinned equivalence)."""
    from ..inference import decoding as dec

    if beam_size is not None:
        K = beam_size

        def decode(prompt_ids):
            cache, _logits = prefill(prompt_ids)
            cache = jax.tree_util.tree_map(
                lambda x: jnp.repeat(x, K, 0), cache)
            # feed the last prompt token at start_t = P-1: the step
            # re-writes that position's K/V (identical values) and the
            # scan emits gen tokens starting at position P
            return dec.beam_decode(
                step, cache, prompt_ids[:, -1], gen, K,
                eos_id if eos_id is not None else -1,
                length_penalty=length_penalty, start_t=p - 1)

        return decode

    def decode(prompt_ids):
        cache, logits = prefill(prompt_ids)
        first, score0, _ = _select_first(logits[:, -1], None, None,
                                         None, None)
        ids, scores = dec.greedy_decode(step, cache, first, gen - 1,
                                        eos_id=eos_id, start_t=p)
        return _stitch_prompt_output(first, score0, ids, scores, gen,
                                     eos_id)

    return decode


def generate_with_prompt(params, cfg, prompt_ids, max_len, eos_id=None,
                         dtype=None, beam_size=None, length_penalty=0.6):
    """One-shot convenience over make_prompt_decoder (which serving
    loops should hold onto — it compiles once per (B, P) shape)."""
    prompt_ids = jnp.asarray(prompt_ids)
    decode = make_prompt_decoder(
        params, cfg, prompt_ids.shape[1], max_len, eos_id=eos_id,
        dtype=dtype, beam_size=beam_size, length_penalty=length_penalty)
    return decode(prompt_ids)


def make_greedy_decoder(params, cfg, max_len, eos_id=None, dtype=None):
    """Jit-compiled greedy KV-cache decoder: decode(bos_ids (B,)) ->
    (ids (B, max_len), scores (B,)). `dtype` casts f32 params AND the
    cache for serving (bf16 halves the bandwidth decode is bound by);
    scores/softmax stay f32 inside (build_kv_step). The single wiring
    point for cache-init + greedy_decode — generate() rides it."""
    import jax
    from ..inference import decoding as dec
    params = _cast_params(params, dtype)
    step = build_kv_step(params, cfg, max_len)
    d = cfg.hidden_size // cfg.num_heads

    @jax.jit
    def decode(bos_ids):
        cache = dec.init_kv_cache(bos_ids.shape[0], cfg.num_layers,
                                  cfg.num_heads, max_len, d,
                                  dtype=dtype or jnp.float32)
        return dec.greedy_decode(step, cache, bos_ids, max_len,
                                 eos_id=eos_id)

    return decode


def make_sampler(params, cfg, max_len, temperature=1.0, top_k=None,
                 top_p=None, eos_id=None, dtype=None, prompt_len=None):
    """Jit-compiled stochastic decoder (temperature / top-k / nucleus;
    inference/decoding.sample_decode). Without prompt_len:
    sample(bos_ids (B,), rng_key) -> (ids (B, max_len), scores). With
    prompt_len: parallel prefill first, then sampled continuation —
    sample(prompt_ids (B, P), rng_key) -> (ids (B, max_len - P),
    scores); the first generated token is sampled from the prefill's
    last-position logits."""
    from ..inference import decoding as dec

    params = _cast_params(params, dtype)
    step = build_kv_step(params, cfg, max_len)
    d = cfg.hidden_size // cfg.num_heads

    if prompt_len is None:
        @jax.jit
        def sample(bos_ids, rng_key):
            cache = dec.init_kv_cache(bos_ids.shape[0], cfg.num_layers,
                                      cfg.num_heads, max_len, d,
                                      dtype=dtype or jnp.float32)
            return dec.sample_decode(step, cache, bos_ids, max_len,
                                     rng_key, temperature=temperature,
                                     top_k=top_k, top_p=top_p,
                                     eos_id=eos_id)

        return sample

    p = int(prompt_len)
    gen = max_len - p
    if gen <= 0:
        raise ValueError(f"max_len={max_len} must exceed the prompt "
                         f"length {p}")
    prefill = build_prefill(params, cfg, max_len)

    @jax.jit
    def sample(prompt_ids, rng_key):
        cache, logits = prefill(prompt_ids)
        first, score0, rng_key = _select_first(
            logits[:, -1], temperature, top_k, top_p, rng_key)
        ids, scores = dec.sample_decode(
            step, cache, first, gen - 1, rng_key,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, start_t=p)
        return _stitch_prompt_output(first, score0, ids, scores, gen,
                                     eos_id)

    return sample


def gpt_tp_shardings(cfg, mesh, axis="tp"):
    """NamedSharding pytree for a load_params() tree on a tp mesh: the
    Megatron serving layout — attention heads (qkv output columns / o
    rows) and the ffn hidden dim shard over `axis`; embeddings, layer
    norms and the small biases replicate. Under jit, GSPMD propagates
    these through the decode step and inserts exactly one all-reduce
    per block pair (o-proj + ffn-down), riding ICI on real pods."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    rep, col, row = ns(), ns(None, axis), ns(axis)
    tree = {"word_emb": rep, "pos_emb": rep, "lnf_s": rep, "lnf_b": rep}
    for i in range(cfg.num_layers):
        tree[f"l{i}"] = {
            "ln1_s": rep, "ln1_b": rep, "ln2_s": rep, "ln2_b": rep,
            # qkv: (M, M) output columns are head-major -> shard cols
            "wq": col, "wk": col, "wv": col, "bq": row, "bk": row,
            "bv": row,
            # o: (M, M) input rows are head-major -> shard rows; the
            # contraction leaves partial sums GSPMD all-reduces
            "wo": row, "bo": rep,
            "f0w": col, "f0b": row, "f1w": row, "f1b": rep,
        }
    return tree


def make_tp_decoder(params, cfg, mesh, max_len, eos_id=None, dtype=None,
                    axis="tp", beam_size=None, length_penalty=0.6,
                    dp_axis=None):
    """Tensor-parallel KV-cache decoder (greedy, or beam search with
    `beam_size`): same contracts as make_greedy_decoder / beam_decode
    but sharded over the mesh's `axis` — params in the Megatron layout
    (gpt_tp_shardings), the KV cache sharded over HEADS, so per-chip
    cache bandwidth (the decode bottleneck) drops by the tp degree.
    With `dp_axis` the BATCH additionally shards over that mesh axis
    (cache rows and inputs split; outputs gathered back replicated) —
    the dp x tp throughput-serving layout. Outputs are checked against
    the single-chip decoders in tests/parallel/test_tp_decode.py.

    The tp degree must divide cfg.num_heads and the ffn inner dim; the
    dp degree must divide the batch itself (bos_ids rides P(dp_axis))."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = mesh.shape[axis]
    d = cfg.hidden_size // cfg.num_heads
    if cfg.num_heads % tp or cfg.inner_size % tp:
        raise ValueError(
            f"tp={tp} must divide both num_heads={cfg.num_heads} and "
            f"inner_size={cfg.inner_size}")
    params = _cast_params(params, dtype)
    params = jax.device_put(params, gpt_tp_shardings(cfg, mesh, axis))
    step = build_kv_step(params, cfg, max_len)
    cache_ns = NamedSharding(mesh, P(dp_axis, axis, None, None))

    from ..inference import decoding as dec

    def _sharded_cache(rows):
        cache = dec.init_kv_cache(rows, cfg.num_layers, cfg.num_heads,
                                  max_len, d, dtype=dtype or jnp.float32)
        # pin the (batch-, )head-sharded cache layout; everything else
        # propagates
        return jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(a, cache_ns),
            cache)

    # dp|batch is validated by pjit itself before tracing: a non-divisible
    # batch raises "size of its dimension 0 should be divisible by <dp>"
    # naming the bos_ids argument (asserted in test_tp_validates_divisibility)
    def decode(bos_ids):
        if beam_size is None:
            return dec.greedy_decode(step, _sharded_cache(
                bos_ids.shape[0]), bos_ids, max_len, eos_id=eos_id)
        # beam lanes ride the batch dim: (B*K) rows
        return dec.beam_decode(
            step, _sharded_cache(bos_ids.shape[0] * beam_size), bos_ids,
            max_len, beam_size,
            eos_id if eos_id is not None else -1,
            length_penalty=length_penalty)

    rep = NamedSharding(mesh, P())
    in_ns = rep if dp_axis is None else NamedSharding(mesh, P(dp_axis))
    return jax.jit(decode, in_shardings=in_ns, out_shardings=(rep, rep))


def make_tp_greedy_decoder(params, cfg, mesh, max_len, eos_id=None,
                           dtype=None, axis="tp"):
    """Greedy-only alias of make_tp_decoder (the benched serving path)."""
    return make_tp_decoder(params, cfg, mesh, max_len, eos_id=eos_id,
                           dtype=dtype, axis=axis)


def build_tp_prefill(params, cfg, mesh, max_len, axis="tp"):
    """Tensor-parallel prompt prefill under shard_map: every chip runs
    the flash kernel on ITS heads (attention is head-independent — the
    same pattern ring attention uses for the sp axis) with exactly one
    psum per block pair (o-proj + ffn-down), and keeps only its cache
    shard. `params` must already be laid out per gpt_tp_shardings and
    is closed over here (one binding site). Returns
    prefill(prompt_ids (B, P)) -> (head-sharded cache, replicated
    logits (B, P, V)) — the SAME body as build_prefill
    (_prefill_forward) with local head count + psum reduction."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[axis]
    h_loc = cfg.num_heads // tp

    def local(lp_all, prompt_ids):
        return _prefill_forward(lp_all, prompt_ids, cfg, max_len, h_loc,
                                lambda z: jax.lax.psum(z, axis))

    param_specs = jax.tree_util.tree_map(
        lambda ns: ns.spec, gpt_tp_shardings(cfg, mesh, axis))
    cache_specs = [{"k": P(None, axis, None, None),
                    "v": P(None, axis, None, None)}
                   for _ in range(cfg.num_layers)]
    fn = shard_map(local, mesh=mesh, in_specs=(param_specs, P()),
                   out_specs=(cache_specs, P()), check_vma=False)
    return lambda prompt_ids: fn(params, prompt_ids)


def make_tp_prompt_decoder(params, cfg, mesh, prompt_len, max_len,
                           eos_id=None, dtype=None, axis="tp",
                           beam_size=None, length_penalty=0.6):
    """Tensor-parallel prompt serving end-to-end: shard_map prefill
    (build_tp_prefill) fills the head-sharded cache in one parallel
    forward, then the GSPMD continuation decodes greedily (or with beam
    search). Same contracts as make_prompt_decoder; outputs pinned
    against it in tests/parallel/test_tp_decode.py. Batch is
    replicated here — compose dp via make_tp_decoder's layout if
    sharded-batch prompt serving is needed."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..inference import decoding as dec

    tp = mesh.shape[axis]
    if cfg.num_heads % tp or cfg.inner_size % tp:
        raise ValueError(
            f"tp={tp} must divide both num_heads={cfg.num_heads} and "
            f"inner_size={cfg.inner_size}")
    p = int(prompt_len)
    gen = max_len - p
    if gen <= 0:
        raise ValueError(f"max_len={max_len} must exceed the prompt "
                         f"length {p}")
    params = _cast_params(params, dtype)
    params = jax.device_put(params, gpt_tp_shardings(cfg, mesh, axis))
    prefill = build_tp_prefill(params, cfg, mesh, max_len, axis)
    step = build_kv_step(params, cfg, max_len)
    # the SAME continuation the single-chip factory compiles — only the
    # prefill (shard_map) and the io shardings differ
    decode = _prompt_continuation(prefill, step, p, gen, eos_id,
                                  beam_size, length_penalty)
    rep = NamedSharding(mesh, P())
    return jax.jit(decode, in_shardings=rep, out_shardings=(rep, rep))


def generate(scope, cfg, bos_ids=None, max_len=None, eos_id=None,
             beam_size=None, length_penalty=0.6, prompt_ids=None):
    """KV-cache generation from trained scope params: greedy by default,
    beam search (dense lanes, GNMT length penalty) with beam_size.
    `prompt_ids` (B, P) conditions on a whole prompt via the parallel
    prefill (greedy or beam); `bos_ids` (B,) starts from single
    tokens."""
    from ..inference import decoding as dec
    if bos_ids is None and prompt_ids is None:
        raise ValueError("generate() needs bos_ids (B,) or "
                         "prompt_ids (B, P)")
    if max_len is None:
        raise ValueError("generate() needs max_len (total sequence "
                         "positions, prompt included)")
    params = load_params(scope, cfg)
    if prompt_ids is not None:
        return generate_with_prompt(params, cfg, prompt_ids, max_len,
                                    eos_id=eos_id, beam_size=beam_size,
                                    length_penalty=length_penalty)
    d = cfg.hidden_size // cfg.num_heads
    b = len(np.asarray(bos_ids))
    if beam_size is None:
        decode = make_greedy_decoder(params, cfg, max_len, eos_id=eos_id)
        return decode(jnp.asarray(bos_ids))
    step = build_kv_step(params, cfg, max_len)
    cache = dec.init_kv_cache(b * beam_size, cfg.num_layers,
                              cfg.num_heads, max_len, d)
    return dec.beam_decode(step, cache, jnp.asarray(bos_ids), max_len,
                           beam_size, eos_id if eos_id is not None else -1,
                           length_penalty=length_penalty)
