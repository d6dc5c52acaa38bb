"""GenerationServer: continuous-batching generation behind the
BatchingServer submit/Future surface.

The whole serve loop is ONE jitted fused prefill/decode step:

    fused(pools, tokens (S, C), positions (S, C), valid (S, C),
          tables (S, M)) -> (pools, next_ids, next_logps[, fed_logps])

S decode slots x C chunk columns, shapes fixed for the server lifetime
— a prefilling lane feeds up to C prompt tokens per iteration, a
decoding lane feeds its one in-flight token (or, in speculative mode,
its token plus up to k draft proposals to verify in the same
prefill-shaped call), an idle lane is masked. Plain serving projects
each lane's LAST valid column only ((S,) outputs); a speculative
server's step projects every column ((S, C) outputs plus fed-token
logps) so acceptance can compare the target's choice at each draft
position. Requests of any length mix freely in one executable; after
warmup the jit cache holds exactly one fused signature (asserted via
get_stats()), plus at most one draft-step signature when speculative
decoding is on (spec_decode.py) — the whole server lifetime compiles
at most two step functions.

The model side is pluggable; GPTServingModel adapts models/gpt.py
params (same math as gpt.build_kv_step, vectorized over the chunk
axis, KV routed through serving.kv_cache.paged_attention/write).
"""

import itertools
import math
import threading
import time
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from ..models.gpt import _cast_params, load_params
from ..observability import _help
from ..observability.metrics import global_registry
from ..observability.tracing import get_recorder
from . import kv_cache as _kvc
from .blocks import (ATTENTIONS, MLPS, NORMS, STATE_ATTENTIONS, LayerSpec,
                     StepContext, StepSpec, fold_counts, rotary_angles,
                     state_counts)
from .decode_strategies import (GroupFuture, RequestGroup,
                                SamplingParams, gumbel_noise)
from .kv_cache import NEG_INF, NULL_BLOCK, PagedKVCache
from .scheduler import ContinuousBatchingScheduler, RequestCancelled, _Request

__all__ = ["GenerationServer", "GenerationFuture", "GPTServingModel"]

# HBM-ledger component ids ("serving0", ...): monotonic, never recycled
_SERVER_SEQ = itertools.count()


def _sample_rows(base, rng, temperature, do_top_k, top_p):
    """Stochastic token choice over (S, V) log-prob rows INSIDE the one
    fused step: temperature scale, top-k / nucleus filtering
    (inference.decoding._filter_logits semantics), Gumbel-argmax draw
    from per-lane counter keys. Every control is DATA — (S,) arrays, 0
    meaning top-k off and 2.0 meaning top-p off — so sampled, greedy,
    and mixed batches all share one jit signature. Returns
    (sampled ids (S,), their logp under the filtered distribution)."""
    s, v = base.shape
    scaled = base / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    k_eff = jnp.clip(jnp.where(do_top_k > 0, do_top_k, v), 1, v)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], -1)
    filt = jnp.where(scaled < kth, jnp.float32(NEG_INF), scaled)
    # nucleus over the top-k survivors (softmax subtracts the row max,
    # so the NEG_INF entries contribute exp(-huge) = 0, never NaN)
    sd = -jnp.sort(-filt, axis=-1)
    cum = jnp.cumsum(jax.nn.softmax(sd, axis=-1), axis=-1)
    keep = jnp.concatenate([jnp.ones((s, 1), bool),
                            cum[:, :-1] < top_p[:, None]], axis=-1)
    thresh = jnp.min(jnp.where(keep, sd, jnp.inf), axis=-1,
                     keepdims=True)
    filt = jnp.where(filt < thresh, jnp.float32(NEG_INF), filt)
    samp = jnp.argmax(filt + gumbel_noise(rng, v, xp=jnp), axis=-1)
    samp_lp = jnp.take_along_axis(
        jax.nn.log_softmax(filt, axis=-1), samp[:, None], -1)[:, 0]
    return samp.astype(jnp.int32), samp_lp


def _sampled_where_asked(logp, nxt, chosen, rng, temperature, do_sample,
                         top_k, top_p):
    """The step's (ids, their logp) from the greedy `(nxt, chosen)`: a
    lane whose `do_sample` is set takes `_sample_rows`' draw over its
    `logp` row instead. The draw (two sorts of the whole vocabulary, a
    nucleus sum, the noise) runs under ONE device-side branch on
    `do_sample.any()`, so a step whose lanes are all greedy pays for
    none of it; the predicate is data the step already takes, so there
    is still one executable. Over per-column `(S, C, ...)` inputs the
    draw is column 0's. Everything a branch reads is an operand."""
    per_column = logp.ndim == 3

    def sampled(logp, nxt, chosen, rng, temperature, do_sample, top_k,
                top_p):
        samp, samp_lp = _sample_rows(logp[:, 0] if per_column else logp,
                                     rng, temperature, top_k, top_p)
        if per_column:
            nxt = nxt.at[:, 0].set(jnp.where(do_sample, samp, nxt[:, 0]))
            chosen = chosen.at[:, 0].set(
                jnp.where(do_sample, samp_lp, chosen[:, 0]))
        else:
            nxt = jnp.where(do_sample, samp, nxt)
            chosen = jnp.where(do_sample, samp_lp, chosen)
        return nxt, chosen

    def greedy(logp, nxt, chosen, *controls):
        return nxt, chosen

    return jax.lax.cond(jnp.any(do_sample), sampled, greedy, logp,
                        nxt.astype(jnp.int32), chosen, rng, temperature,
                        do_sample, top_k, top_p)


def _fused_step_body(params, spec, block_size, reduce_fn, pools, tokens,
                     positions, valid, tables, per_column=False,
                     sampling=False, mask=None, rng=None,
                     temperature=None, do_sample=None, top_k=None,
                     top_p=None, in_shard_map=False):
    """The ONE fused prefill/decode step body, over (S, C) ragged lanes
    with paged KV, for every model family: `spec` (`blocks.StepSpec`)
    says, layer by layer, which norm, which attention over which cache
    geometry and which MLP a block is made of, how positions enter and
    whether the head is tied; `blocks.py` holds each kind's
    arithmetic. The skeleton around the layers is the same for all:
    embedding, write targets (masked lanes to the NULL block), the
    layer loop with its two residual adds, the final norm, the
    last-column gather, log-softmax in float32, and the greedy and
    sampled tails.

    It is shared by the single-device and tensor-parallel fused steps
    exactly like gpt._prefill_forward: `spec.heads` is the QUERY head
    count THIS caller sees (H, or H/tp inside shard_map over
    head-sharded params and pools), `spec.kv_heads` the KV head count
    (equal for MHA; H_kv or H_kv/tp for grouped-query attention, where
    wk/wv project to kv_heads * head_dim columns and the
    paged_attention dispatcher groups the query heads onto the shared
    KV heads), and `reduce_fn`
    finishes the row-parallel o-proj / ffn-down contractions (identity
    single-device; one psum per sub-block under tp — the partial sums
    those matmuls leave are the ONLY cross-shard state the step has);
    `in_shard_map` is that same fact handed on to the paged_attention
    dispatcher, which cannot see it from inside the trace.

    `per_column=False` (plain serving): each lane's LAST valid column
    is gathered before the lm-head projection — one (S, H) @ (H, V)
    gemm, returns (pools, next_ids (S,), next_logps (S,)).
    `per_column=True` (speculative verify): every column is projected —
    (S*C, H) @ (H, V) — and a third `fed_logps` output carries the
    target logp of each NEXT fed column's token (the draft under
    verification; rejection-mode acceptance needs p_target(draft)).
    Rows of the wide gemm are independent dot products, so a column's
    outputs are bitwise the last-column gather's (the spec parity tests
    pin this); plain servers keep the narrow gemm — C x fewer lm-head
    FLOPs on the decode hot path.

    A spec with layers that count (an MLP kind that returns counts
    beside its addend: the expert layer its routing) returns ONE more
    output, last: the step's counts, an int32 vector folded over those
    layers (`blocks.fold_counts`), which the model names
    (`step_counters`). A spec with state layers (`STATE_ATTENTIONS`)
    appends theirs (`blocks.state_counts`) to that vector.

    Quantized serving (ISSUE 14) rides the same body: a layer dict
    carrying "k_scale"/"v_scale" pools takes the quantize-at-write path
    and hands the scales to the paged_attention dispatcher (which fuses
    the dequant into the Pallas kernel's gather); a layer dict carrying
    "<w>@q8"/"<w>@scale" weight entries (GPTServingModel.quantize_int8)
    gets its matmul weight dequantized INLINE — int8 codes times the
    per-output-channel f32 scale, cast to the activation dtype — so the
    step reads half the weight bytes from HBM and the jit signature
    budget is untouched (the dequant is part of the one compiled
    step, not a second executable)."""
    s, c = tokens.shape
    wdt = params["word_emb"].dtype     # activation/compute dtype

    def w(container, name):
        # int8 weight entry -> inline dequant; plain entry -> as-is
        q8 = container.get(name + "@q8")
        if q8 is None:
            return container[name]
        return (q8.astype(jnp.float32)
                * container[name + "@scale"]).astype(wdt)

    pos = jnp.where(valid, positions, 0)
    x = params["word_emb"][tokens]
    angles = None
    if spec.positions == "learned":
        x = x + params["pos_emb"][pos]
    elif spec.positions == "rotary":
        angles = rotary_angles(pos, spec.dims["qk_rope"],
                               spec.dims["rope_theta"])
    # write targets: masked lanes route to the NULL block
    bidx = jnp.take_along_axis(tables, pos // block_size, axis=1)
    bidx = jnp.where(valid, bidx, NULL_BLOCK)
    off = jnp.where(valid, pos % block_size, 0)
    # what a state layer reads: a lane's valid columns are a prefix, and
    # a lane whose first one holds position 0 starts a request
    lane_cols = jnp.sum(valid, axis=1, dtype=jnp.int32)
    starts = valid[:, 0] & (positions[:, 0] == 0)
    ctx = StepContext(spec, s, c, x.dtype, pos, bidx, off, valid, tables,
                      reduce_fn, in_shard_map, w, angles, lane_cols,
                      starts)
    new_pools, counts = [], []
    for i, layer in enumerate(spec.layers):
        lp = params[f"l{i}"]
        norm = NORMS[layer.norm]
        a, layer_pools = ATTENTIONS[layer.attention](
            ctx, norm(spec, x, lp, "ln1"), lp, pools[i])
        x = x + a
        f, stats = MLPS[layer.mlp](ctx, norm(spec, x, lp, "ln2"), lp)
        x = x + f
        new_pools.append(layer_pools)
        if stats is not None:
            counts.append(stats)
    x = NORMS[spec.layers[-1].norm](spec, x, params, "lnf")
    head = params["word_emb"].T if spec.tied_head else params["head"]
    out = _step_tail(x, head, tokens, valid, new_pools, per_column,
                     sampling, mask, rng, temperature, do_sample, top_k,
                     top_p)
    state_layers = sum(layer.attention in STATE_ATTENTIONS
                       for layer in spec.layers)
    vectors = ([fold_counts(counts)] if counts else []) + (
        [state_counts(ctx, state_layers)] if state_layers else [])
    if vectors:
        out += (jnp.concatenate(vectors),)
    return out


def _step_tail(x, head, tokens, valid, new_pools, per_column, sampling,
               mask, rng, temperature, do_sample, top_k, top_p):
    """From the final-normed residual to the step's outputs: the head
    over each lane's last valid column (or every column), log-softmax
    in float32, the greedy choice and, where compiled in, the sampled
    one."""
    s, c = tokens.shape
    if not per_column:
        # next token comes from each lane's LAST valid column only
        last = jnp.clip(valid.sum(1) - 1, 0, c - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = xl @ head
        logitsf = logits.astype(jnp.float32)
        if sampling:
            # guided-decoding constraint mask (S, V): additive 0 /
            # NEG_INF rows, all-zero for unconstrained lanes — data,
            # never shape, so the one-signature invariant holds
            logitsf = logitsf + mask
        logp = jax.nn.log_softmax(logitsf)
        nxt = jnp.argmax(logp, axis=-1)
        chosen = jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]
        if not sampling:
            return new_pools, nxt.astype(jnp.int32), chosen
        nxt, chosen = _sampled_where_asked(
            logp, nxt, chosen, rng, temperature, do_sample, top_k, top_p)
        # 4th output: the full logp rows — fork-time host sampling and
        # beam re-ranking read these (the host transfer is paid only
        # when the plan says a group needs them)
        return new_pools, nxt, chosen, logp
    logits = (x.reshape(s * c, -1) @ head).reshape(
        s, c, head.shape[1])
    logitsf = logits.astype(jnp.float32)
    if sampling:
        logitsf = logitsf + mask        # (S, C, V) per-column masks
    logp = jax.nn.log_softmax(logitsf)
    nxt = jnp.argmax(logp, axis=-1)                         # (S, C)
    chosen = jnp.take_along_axis(logp, nxt[..., None], -1)[..., 0]
    # target logp of the NEXT FED column's token — the draft under
    # verification at this column; rejection-sampled acceptance needs
    # p_target(draft). The last column's value wraps and is meaningless.
    nt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    fed = jnp.take_along_axis(logp, nt[..., None], -1)[..., 0]
    if not sampling:
        return new_pools, nxt.astype(jnp.int32), chosen, fed
    # sampled lanes run 1-column (the scheduler plans no drafts for
    # them), so the stochastic draw applies to column 0 only
    nxt, chosen = _sampled_where_asked(
        logp, nxt, chosen, rng, temperature, do_sample, top_k, top_p)
    return new_pools, nxt, chosen, fed, logp


def single_device_step(params, spec, block_size, per_column=False,
                       sampling=False):
    """The fused step of one device over `params` and `spec`, as
    `GenerationServer` jits it: (pools, tokens, positions, valid,
    tables[, mask, rng, temperature, do_sample, top_k, top_p]) ->
    `_fused_step_body`'s outputs. What every family's
    `build_fused_step` returns without a mesh."""
    controls = ("mask", "rng", "temperature", "do_sample", "top_k",
                "top_p") if sampling else ()

    def fused(pools, tokens, positions, valid, tables, *ctl):
        return _fused_step_body(
            params, spec, block_size, lambda z: z, pools, tokens,
            positions, valid, tables, per_column=per_column,
            sampling=sampling, **dict(zip(controls, ctl, strict=True)))

    return fused


class GPTServingModel:
    """models/gpt.py parameters behind the engine's model interface:
    config facts + `build_fused_step(block_size, mesh=None)`. The step
    math is build_kv_step's, re-expressed over (S, C) ragged lanes with
    paged KV — tests pin the two token-for-token. With a mesh the SAME
    body runs under shard_map: params in the Megatron serving layout
    (gpt.gpt_tp_shardings), pools head-sharded, one psum per sub-block
    (attention o-proj + ffn down-projection)."""

    def __init__(self, params, cfg, dtype=None):
        self.params = _cast_params(params, dtype)
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        # GQA: cfg.kv_heads < num_heads shares each KV head across a
        # group of query heads; None/absent means MHA (H_kv == H)
        self.num_kv_heads = getattr(cfg, "kv_heads", None) or cfg.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}: grouped-query attention "
                f"needs an integral query-head group per KV head")
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.max_position = cfg.max_position
        self.kv_dtype = dtype or jnp.float32
        self._int8_weights = 0

    @classmethod
    def from_scope(cls, scope, cfg, dtype=None):
        return cls(load_params(scope, cfg), cfg, dtype=dtype)

    # int8 weight entries a quantize_int8'd layer dict carries in place
    # of each matmul weight (the fused step dequantizes inline)
    INT8_WEIGHT_NAMES = ("wq", "wk", "wv", "wo", "f0w", "f1w")

    def quantize_int8(self):
        """Per-output-channel absmax int8 quantization of every layer's
        matmul weights (the AnalysisConfig.enable_int8 weight side):
        each (in, out) weight w becomes w@q8 int8 codes + w@scale f32
        (1, out) — absmax over the input axis, the reference PTQ
        convention for mul/matmul Y operands (quant/ptq.py). The fused
        step dequantizes inline (codes * scale -> activation dtype), so
        HBM reads halve for these weights and the one-signature-per-
        lifetime budget is untouched. Embeddings, biases and layernorms
        stay float: the word embedding doubles as the lm head (rounding
        it distorts every logit for <2% of the byte win), the rest are
        O(hidden) vectors. Idempotent; returns self."""
        if self._int8_weights:
            return self
        from ..observability import _help
        from ..observability.metrics import global_registry
        # rebind a fresh top-level dict BEFORE rewriting layers: the
        # constructor may hold the caller's own params dict (dtype=None
        # skips the cast-copy), and quantization must never mutate a
        # tree the caller still serves dense elsewhere
        self.params = dict(self.params)
        n = 0
        for i in range(self.cfg.num_layers):
            lp = dict(self.params[f"l{i}"])
            for name in self.INT8_WEIGHT_NAMES:
                wf = lp.pop(name).astype(jnp.float32)
                absmax = jnp.max(jnp.abs(wf), axis=0, keepdims=True)
                scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
                lp[name + "@q8"] = jnp.clip(
                    jnp.round(wf / scale), -127, 127).astype(jnp.int8)
                lp[name + "@scale"] = scale          # (1, out) f32
                n += 1
            self.params[f"l{i}"] = lp
        self._int8_weights = n
        global_registry().counter(
            "inference.int8.weights",
            _help("inference.int8.weights")).inc(n)
        return self

    @property
    def int8_weights(self):
        """Quantized weight-tensor count (0 = dense weights)."""
        return self._int8_weights

    def step_spec(self, heads=None, kv_heads=None):
        """This family's block as the fused step reads it
        (`blocks.StepSpec`): LayerNorm with bias, learned positions,
        multi-head attention with biased projections over a K-beside-V
        pool, erf-GELU MLP, head tied to the embedding. `heads` /
        `kv_heads` override the counts for a caller inside a
        shard_map."""
        return StepSpec(
            layers=(LayerSpec("layer_norm", "mha", "gelu"),
                    ) * self.num_layers,
            positions="learned", tied_head=True,
            heads=heads or self.num_heads,
            kv_heads=kv_heads or self.num_kv_heads,
            head_dim=self.head_dim, norm_eps=None, dims=None)

    def build_fused_step(self, block_size, mesh=None, axis="tp",
                         per_column=False, kv_quantized=False,
                         sampling=False):
        params, cfg = self.params, self.cfg

        if mesh is not None and self._int8_weights:
            raise NotImplementedError(
                "int8 weights under a mesh are not supported yet — the "
                "tp shard rules name the dense weight keys; run int8-"
                "weight servers single-device (int8 KV pools DO shard; "
                "docs/serving.md)")
        if mesh is not None and sampling:
            raise NotImplementedError(
                "the sampling/guided step under a mesh is not "
                "supported yet — run fork-group servers single-device "
                "(replicating the mask/rng feeds through shard_map is "
                "follow-up work, docs/serving.md)")
        if mesh is None:
            return single_device_step(params, self.step_spec(),
                                      block_size, per_column, sampling)
        if per_column:
            raise NotImplementedError(
                "per-column outputs (speculative verify) are not "
                "supported under a mesh yet")

        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..models.gpt import gpt_tp_shardings

        tp = mesh.shape[axis]
        if self.num_heads % tp or cfg.inner_size % tp:
            raise ValueError(
                f"tp={tp} must divide both num_heads={self.num_heads} "
                f"and inner_size={cfg.inner_size}")
        if self.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide kv_heads={self.num_kv_heads}: "
                f"the KV pools (and wk/wv columns) shard on the KV "
                f"head axis, so each device needs a whole number of "
                f"KV-head groups")
        h_loc = self.num_heads // tp
        kv_loc = self.num_kv_heads // tp
        shardings = gpt_tp_shardings(cfg, mesh, axis)
        sharded = jax.device_put(params, shardings)
        # rebind to the sharded copy so THIS model holds no reference
        # to the unsharded source tree — the caller can free theirs and
        # halve the footprint (at the HBM edge that's the difference
        # between fitting and OOM). Shape/dtype consumers
        # (param_bytes*, the ledger) are unaffected; a later
        # single-device build_fused_step on this instance would close
        # over sharded arrays, so use one model per server layout.
        self.params = sharded
        del params

        local_spec = self.step_spec(heads=h_loc, kv_heads=kv_loc)

        def local(lp_all, pools, tokens, positions, valid, tables):
            return _fused_step_body(
                lp_all, local_spec, block_size,
                lambda z: jax.lax.psum(z, axis),
                pools, tokens, positions, valid, tables,
                in_shard_map=True)

        param_specs = jax.tree_util.tree_map(
            lambda ns: ns.spec, shardings)
        layer_spec = {"kv": P(None, axis, None, None)}
        if kv_quantized:
            # the (N, H, bs) scale pools shard on the SAME head axis as
            # their code pools — a shard's rows carry their own scales
            layer_spec["k_scale"] = P(None, axis, None)
            layer_spec["v_scale"] = P(None, axis, None)
        pool_specs = [dict(layer_spec) for _ in range(cfg.num_layers)]
        rep = P()
        fn = shard_map(local, mesh=mesh,
                       in_specs=(param_specs, pool_specs, rep, rep,
                                 rep, rep),
                       out_specs=(pool_specs, rep, rep),
                       check_vma=False)

        def fused(pools, tokens, positions, valid, tables):
            return fn(sharded, pools, tokens, positions, valid, tables)

        return fused

    def param_bytes_per_device(self, mesh=None, axis="tp"):
        """Bytes of the parameter tree ONE device holds under the
        serving layout: sharded leaves (spec mentions `axis`) split by
        tp, replicated leaves count full — the HBM ledger's per-device
        unit. Without a mesh: the whole tree."""
        from ..observability.compile_insight import array_nbytes
        leaves = jax.tree_util.tree_leaves(self.params)
        if mesh is None:
            return sum(array_nbytes(a) for a in leaves)
        from ..models.gpt import gpt_tp_shardings
        tp = int(mesh.shape[axis])
        # tree_map over BOTH trees so a params/shardings structure
        # divergence fails loudly instead of zip-truncating silently
        per_leaf = jax.tree_util.tree_map(
            lambda a, ns: array_nbytes(a)
            // (tp if axis in tuple(ns.spec) else 1),
            self.params, gpt_tp_shardings(self.cfg, mesh, axis))
        return sum(jax.tree_util.tree_leaves(per_leaf))


class GenerationFuture(Future):
    """A Future whose cancel() also tells the scheduler to reclaim the
    request's slot and blocks (a plain Future can only cancel while
    queued; generation requests are cancellable mid-stream)."""

    def __init__(self, server, request_id):
        super().__init__()
        self._server = server
        self.request_id = request_id

    def cancel(self):
        if self.done():
            return False
        self._server._request_cancel(self.request_id)
        # the request may retire between the done() check and here; the
        # scheduler clears the stale cancel flag as a no-op next plan()
        if not super().cancel():
            return False
        self.set_running_or_notify_cancel()     # notify waiters now
        return True


class GenerationServer:
    """Continuous-batching generation engine: submit() from any thread,
    a single worker pumps scheduler iterations, results arrive as
    GenerationResult futures, tokens stream via per-request callbacks.

        server = GenerationServer(GPTServingModel.from_scope(scope, cfg))
        fut = server.submit(prompt_ids, max_new_tokens=32, eos_id=2,
                            stream=lambda rid, tok: print(tok))
        out = fut.result()          # GenerationResult
        server.close()              # graceful drain

    `start=False` skips the worker thread; tests then pump `step()`
    manually under an injected clock (no sleeps in the serving tier)."""

    # serializes FIRST fused-step traces process-wide: the kernel
    # dispatch counters in kv_cache are module globals, and two servers
    # tracing concurrently would read each other's dispatches into
    # their engagement verdicts
    _first_trace_lock = threading.Lock()

    def __init__(self, model, *, num_slots=4, block_size=16,
                 num_blocks=None, max_context=None, chunk=4, clock=None,
                 watermark_blocks=0, chaos=None, start=True,
                 telemetry=True, slo_window_s=60.0, flight_dir=None,
                 flight_capacity=256, deadline_storm=3, mesh=None,
                 mesh_axis="tp", prefix_cache=False, spec=None,
                 kv_dtype=None, host_kv_blocks=0):
        self.model = model
        self.block_size = int(block_size)
        self.mesh = mesh
        self.mesh_axis = mesh_axis if mesh is not None else None
        if mesh is not None and mesh_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh_axis {mesh_axis!r} is not a mesh axis (mesh has "
                f"{mesh.axis_names}) — pass mesh_axis=<the axis name>")
        tp = int(mesh.shape[mesh_axis]) if mesh is not None else 1
        # validate divisibility BEFORE anything allocates (pools,
        # scheduler, telemetry): build_fused_step re-checks for direct
        # callers, but by then the device pools already exist
        inner = getattr(getattr(model, "cfg", None), "inner_size", None)
        if mesh is not None and inner is not None and inner % tp:
            raise ValueError(
                f"tp={tp} must divide both num_heads={model.num_heads} "
                f"and inner_size={inner}")
        # GQA geometry, also before allocation: H % H_kv for any model
        # (GPTServingModel re-checks for direct construction) and
        # H_kv % tp under a mesh (the pools shard the KV head axis)
        kv_heads = getattr(model, "num_kv_heads", model.num_heads)
        if model.num_heads % kv_heads:
            raise ValueError(
                f"kv_heads={kv_heads} must divide "
                f"num_heads={model.num_heads}: grouped-query attention "
                f"needs an integral query-head group per KV head")
        if mesh is not None and kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide kv_heads={kv_heads}: the KV "
                f"pools shard on the KV head axis (with GQA that is "
                f"H_kv={kv_heads}, not the {model.num_heads} query "
                f"heads)")
        # a model with a state layer (a recurrent state a lane beside
        # the paged cache): everything that copies, shares or moves
        # cache by block cannot carry the state yet
        geometry = getattr(model, "kv_geometry", None)
        self._state_model = any(isinstance(g, dict)
                                for g in geometry or ())
        if self._state_model:
            for asked, what in (
                    (prefix_cache, "prefix_cache (reuse of a prefix's "
                     "blocks needs a snapshot of the state at a block "
                     "boundary)"),
                    (host_kv_blocks, "the host tier and preemption "
                     "(host_kv_blocks: a parked request's state is not "
                     "spilled)"),
                    (spec is not None, "speculative decoding "
                     "(SpecDecodeConfig: a rejected draft cannot be "
                     "rolled back out of a state)"),
                    (mesh is not None, "a mesh (mesh=: the state and "
                     "the expert exchange are not sharded)"),
                    (kv_dtype == "int8", "int8 pools (kv_dtype='int8': "
                     "the state is float32 by design)")):
                if asked:
                    raise NotImplementedError(
                        f"{what} is not supported for a model with a "
                        f"state layer ({type(model).__name__}): "
                        f"ROADMAP R5")
        max_context = int(max_context or model.max_position)
        if max_context > model.max_position:
            raise ValueError(
                f"max_context {max_context} exceeds the model's "
                f"max_position {model.max_position}")
        blocks_per_seq = -(-max_context // self.block_size)
        if num_blocks is None:
            num_blocks = num_slots * blocks_per_seq + 1   # +1: NULL block
        # kv_dtype: None serves dense pools in the model dtype (the
        # pre-quantization behavior); "bf16"/"int8" select the pool
        # storage format, with int8 reads dequantizing back to the
        # model dtype (PagedKVCache docstring has the scale layout)
        self.cache = PagedKVCache(model.num_layers, model.num_heads,
                                  model.head_dim, num_blocks,
                                  block_size=self.block_size,
                                  dtype=model.kv_dtype, mesh=mesh,
                                  axis=mesh_axis, kv_dtype=kv_dtype,
                                  num_kv_heads=kv_heads,
                                  geometry=geometry,
                                  num_slots=num_slots)
        if chaos is not None and clock is None and \
                getattr(chaos, "drives_clock", lambda: False)():
            clock = chaos.serving_clock
        # HBM-ledger component id: assigned early — the prefix index
        # labels its gauge series with it
        self._ledger_id = f"serving{next(_SERVER_SEQ)}"
        # prefix cache (serving/prefix_cache.py): cross-request block
        # sharing by content hash. True builds a fresh index over this
        # server's pool; tests may pass a pre-built PrefixCacheIndex.
        self._prefix = None
        if prefix_cache:
            from .prefix_cache import PrefixCacheIndex
            self._prefix = (prefix_cache if not isinstance(
                prefix_cache, bool)
                else PrefixCacheIndex(self.cache, chaos=chaos,
                                      label=self._ledger_id))
        # speculative decoding (serving/spec_decode.py)
        self._spec = spec
        self._draft_cache = None
        self._draft = None
        self._draft_signatures = set()
        if spec is not None:
            if mesh is not None:
                raise NotImplementedError(
                    "speculative decoding on a mesh is not supported "
                    "yet — run spec servers single-device (the draft "
                    "step under shard_map is follow-up work, "
                    "docs/serving.md)")
            dm = spec.draft_model
            if dm.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab {dm.cfg.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size} — proposals are fed "
                    f"straight into the target's verify step")
        # request-level telemetry (observability/serving_telemetry.py):
        # lifecycle span trees, SLO digests, and the fault flight
        # recorder. telemetry=False runs the bare PR-6 engine; an
        # explicit ServingTelemetry instance lets tests inject
        # clocks/sampling without env vars.
        if telemetry is True:
            from ..observability.serving_telemetry import ServingTelemetry
            telemetry = ServingTelemetry(
                clock=clock, window_s=slo_window_s,
                flight_dir=flight_dir, flight_capacity=flight_capacity,
                deadline_storm=deadline_storm)
        elif telemetry is False:
            telemetry = None
        self._tel = telemetry
        self._chaos = chaos
        self._prompt_poison_fired = set()   # plan entries this engine
        #                                     already applied (chaos)
        self._fault = None          # what fail-stopped the engine
        self._exporter = None
        self._sched = ContinuousBatchingScheduler(
            self.cache, num_slots=num_slots, chunk=chunk,
            max_context=max_context, clock=clock,
            watermark_blocks=watermark_blocks, chaos=chaos,
            telemetry=telemetry, prefix_cache=self._prefix,
            spec_k=spec.k if spec is not None else 0,
            spec_mode=spec.mode if spec is not None else "greedy",
            spec_seed=spec.seed if spec is not None else 0)
        self.max_context = max_context
        if spec is not None:
            # draft pools mirror the target pool's block ids (same
            # num_blocks/block_size, the draft's own head geometry) —
            # one host allocation drives both, and cow_copy keeps the
            # sibling rows consistent with every repointed table
            dm = spec.draft_model
            # the draft pools follow the target's kv_dtype: speculation
            # exists to stretch the same HBM budget, and greedy
            # acceptance keeps ids bitwise-correct whatever the draft's
            # KV precision (every committed id is the target's)
            self._draft_cache = PagedKVCache(
                dm.num_layers, dm.num_heads, dm.head_dim,
                self.cache.num_blocks, block_size=self.block_size,
                dtype=dm.kv_dtype, kv_dtype=kv_dtype,
                num_kv_heads=getattr(dm, "num_kv_heads", dm.num_heads))
            self.cache.attach_sibling(self._draft_cache)
            from .spec_decode import build_draft_step
            self._draft = jax.jit(build_draft_step(
                dm, self.block_size, spec.k), donate_argnums=(0,))
        # host KV tier (tiered cache): a numpy block pool in host RAM
        # that eviction spills to and preemption parks in. Enabled
        # AFTER the draft sibling attaches so the tier mirrors onto the
        # draft pools too (a parked spec request keeps its draft KV).
        if host_kv_blocks:
            self.cache.enable_host_tier(int(host_kv_blocks))
        # mesh/per_column kwargs only when needed: a custom model
        # implementing the original build_fused_step(block_size) keeps
        # working for plain single-device serving. Speculative servers
        # are the ONLY ones that pay the per-column lm-head projection
        # (C x the narrow gemm) — plain decode reads one column per
        # lane, so it keeps the last-column gather.
        # decode strategies (ISSUE 20): single-device servers whose
        # model's build_fused_step grew the `sampling` kwarg get the
        # in-step sampling/guided-mask path — and with it fork groups
        # (submit(n=K) / beam=) and guided decoding. Feature-detected so
        # custom models with the original signature keep working; the
        # vocab size must be readable for the mask rows.
        import inspect
        self._vocab = getattr(getattr(model, "cfg", None),
                              "vocab_size", None)
        self._strategies = (
            mesh is None and self._vocab is not None
            and "sampling" in inspect.signature(
                model.build_fused_step).parameters)
        if mesh is not None:
            mesh_kw = {"mesh": mesh, "axis": mesh_axis}
            if self.cache.quantized:
                # only passed when needed, so a custom model with the
                # pre-quantization build_fused_step signature keeps
                # working for dense mesh serving
                mesh_kw["kv_quantized"] = True
            fused = model.build_fused_step(self.block_size, **mesh_kw)
        else:
            step_kw = {}
            if spec is not None:
                step_kw["per_column"] = True
            if self._strategies:
                step_kw["sampling"] = True
            fused = model.build_fused_step(self.block_size, **step_kw)
        # argument 0 is the pools: the step rewrites them in place (XLA
        # aliases each pool's output to its input), so the call consumes
        # them and _dispatch_fused stores the new set before anyone can
        # look (docs/serving.md "Who owns the pools")
        self._fused = jax.jit(fused, donate_argnums=(0,))
        self._signatures = set()
        # HBM ledger (observability/compile_insight.py): the serving
        # side of get_stats()["memory"] / the /memory endpoint — block
        # pools + model params as resident rows, plus a static peak
        # estimate for the fused step (pools and params dominate; the
        # per-iteration activations are S x C x hidden per layer).
        # Under a mesh the kv rows are PER DEVICE (one row per mesh
        # position, each holding its H/tp shard's bytes) so the rows
        # sum to the pool's logical bytes — never tp x overcounted —
        # while still attributing capacity to the device that pays it.
        # close() retires the rows on BOTH teardown paths.
        from ..observability.compile_insight import (array_nbytes,
                                                     hbm_ledger)
        kv_bytes = self.cache.pool_bytes()
        shard_bytes = self.cache.shard_pool_bytes()
        param_bytes = sum(array_nbytes(a) for a in
                          jax.tree_util.tree_leaves(model.params))
        hidden = model.num_heads * model.head_dim
        act_est = num_slots * chunk * hidden * 4 * (2 * model.num_layers
                                                    + 4)
        led = hbm_ledger()
        # quantized pools report their TRUE int8+scales bytes (pool_
        # bytes already counts the scale pools) plus the dense size the
        # same block count would have cost — capacity dashboards read
        # the saving straight off the row instead of recomputing it
        # "heads" is the pools' PHYSICAL head count (H_kv under GQA —
        # the byte truth); "q_heads" keeps the model-side head count on
        # the row so the group factor is readable in place
        kv_detail = {"layers": model.num_layers,
                     "num_blocks": self.cache.num_blocks,
                     "block_size": self.block_size,
                     "heads": kv_heads,
                     "q_heads": model.num_heads,
                     "head_dim": model.head_dim,
                     "dtype": str(np.dtype(self.cache.dtype)),
                     "kv_dtype": kv_dtype,
                     "tier": "device"}
        if self.cache.quantized:
            kv_detail["scale_bytes"] = self.cache.scale_bytes()
            kv_detail["dense_equiv_bytes"] = \
                self.cache.dense_pool_bytes()
        if mesh is None:
            led.register(self._ledger_id, "kv_pool", "kv_cache",
                         kv_bytes, detail=kv_detail)
            param_dev_bytes = param_bytes
        else:
            for i, dev in enumerate(mesh.devices.flat):
                led.register(
                    self._ledger_id, f"kv_pool/shard{i}", "kv_cache",
                    shard_bytes,
                    detail=dict(kv_detail, device=str(dev),
                                mesh_index=i, axis=mesh_axis,
                                heads_local=kv_heads // tp))
            param_dev_bytes = param_bytes
            if hasattr(model, "param_bytes_per_device"):
                param_dev_bytes = model.param_bytes_per_device(
                    mesh, mesh_axis)
        # host tier: its own row under the NON-resident "host_ram"
        # kind — host RAM is real memory the fleet sizes against, but
        # it must never inflate the per-device HBM totals the resident
        # kinds sum into (memory.total_bytes stays device truth). The
        # device/host split is readable straight off the two rows'
        # tier details.
        if self.cache.host is not None:
            led.register(
                self._ledger_id, "kv_pool_host", "host_ram",
                self.cache.host_pool_bytes(),
                detail=dict(kv_detail, tier="host",
                            num_blocks=self.cache.host.num_blocks))
        led.register(self._ledger_id, "model_params", "params",
                     param_bytes,
                     detail={"source": "serving model",
                             "per_device_bytes": param_dev_bytes})
        # speculative decoding: the draft pools and draft params are
        # REAL extra residency — their own rows, under this server's
        # component id so close() retires them too. Shared prefix
        # blocks, by contrast, are NOT extra bytes: the pool rows above
        # are the preallocated pools' full footprint whoever holds the
        # block refs, so sharing can never double-count a block.
        draft_bytes = 0
        if spec is not None:
            draft_pool_bytes = self._draft_cache.pool_bytes()
            draft_param_bytes = sum(
                array_nbytes(a) for a in
                jax.tree_util.tree_leaves(spec.draft_model.params))
            led.register(self._ledger_id, "draft_kv_pool", "kv_cache",
                         draft_pool_bytes,
                         detail={"layers": spec.draft_model.num_layers,
                                 "num_blocks": self.cache.num_blocks,
                                 "block_size": self.block_size,
                                 "heads": self._draft_cache.num_kv_heads,
                                 "q_heads": spec.draft_model.num_heads,
                                 "head_dim": spec.draft_model.head_dim,
                                 "spec_k": spec.k})
            led.register(self._ledger_id, "draft_params", "params",
                         draft_param_bytes,
                         detail={"source": "spec draft model"})
            draft_bytes = draft_pool_bytes + draft_param_bytes
        # peak is PER DEVICE (compile_insight's unit): one shard's
        # params + its kv shard + the replicated activations (+ the
        # draft model's pools and params when speculating)
        led.register(self._ledger_id, "fused_step", "peak_hbm",
                     param_dev_bytes + shard_bytes + act_est
                     + draft_bytes,
                     detail={"source": "static",
                             "activation_bytes_est": act_est,
                             "per_device": True})
        # mesh gauges (serving.mesh.*): the tp degree, what one device
        # commits to the pools, and the psums a fused step pays — the
        # capacity facts a fleet dashboard sizes against. Removed on
        # close (both paths) like the SLO gauges.
        self._mesh_gauges = None
        if mesh is not None:
            reg0 = global_registry()
            self._mesh_gauges = {
                "serving.mesh.axis_size": tp,
                "serving.mesh.shard_pool_bytes": shard_bytes,
                "serving.mesh.psums_per_step": 2 * model.num_layers,
            }
            for name, val in self._mesh_gauges.items():
                reg0.gauge(name, _help(name)).labels(
                    server=self._ledger_id).set(val)
        # quantized-pool gauges (serving.kv.quant.*): the true
        # int8+scales footprint and the bytes the quantization saved vs
        # the dense compute-dtype pool — the capacity facts behind
        # "~2x blocks per chip". Same label/retire discipline as the
        # mesh gauges (a closed server must stop reporting savings).
        self._quant_gauges = None
        if self.cache.quantized:
            reg0 = global_registry()
            self._quant_gauges = {
                "serving.kv.quant.pool_bytes": kv_bytes,
                "serving.kv.quant.bytes_saved":
                    self.cache.dense_pool_bytes() - kv_bytes,
            }
            for name, val in self._quant_gauges.items():
                reg0.gauge(name, _help(name)).labels(
                    server=self._ledger_id).set(val)
        # what the state layers hold over all lanes (0 series without
        # one): retired on close like the gauges above
        self._state_gauge = None
        if self._state_model:
            self._state_gauge = global_registry().gauge(
                "serving.state.bytes",
                _help("serving.state.bytes")).labels(
                    server=self._ledger_id)
            self._state_gauge.set(self.cache.state_bytes())
        # host-tier gauges (serving.kv.tier.*): the tier's capacity
        # plus its cumulative traffic (spills/swap-ins/preempts/
        # resumes/re-prefills avoided), server-labeled and re-published
        # every _publish_gauges tick. Same retire discipline as the
        # mesh/quant gauges — a closed server must stop reporting a
        # host-RAM footprint (both close paths).
        self._tier_gauges = None
        if self.cache.host is not None:
            reg0 = global_registry()
            self._tier_gauges = {
                name: reg0.gauge(name, _help(name)).labels(
                    server=self._ledger_id)
                for name in ("serving.kv.tier.host_blocks",
                             "serving.kv.tier.spills",
                             "serving.kv.tier.swap_ins",
                             "serving.kv.tier.preempts",
                             "serving.kv.tier.resumes",
                             "serving.kv.tier.reprefills_avoided")}
            self._tier_gauges["serving.kv.tier.host_blocks"].set(
                self.cache.host.num_blocks)
            self._publish_tier_gauges()
        # paged-kernel engagement accounting: the fused step traces
        # ONCE; the module dispatch counters' delta across that trace
        # proves which attention path this server actually compiled
        # (flash.py's TRACE_COUNT lesson — a silent fallback must not
        # masquerade as the kernel). The delta is measured around the
        # first fused call under a process-wide lock (see step()), so
        # neither other servers' dispatches nor concurrent first-step
        # traces can corrupt this server's verdict.
        self._kernel_engaged = None     # unknown until the first step
        self._kernel_mode = None        # mode the step traced under
        self._kernel_counts = (0, 0)    # this server's trace dispatches
        self._kernel_version = None     # v1/v2 the trace dispatched to
        self._kernel_name = None        # and the kernel's own name
        self._next_rid = 0
        self._rid_lock = threading.Lock()
        self._closed = False
        self._step_lock = threading.Lock()
        self._cv = threading.Condition()
        reg = global_registry()
        self._m = {
            "requests": reg.counter("serving.requests",
                                    _help("serving.requests")),
            "iterations": reg.counter("serving.iterations",
                                      _help("serving.iterations")),
            "sampled_iterations": reg.counter(
                "serving.sampled_iterations",
                _help("serving.sampled_iterations")),
            "step_ms": reg.histogram("serving.step_ms",
                                     _help("serving.step_ms")),
            "valid_columns": reg.counter(
                "serving.valid_columns", _help("serving.valid_columns")),
            "padded_columns": reg.counter(
                "serving.padded_columns", _help("serving.padded_columns")),
            "queue_depth": reg.gauge("serving.queue_depth",
                                     _help("serving.queue_depth")),
            "active_slots": reg.gauge("serving.active_slots",
                                      _help("serving.active_slots")),
            "blocks_in_use": reg.gauge("serving.blocks_in_use",
                                       _help("serving.blocks_in_use")),
            "pool_donations": reg.counter(
                "serving.kv.pool_donations",
                _help("serving.kv.pool_donations")),
        }
        # a model whose step counts something returns the counts as
        # one more output (`_fused_step_body`) and names them:
        # `step_counters`, a (name in the iteration record, registry
        # counter it feeds or None) a count
        self._step_counters = tuple(getattr(model, "step_counters", ()))
        self._step_counts = None
        self._counters_fed = [
            (i, reg.counter(metric, _help(metric)))
            for i, (_arg, metric) in enumerate(self._step_counters)
            if metric]
        self._worker = None
        if start:
            self._worker = threading.Thread(target=self._serve,
                                            daemon=True)
            self._worker.start()

    # -- client surface ----------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=32, eos_id=None,
               priority=0, deadline_ms=None, stream=None,
               trace_ctx=None, tenant=None, n=1, sampling=None,
               beam=None, guided=None):
        """prompt_ids: 1-D int token ids. Returns a GenerationFuture
        resolving to a GenerationResult (or raising DeadlineExceeded /
        RequestCancelled). `stream(request_id, token)` fires on the
        serve thread for every generated token. Lower `priority` values
        run first (FIFO within a priority). `trace_ctx` is the fleet
        router's TraceContext (observability/fleet_trace.py): its
        trace id/hop land on this request's span tree and its sampling
        verdict overrides this engine's own — a request is traced on
        all hops or none. `tenant` is an opaque cost-attribution
        identity (get_stats()["tenants"], /tenants endpoint); it never
        affects scheduling or token ids.

        Decode strategies (ISSUE 20, single-device servers):

        - `sampling=SamplingParams(...)` turns on stochastic decode for
          this request (temperature / top-k / nucleus, counter-keyed so
          replays resample identically).
        - `n=K` (or SamplingParams(n=K)) forks the request into K lanes
          sharing the prompt KV — ONE prefill, K streams; returns a
          GroupFuture resolving to a GroupResult (per-lane stream
          callbacks fire with GroupFuture.lane_rids[rank]).
        - `beam=BeamParams(beam_size=K)` runs paged beam search
          (requires eos_id; excludes sampling/stream; ids bitwise the
          dense inference.decoding.beam_decode reference's).
        - `guided=<Constraint>` (serving.guided) masks every emission
          to the constraint's allowed set (requires eos_id; composes
          with sampling and fork groups)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = int(prompt.size) + int(max_new_tokens)
        if total > self.max_context:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds max_context "
                f"{self.max_context}")
        need = self.cache.blocks_for_tokens(total)
        if need > self.cache.usable_blocks:
            raise ValueError(
                f"request needs {need} blocks but the pool only has "
                f"{self.cache.usable_blocks}")
        # -- decode-strategy validation ---------------------------------
        n = int(n)
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if beam is not None:
            if sampling is not None or n != 1:
                raise ValueError(
                    "beam search excludes sampling/n — beams are ranked "
                    "deterministically by cumulative logprob")
            if stream is not None:
                raise ValueError(
                    "beam search cannot stream: a beam-reorder rewrites "
                    "lane streams retroactively")
            if eos_id is None:
                raise ValueError(
                    "beam search requires eos_id (finished-hypothesis "
                    "masking is defined by it)")
            if self._spec is not None and self._spec.mode == "rejection":
                raise NotImplementedError(
                    "beam search composes with greedy speculative "
                    "verification only — rejection-sampled acceptance "
                    "has no beam analogue (docs/serving.md)")
        if n > 1:
            if sampling is None:
                sampling = SamplingParams(n=n)
            elif sampling.n not in (1, n):
                raise ValueError(
                    f"n={n} conflicts with SamplingParams(n="
                    f"{sampling.n})")
        k = beam.beam_size if beam is not None else \
            max(n, sampling.n if sampling is not None else 1)
        wants = (beam is not None or guided is not None or k > 1
                 or (sampling is not None and sampling.do_sample))
        if wants and not self._strategies:
            raise NotImplementedError(
                "decode strategies (sampling/n>1/beam/guided) need the "
                "strategies fused step: single-device serving with a "
                "model whose build_fused_step accepts `sampling` "
                "(mesh servers are follow-up work, docs/serving.md)")
        if guided is not None and eos_id is None:
            raise ValueError(
                "guided decoding requires eos_id (constraint "
                "completion is signalled by unmasking eos)")
        if k > 1 and self._state_model:
            raise NotImplementedError(
                "fork groups (n > 1, beam) are not supported for a model "
                "with a state layer: the lanes of a group share the "
                "prompt's blocks, and a lane's state is not copied to "
                "its siblings (ROADMAP R5)")
        if k > 1:
            if k > self._sched.num_slots:
                raise ValueError(
                    f"fork group of {k} lanes exceeds num_slots="
                    f"{self._sched.num_slots} — the group admits "
                    f"atomically and could never fit")
            m_total = need
            m_prompt = self.cache.blocks_for_tokens(int(prompt.size))
            worst = m_total + (k - 1) * (m_total - m_prompt) + k
            if worst > self.cache.usable_blocks:
                raise ValueError(
                    f"fork group needs up to {worst} blocks but the "
                    f"pool only has {self.cache.usable_blocks}")
            return self._submit_group(
                prompt, int(max_new_tokens), eos_id, priority,
                deadline_ms, stream, trace_ctx, tenant, k,
                sampling if beam is None else None, beam, guided)
        with self._rid_lock:
            if self._closed:
                raise RuntimeError("GenerationServer is closed")
            rid = self._next_rid
            self._next_rid += 1
        if self._tel is not None:
            # before enqueue: the worker thread may admit the request
            # the instant it lands, and on_admit needs the submit stamp
            self._tel.on_submit(rid, ctx=trace_ctx, tenant=tenant)
        fut = GenerationFuture(self, rid)
        deadline = None
        if deadline_ms is not None:
            deadline = self._sched.now() + deadline_ms / 1e3
        req = _Request(rid, prompt, int(max_new_tokens), eos_id,
                       priority, deadline, stream, fut,
                       self._sched.now(), tenant=tenant,
                       sampling=sampling, guided=guided)
        if guided is not None:
            req.guided_state = guided.initial_state()
        self._sched.enqueue(req)
        with self._rid_lock:
            raced_closed = self._closed
        if raced_closed:
            # lost the race with close()/_on_engine_fault: their
            # cancel_all queue sweep may have run before this enqueue
            # landed, which would leave the request (and its future)
            # orphaned with no worker to plan it. Pull it back out and
            # behave exactly as if the closed-check above had caught us.
            self._sched.drop_queued_request(
                rid, self._fault or
                RequestCancelled("GenerationServer is closed"))
            raise RuntimeError("GenerationServer is closed")
        self._m["requests"].inc()
        with self._cv:
            self._cv.notify()
        return fut

    def _submit_group(self, prompt, max_new_tokens, eos_id, priority,
                      deadline_ms, stream, trace_ctx, tenant, k,
                      sampling, beam, guided):
        """Build and enqueue one RequestGroup: K lane _Requests (rank 0
        is the leader — the only one queued; the scheduler admits the
        whole group atomically off it), one GroupFuture. Beam lanes
        carry eos on the GROUP, never on the lane (finished hypotheses
        pad with forced eos instead of retiring, exactly like the dense
        reference), and never stream."""
        kind = "beam" if beam is not None else "sample"
        with self._rid_lock:
            if self._closed:
                raise RuntimeError("GenerationServer is closed")
            rids = [self._next_rid + i for i in range(k)]
            self._next_rid += k
        if self._tel is not None:
            for rid in rids:
                # one on_submit per LANE: tenant billing counts every
                # lane's tokens, not one K-th of the group
                self._tel.on_submit(rid, ctx=trace_ctx, tenant=tenant)
        group = RequestGroup(rids[0], kind, k, eos_id, max_new_tokens,
                             sampling=sampling, beam=beam)
        fut = GroupFuture(rids[0], rids,
                          cancel_fn=lambda: [self._request_cancel(r)
                                             for r in rids])
        group.future = fut
        now = self._sched.now()
        deadline = None
        if deadline_ms is not None:
            deadline = now + deadline_ms / 1e3
        for rank, rid in enumerate(rids):
            req = _Request(rid, prompt, max_new_tokens,
                           None if kind == "beam" else eos_id,
                           priority, deadline,
                           None if kind == "beam" else stream,
                           Future(), now, tenant=tenant, group=group,
                           lane=rank, sampling=sampling, guided=guided)
            if guided is not None:
                req.guided_state = guided.initial_state()
            group.lanes.append(req)
        self._sched.enqueue(group.lanes[0])
        with self._rid_lock:
            raced_closed = self._closed
        if raced_closed:
            self._sched.drop_queued_request(
                rids[0], self._fault or
                RequestCancelled("GenerationServer is closed"))
            raise RuntimeError("GenerationServer is closed")
        self._m["requests"].inc()
        with self._cv:
            self._cv.notify()
        return fut

    def _request_cancel(self, rid):
        self._sched.request_cancel(rid)
        with self._cv:
            self._cv.notify()

    def pending(self):
        return self._sched.queue_depth + self._sched.active_count

    # -- serve loop --------------------------------------------------------
    def step(self):
        """Run one scheduler iteration + fused device step. Returns
        True if any lane did work. Public so tests can pump the engine
        deterministically without the worker thread."""
        with self._step_lock:
            tel = self._tel
            rec = get_recorder()
            it0 = self._sched.iteration
            # leaf spans (docs/serving.md): plan, feed, dispatch, fetch,
            # commit, account tile the body of a non-idle iteration on
            # this thread, so every idle gap of the device has a name
            with rec.span("serving.plan", cat="serving",
                          args=lambda: {"iteration": self._sched.iteration,
                                        "idle": plan is None}):
                if tel is not None:
                    # before plan(): the iteration's deadline cancels
                    # fire inside plan and must land on THIS iteration's
                    # flight entry (plan() increments the counter if
                    # non-idle)
                    tel.begin_iteration(it0 + 1)
                admitted0 = self._sched.counts["admitted"]
                plan = self._sched.plan()
                self._publish_gauges()
            if plan is None:
                it = self._sched.iteration
                if self._chaos is not None and it > it0:
                    # a poison keyed to a cancel/deadline-only
                    # iteration (counted, but no lane ran) would be
                    # popped by no one and silently lost — re-key it to
                    # the next iteration instead
                    poison_layer = self._chaos.serving_poison_at(it)
                    if poison_layer is not None:
                        self._chaos.poison_serving_at(it + 1,
                                                      poison_layer)
                if tel is not None and it > it0:
                    # a cancel/deadline-only iteration (counted by the
                    # scheduler, but no lane ran): the flight ring and
                    # the deadline-storm detector must still see it
                    tel.end_iteration(
                        it, step_ms=0.0, lanes=[], emitting=[],
                        prefill_tokens=0,
                        admitted=self._sched.counts["admitted"]
                        - admitted0,
                        retired=[],
                        queue_depth=self._sched.queue_depth,
                        active_slots=self._sched.active_count,
                        blocks_free=self.cache.num_free,
                        blocks_in_use=self.cache.num_used,
                        watermark_blocks=self._sched.watermark_blocks,
                        lanes_detail=[],
                        kernel={"mode": self._kernel_mode,
                                "engaged": self._kernel_engaged})
                return False
            it = self._sched.iteration
            # pre-step occupancy rides the plan (built inside plan()'s
            # slot loop — no second scheduler-lock round-trip)
            lanes = plan.lanes_detail
            leaf = {"iteration": it} if rec.enabled else None
            t0 = time.perf_counter()
            with rec.span("serving.iteration", cat="serving",
                          args=lambda: self._iteration_record(plan, it)):
                with rec.span("serving.feed", cat="serving", args=leaf):
                    if self._chaos is not None:
                        self._apply_step_chaos(it, lanes)
                    # speculative mode: the draft step runs EVERY
                    # iteration (its KV must track prefill chunks too,
                    # not just decode lanes) and its proposals land in
                    # plan.tokens columns 1..q-1 before the fused step
                    # verifies them: they are part of what is fed
                    draft_logps = None
                    if self._draft is not None:
                        with rec.span("serving.draft", cat="serving",
                                      args=leaf):
                            draft_logps = self._run_draft(plan)
                    args = (jnp.asarray(plan.tokens),
                            jnp.asarray(plan.positions),
                            jnp.asarray(plan.valid),
                            jnp.asarray(plan.tables))
                    if self._strategies:
                        # mask/rng/temperature/do_sample/top_k/top_p are
                        # DATA with constant shapes — the signature set
                        # below still collapses to one entry
                        args = args + self._strategies_args(plan, it)
                    self._signatures.add(
                        tuple((a.shape, str(a.dtype)) for a in args))
                with rec.span("serving.dispatch", cat="serving",
                              args=leaf):
                    out = self._dispatch_fused(args)
                with rec.span("serving.fetch", cat="serving", args=leaf):
                    nxt, logps, fed, rows = self._fetch_outputs(out, plan)
            with rec.span("serving.commit", cat="serving", args=leaf):
                # non-finite logits guard: one reduce on the hot path (a
                # NaN/Inf anywhere makes the sum non-finite; idle lanes
                # hold finite garbage); the per-slot triage only runs on
                # a trip, BEFORE commit() streams garbage tokens to
                # clients. math.isfinite on the extracted scalar beats
                # np.isfinite's ufunc dispatch on this every-iteration
                # path. The fail-stop is a safety feature and runs
                # regardless of telemetry — only the flight-recorder
                # dump needs it
                if plan.slot_ids and \
                        not math.isfinite(float(logps.sum())):
                    if not np.all(np.isfinite(logps[plan.slot_ids])):
                        self._on_engine_fault(plan, it, logps, lanes)
                retired = self._sched.commit(plan, nxt, logps,
                                             fed_logps=fed,
                                             draft_logps=draft_logps,
                                             rows=rows)
            with rec.span("serving.account", cat="serving", args=leaf):
                self._m["iterations"].inc()
                if plan.sample_ctl[0].any():
                    # the step's own predicate: it took its sampled
                    # branch (`_sampled_where_asked`)
                    self._m["sampled_iterations"].inc()
                self._m["valid_columns"].inc(plan.valid_columns)
                self._m["padded_columns"].inc(plan.padded_columns)
                step_ms = (time.perf_counter() - t0) * 1e3
                self._m["step_ms"].observe(step_ms)
                self._publish_gauges()
                if tel is not None:
                    st = self._sched
                    # hot path: one ITER_FIELDS-order tuple per
                    # iteration (tuples of scalars are GC-untracked;
                    # per-iteration dicts next to a ~0.25 ms fused step
                    # kept promoting ring garbage into the older GC
                    # generations)
                    tel.end_iteration(it, (
                        round(step_ms, 3),              # step_ms
                        tuple(plan.slot_ids),           # lanes
                        tuple(plan.emitting),           # emitting
                        plan.prefill_tokens,
                        st.counts["admitted"] - admitted0,
                        tuple(r.request_id for r in retired),
                        plan.queue_depth,
                        len(plan.slot_ids),             # active_slots
                        self.cache.num_free,            # blocks_free
                        self.cache.num_used,            # blocks_in_use
                        st.watermark_blocks,
                        lanes,                          # lanes_detail
                        self._kernel_info()))
            return True

    def _iteration_record(self, plan, it):
        """The `serving.iteration` span's args, built only while a
        capture is live: what the fused step was handed. `lanes_qc` is
        each lane's (queries, context) — columns fed this iteration, and
        the tokens its attention reads once they are written.
        `sampled_lanes` counts the lanes that draw their token: with 0
        the step skips its sampled tail. `walk_groups_live` over
        `walk_groups` is the share of the block
        table the paged kernel touches: it walks a lane's table in
        groups of `walk_group` columns and stops after the last one
        that holds a token (the latent walk the same). A model whose
        step counts (`step_counters`) adds the counts under its
        names."""
        from ..ops.pallas.paged import walk_group
        cols = plan.valid.sum(axis=1)
        lanes_qc = [[int(cols[sid]),
                     int(plan.positions[sid, cols[sid] - 1]) + 1]
                    for sid in plan.slot_ids]
        width = plan.tables.shape[1]
        group = walk_group(self.block_size, width)
        keys = group * self.block_size          # 128 key positions
        record = {"iteration": it, "lanes": len(plan.slot_ids),
                  "prefill_tokens": plan.prefill_tokens,
                  "valid_columns": plan.valid_columns,
                  "padded_columns": plan.padded_columns,
                  "sampled_lanes": int(plan.sample_ctl[0].sum()),
                  "lanes_qc": lanes_qc,
                  "walk_groups_live": sum(-(-ctx // keys)
                                          for _q, ctx in lanes_qc),
                  "walk_groups": len(lanes_qc) * -(-width // group)}
        record.update(zip((arg for arg, _metric in self._step_counters),
                          self._step_counts or ()))
        return record

    def _apply_step_chaos(self, it, lanes):
        """Injected KV poison, applied before the step is fed."""
        # content-addressed poison: a STANDING plan keyed to a request's
        # prompt bytes, so the fault follows the request's failover
        # replay onto every replica it lands on (the quarantine cascade
        # seed). Each plan entry applies (and counts) at most once per
        # ENGINE — the fault kills the server the same iteration, so
        # fired == replica deaths caused, never inflated by a lane
        # sitting poisoned across iterations
        for pi, (pp, pl) in enumerate(self._chaos.prompt_poison_plan()):
            if pi in self._prompt_poison_fired:
                continue
            blk = self._sched.lane_block_for_prompt(pp)
            if blk is not None:
                self._nan_block(pl, blk)
                self._prompt_poison_fired.add(pi)
                self._chaos.prompt_poison_applied()
        poison_layer = self._chaos.serving_poison_at(it)
        if poison_layer is not None:
            if self._poison_kv(poison_layer, lanes):
                self._chaos.serving_poison_applied()
            else:
                # no lane past pos 0 yet: its block would be fully
                # overwritten by its own prefill write this iteration —
                # defer, don't no-op
                self._chaos.poison_serving_at(it + 1, poison_layer)

    def _call_donating(self, fn, cache, args):
        """Call a jitted step that donates `cache.pools` (its argument
        0) and returns the rewritten pools first, and store those: one
        step under the cache's lock, so that no reader on another
        thread finds the consumed set. Returns (outputs, whether the
        old pools were consumed)."""
        k0 = None
        try:
            with cache.pools_lock:
                k0 = next(iter(cache.pools[0].values()))
                out = fn(cache.pools, *args)
                cache.pools = out[0]
        except Exception as e:
            if k0 is not None and k0.is_deleted():
                # the buffers went with the call: there is no KV left
                # to serve from. Stop now and with this error, not at
                # the next step with "Array has been deleted"
                self._fail_stop(e)
            raise
        return out, k0.is_deleted()

    def _call_fused(self, args):
        out, donated = self._call_donating(self._fused, self.cache, args)
        if donated:
            self._m["pool_donations"].inc()
        return out

    def _dispatch_fused(self, args):
        """Launch the fused step on the live pools and store the pools
        it returns; returns its outputs (device arrays, not waited
        for)."""
        if self._kernel_engaged is not None:
            return self._call_fused(args)
        # first fused call is about to TRACE: serialize it against other
        # servers' first traces and snapshot the dispatch mode +
        # counters right around it, so the delta covers exactly THIS
        # trace
        with GenerationServer._first_trace_lock:
            self._kernel_mode = _kvc.paged_kernel_mode()
            k0, f0 = (_kvc.KERNEL_DISPATCHES, _kvc.FALLBACK_DISPATCHES)
            v0 = dict(_kvc.KERNEL_VERSIONS)
            n0 = dict(_kvc.KERNEL_NAMES)
            out = self._call_fused(args)
            self._kernel_counts = (_kvc.KERNEL_DISPATCHES - k0,
                                   _kvc.FALLBACK_DISPATCHES - f0)
            # which kernel GENERATION this trace's dispatches took (None
            # if none engaged)
            dv = [v for v in ("v1", "v2")
                  if _kvc.KERNEL_VERSIONS.get(v, 0) > v0.get(v, 0)]
            self._kernel_version = (dv[0] if len(dv) == 1 else
                                    ("mixed" if dv else None))
            # and which kernel(s), by name in a device trace
            self._kernel_name = "+".join(sorted(
                n for n, k in _kvc.KERNEL_NAMES.items()
                if k > n0.get(n, 0))) or None
        self._check_kernel_engagement()
        return out

    def _fetch_outputs(self, out, plan):
        """Bring the step's host-side outputs over (this waits for the
        device): (ids, logps, fed logps or None, logp rows or None)."""
        # plain mode: (pools, ids (S,), logps (S,)) from the last-column
        # step; spec mode adds fed_logps and every output is per-column
        # (S, C)
        if self._step_counters:
            # the step's counts, its last output: their copy starts
            # now and rides under the two waits below, so that reading
            # them costs no round trip of its own
            out[-1].copy_to_host_async()
        nxt, logps = np.asarray(out[1]), np.asarray(out[2])
        if self._step_counters:
            self._step_counts = np.asarray(out[-1]).tolist()
            for i, counter in self._counters_fed:
                counter.inc(self._step_counts[i])
        if nxt.ndim == 1:
            # commit() reads per-column arrays; a broadcast VIEW puts
            # the last-valid-column value at every column (a prefill
            # lane reads col n-1, a decode lane col 0 — both ARE that
            # value), zero copies
            s, c = plan.tokens.shape
            nxt = np.broadcast_to(nxt[:, None], (s, c))
            logps = np.broadcast_to(logps[:, None], (s, c))
        # target-logp-of-fed-token only matters to the rejection-sampled
        # acceptance; don't pay its host transfer otherwise
        fed = (np.asarray(out[3])
               if self._spec is not None
               and self._spec.mode == "rejection" else None)
        # full logp rows (last output when the strategies step is
        # compiled in): fork-time host sampling and beam re-ranking read
        # them — transferred only when this plan actually has a group
        # that needs them
        rows = None
        if self._strategies and plan.needs_rows:
            rows = np.asarray(
                out[4] if self._spec is not None else out[3])
        return nxt, logps, fed, rows

    def _run_draft(self, plan):
        """One draft-step call: sync the draft KV with this iteration's
        feed (prefill chunks; each decode lane's committed token), roll
        out k proposals per decode lane, and write the proposals into
        plan.tokens columns 1..q-1 for the fused verify step. Returns
        the draft's per-proposal logps (S, k) for rejection-mode
        acceptance."""
        valid_d = plan.valid.copy()
        spec_go = plan.decode_cols >= 1
        for sid in plan.slot_ids:
            if int(plan.decode_cols[sid]) > 1:
                # the draft's sync pass feeds ONLY the committed token;
                # the verify columns belong to the target step
                valid_d[sid, 1:] = False
        (_, props, dlps), _ = self._call_donating(
            self._draft, self._draft_cache,
            (jnp.asarray(plan.tokens), jnp.asarray(plan.positions),
             jnp.asarray(valid_d), jnp.asarray(plan.tables),
             jnp.asarray(spec_go), jnp.asarray(plan.limits)))
        self._draft_signatures.add(
            (plan.tokens.shape, plan.tables.shape))
        props = np.asarray(props)
        for sid in plan.slot_ids:
            q = int(plan.decode_cols[sid])
            if q > 1:
                plan.tokens[sid, 1:q] = props[sid, :q - 1]
        return np.asarray(dlps)

    def _strategies_args(self, plan, iteration):
        """The strategies step's extra feeds for one iteration: the
        guided-decoding mask ((S, V) plain, (S, C, V) per-column —
        all-zero rows for unconstrained lanes) plus the sampling
        control arrays the scheduler planned. Per-column guided lanes
        advance a SCRATCH automaton state through the fed draft tokens
        so each verify column is masked under the context it would
        commit under (the real state only advances in commit). Chaos
        mask-starve narrows every guided row to its single lowest
        allowed token — conformance holds, the loop must survive."""
        s, c = plan.tokens.shape
        per_col = self._spec is not None
        mask = np.zeros((s, c, self._vocab) if per_col
                        else (s, self._vocab), np.float32)
        starve = (bool(plan.guided_lanes) and self._chaos is not None
                  and self._chaos.mask_starves_at(iteration))
        starved_any = False

        def _narrow(row):
            allowed = np.flatnonzero(row > NEG_INF / 2)
            out = np.full_like(row, np.float32(NEG_INF))
            if allowed.size:
                out[allowed[0]] = 0.0
            return out

        for sid, req in plan.guided_lanes or ():
            state = req.guided_state
            if state is None:
                continue        # dead automaton (chaos): unconstrained
            eos = req.eos_id if req.group is None else req.group.eos_id
            row = req.guided.mask_row(state, eos)
            if starve:
                row = _narrow(row)
                starved_any = True
            if not per_col:
                mask[sid] = row
                continue
            q = int(plan.decode_cols[sid])
            if q == 0:
                # prefill lane: only its LAST valid column's row is
                # read downstream; filling every column is harmless
                mask[sid, :] = row
                continue
            mask[sid, 0] = row
            st = state
            for j in range(1, q):
                if st is not None:
                    st = req.guided.advance(st,
                                            int(plan.tokens[sid, j]))
                if st is not None:
                    row = req.guided.mask_row(st, eos)
                mask[sid, j] = row      # dead: repeat the last mask
        if starved_any:
            self._chaos.mask_starve_applied()
        do_sample, temperature, top_k, top_p, keys = plan.sample_ctl
        return (jnp.asarray(mask), jnp.asarray(keys),
                jnp.asarray(temperature), jnp.asarray(do_sample),
                jnp.asarray(top_k), jnp.asarray(top_p))

    def _kernel_info(self):
        # constant after the first step: built once, reused by every
        # flight entry instead of a fresh dict per iteration
        info = self.__dict__.get("_kernel_info_cache")
        if info is None or info["engaged"] is None:
            info = {"mode": self._kernel_mode,
                    "engaged": self._kernel_engaged}
            self._kernel_info_cache = info
        return info

    def _nan_block(self, layer, block):
        """Chaos primitive: make `block`'s keys read as NaN. Dense
        pools take the NaN in the K lanes of their rows; quantized
        pools take it in
        the k_scale rows instead — an int8 array cannot hold a NaN, but
        NaN * any code dequantizes to NaN, so the poison propagates
        through the SAME attention arithmetic on both layouts."""
        pool = self.cache.pools[layer]
        if "k_scale" in pool:
            pool["k_scale"] = pool["k_scale"].at[block].set(jnp.nan)
        else:
            # the K lanes of a K-beside-V row; of a latent row, lanes
            # of the c_kv every head's score reads
            pool["kv"] = pool["kv"].at[
                block, :, :, :pool["kv"].shape[-1] // 2].set(jnp.nan)

    def _poison_kv(self, layer, lanes):
        """Chaos hook: NaN the first KV block of the oldest ACTIVE lane
        that has advanced past position 0 (its block 0 is attended by
        every later position, so the NaN propagates through real
        attention arithmetic into that lane's logits this iteration).
        Returns False when no lane qualifies — the caller defers."""
        lanes = lanes if lanes is not None else \
            self._sched.lane_snapshot()
        # lanes are LANE_FIELDS-order tuples:
        # (slot, rid, pos, prefilling, admit_seq, generated, first_block)
        victims = sorted((l for l in lanes if l[2] >= 1),
                         key=lambda l: l[4])
        if not victims:
            return False
        self._nan_block(layer, victims[0][6])
        return True

    def _on_engine_fault(self, plan, iteration, logps, lanes):
        """A fused step produced non-finite logits on a live lane: dump
        the flight recorder (its LAST entry is this iteration, fault-
        annotated), fail every outstanding request, close the server,
        and raise a structured NonFiniteError. A poisoned pool is
        unrecoverable — every later step reads the bad blocks — so
        fail-stop + postmortem artifact beats serving garbage."""
        from ..robustness.guard import NonFiniteError
        bad = [int(s) for s in plan.slot_ids
               if not np.all(np.isfinite(logps[s]))]
        if lanes is None:       # telemetry off: plan carries no lane
            lanes = self._sched.lane_snapshot()     # detail — cold path
        # lanes are LANE_FIELDS-order tuples: l[0]=slot, l[1]=rid
        by_slot = {l[0]: l for l in (lanes or ())}
        bad_rids = [by_slot[s][1] for s in bad if s in by_slot]
        tel = self._tel
        dump = None
        if tel is not None:     # postmortem artifact wants telemetry;
            #                     the fail-stop itself does not
            tel.flight.record(
                iteration, kind="iteration", aborted=True,
                lanes=list(plan.slot_ids),
                emitting=sorted(plan.emitting),
                prefill_tokens=plan.prefill_tokens, lanes_detail=lanes,
                blocks_free=self.cache.num_free,
                blocks_in_use=self.cache.num_used,
                kernel={"mode": self._kernel_mode,
                        "engaged": self._kernel_engaged})
            dump = tel.fault(iteration, "non_finite_logits",
                             {"bad_slots": bad, "bad_rids": bad_rids,
                              "iteration": iteration})
        err = NonFiniteError(
            f"serving.logits[slot {bad[0]}]", iteration,
            [f"serving.logits[slot {s}]" for s in bad])
        err.flight_dump = dump
        # fault ATTRIBUTION for the fleet router: the replica-local
        # request ids whose lanes actually went non-finite. cancel_all
        # fails EVERY in-flight future with this same error, and the
        # router's poison-quarantine lineage must implicate only the
        # requests that were in the blast center — innocent bystanders
        # fail over without a strike (serving/router.py)
        err.bad_rids = bad_rids
        self._fail_stop(err)
        raise err

    def _fail_stop(self, err):
        """The engine cannot go on: record `err` as its fault, refuse
        new work, and fail every outstanding request with it."""
        self._fault = err
        with self._rid_lock:
            self._closed = True
        self._sched.cancel_all(err)

    def run_until_idle(self, max_iterations=100000):
        """Pump step() until no lane has work (manual-drive mode)."""
        n = 0
        while self.step():
            n += 1
            if n >= max_iterations:
                raise RuntimeError(
                    f"serving loop did not drain in {max_iterations} "
                    f"iterations")
        return n

    def _check_kernel_engagement(self):
        """Runs once, right after the first fused-step trace: if the
        dispatch mode says the Pallas kernel should serve this pool
        dtype but the trace took the reference path (or vice versa when
        it is pinned off), fail LOUDLY now — not after a benchmark run
        reports reference numbers as kernel numbers."""
        traced, fell_back = self._kernel_counts
        self._kernel_engaged = traced > 0 and fell_back == 0
        # the first layer whose pool is blocks (a state layer's kernel
        # qualifies on its dtypes alone, and its state is float32)
        p0 = next((p for p in self.cache.pools if "kv" in p), None)
        if p0 is None:
            return
        kvp = p0["kv"]
        # the probe q uses the COMPUTE dtype (what the fused step feeds
        # the dispatcher) — an int8 pool's queries are never int8
        # the probe q is shaped like the real step's queries ((1, H, 1,
        # D) — the GQA-relaxed supported() check needs the true head
        # relation, a (1, 1, 1, 1) probe would fail it for any H_kv > 1)
        # (a latent pool's queries are as wide as its rows)
        latent = self.cache.latent
        expected = (self._kernel_mode != "off" and
                    _kvc.paged_kernel_supported(
                        jnp.zeros((1, self.model.num_heads, 1,
                                   kvp.shape[3] if latent
                                   else self.cache.head_dim),
                                  self.cache.compute_dtype), kvp,
                        p0.get("k_scale"), p0.get("v_scale"),
                        latent=latent))
        if expected and not self._kernel_engaged:
            raise RuntimeError(
                "paged attention kernel was expected "
                f"(PADDLE_TPU_PAGED_KERNEL={self._kernel_mode}, "
                f"pool dtype {kvp.dtype}) but the fused step traced "
                f"{traced} kernel / {fell_back} reference dispatches")
        if not expected and traced > 0:
            raise RuntimeError(
                "paged attention kernel engaged although the dispatch "
                "mode pinned it off")

    def _publish_gauges(self):
        st = self._sched
        self._m["queue_depth"].set(st.queue_depth)
        self._m["active_slots"].set(st.active_count)
        self._m["blocks_in_use"].set(self.cache.num_used)
        self._publish_tier_gauges()

    def _publish_tier_gauges(self):
        if self._tier_gauges is None:
            return
        g = self._tier_gauges
        g["serving.kv.tier.spills"].set(self.cache.host_spills)
        g["serving.kv.tier.swap_ins"].set(self.cache.host_swap_ins)
        g["serving.kv.tier.preempts"].set(self._sched.preempts)
        g["serving.kv.tier.resumes"].set(self._sched.resumes)
        g["serving.kv.tier.reprefills_avoided"].set(
            self._prefix.counts["reprefills_avoided"]
            if self._prefix is not None else 0)

    def _serve(self):
        while True:
            try:
                did = self.step()
            except Exception as e:
                if self._fault is None:
                    # nobody can pump this engine again once its thread
                    # is gone: fail the futures with what killed it,
                    # where they used to wait for ever
                    self._fail_stop(e)
                    raise
                # fail-stopped by the step itself (_on_engine_fault
                # after non-finite logits, or a fused call that died
                # with the pools): every future is failed and the
                # server closed, so the worker just exits (clients
                # observe the error on their futures;
                # get_stats()["engine_fault"] records it)
                return
            if did:
                continue
            with self._cv:
                if self._closed:
                    return
                if not self._sched.has_work():
                    # short timeout: queued-request deadlines under a
                    # REAL clock must still fire while the pool idles
                    self._cv.wait(timeout=0.05)

    # -- lifecycle ---------------------------------------------------------
    def close(self, drain=True, timeout=60):
        """Stop accepting submits; by default finish every in-flight
        and queued request first (graceful drain), then stop the
        worker. drain=False fails outstanding requests instead."""
        with self._rid_lock:
            if self._closed:
                # already closed (or fault-stopped): still release the
                # telemetry endpoint if one is mounted, this server's
                # SLO gauge series, and its HBM-ledger rows
                # (_on_engine_fault sets _closed without reaching the
                # normal teardown below — a dead server must not report
                # stale window quantiles or live pool bytes; every
                # release here is idempotent)
                if self._exporter is not None:
                    self._exporter.close()
                    self._exporter = None
                if self._tel is not None:
                    self._tel.close()
                from ..observability.compile_insight import hbm_ledger
                hbm_ledger().retire(self._ledger_id)
                self._retire_mesh_gauges()
                if self._prefix is not None:
                    self._prefix.drop_gauges()
                return
            if not drain:
                self._sched.cancel_all(RequestCancelled(
                    "GenerationServer closed without drain"))
            self._closed = True
        if self._worker is not None:
            deadline = time.monotonic() + timeout
            while drain and self._sched.has_work() and \
                    time.monotonic() < deadline:
                with self._cv:
                    self._cv.notify()
                time.sleep(0.01)
            with self._cv:
                self._cv.notify()
            self._worker.join(timeout=max(0.0,
                                          deadline - time.monotonic()))
        elif drain:
            self.run_until_idle()
        self._publish_gauges()
        if self._exporter is not None:
            self._exporter.close()
            self._exporter = None
        if self._tel is not None:
            self._tel.close()       # drop this server's SLO gauge series
        from ..observability.compile_insight import hbm_ledger
        hbm_ledger().retire(self._ledger_id)    # and its memory.* rows
        self._retire_mesh_gauges()              # and its serving.mesh.*
        if self._prefix is not None:            # and its prefix gauge
            self._prefix.drop_gauges()

    def _retire_mesh_gauges(self):
        """Drop this server's serving.mesh.*, serving.kv.quant.* AND
        serving.kv.tier.* gauge series (idempotent; called from BOTH
        close paths — a dead server must not keep reporting a live
        shard footprint, a quantization saving, or host-tier traffic)."""
        reg = global_registry()
        for name in (self._mesh_gauges or ()):
            reg.gauge(name).remove(server=self._ledger_id)
        self._mesh_gauges = None
        for name in (self._quant_gauges or ()):
            reg.gauge(name).remove(server=self._ledger_id)
        self._quant_gauges = None
        for name in (self._tier_gauges or ()):
            reg.gauge(name).remove(server=self._ledger_id)
        self._tier_gauges = None
        if self._state_gauge is not None:
            reg.gauge("serving.state.bytes").remove(
                server=self._ledger_id)
            self._state_gauge = None

    def get_stats(self):
        """Scheduler + engine stats; `fused_step_signatures` is the jit
        signature count — the shape-static design's acceptance gauge
        (exactly 1 after warmup, whatever the request mix)."""
        st = self._sched.stats()
        st["fused_step_signatures"] = len(self._signatures)
        st["chunk"] = self._sched.chunk
        st["block_size"] = self.block_size
        st["max_context"] = self.max_context
        # speculative decoding: the compiled-signature budget for the
        # whole server lifetime is fused + draft (<= 2; the acceptance
        # gauge alongside fused_step_signatures == 1)
        st["draft_step_signatures"] = len(self._draft_signatures)
        st["compiled_step_signatures"] = (len(self._signatures)
                                          + len(self._draft_signatures))
        proposed = st.pop("spec.proposed", 0)
        accepted = st.pop("spec.accepted", 0)
        if self._spec is not None:
            st["spec"] = {
                "k": self._spec.k,
                "mode": self._spec.mode,
                "proposed": proposed,
                "accepted": accepted,
                "accept_rate": round(accepted / max(proposed, 1), 4),
                "draft_step_signatures": len(self._draft_signatures),
            }
        else:
            st["spec"] = None
        traced, fell_back = self._kernel_counts
        st["kernel"] = {
            # the mode the fused step actually TRACED under — a later
            # env flip must not make a server misreport its compiled
            # path (None until the first step)
            "mode": self._kernel_mode,
            "engaged": self._kernel_engaged,
            # kernel generation the first trace dispatched to ("v1" /
            # "v2"; None when nothing engaged) — mirrors the
            # serving.kernel.version gauge
            "version": self._kernel_version,
            # the kernel's name as a device trace has it: the latent
            # walk is "paged_latent_attention", of generation "v2"
            "name": self._kernel_name,
            "kernel_dispatches": traced,
            "fallback_dispatches": fell_back,
            # what one table entry addresses, and the walk copies a
            # group of: (H_kv, block_size, 2 * head_dim), K beside V,
            # or a latent layer's (1, block_size, W), one row a token
            # (a fact for whoever reads a trace, not a switch)
            # of the first layer that has blocks (a state layer has
            # none)
            "pool_block_shape": next(
                (list(shp[1:]) for shp in self.cache.layer_shapes
                 if shp is not None), None),
        }
        # quantized-pool facts (None when dense): the TRUE int8+scales
        # footprint, the dense compute-dtype size the same blocks would
        # cost, and their ratio — the acceptance gauge for the ~2x
        # capacity claim (scales included, never hidden)
        if self.cache.quantized:
            pb, db = self.cache.pool_bytes(), \
                self.cache.dense_pool_bytes()
            st["kv_quant"] = {
                "kv_dtype": self.cache.kv_dtype,
                "compute_dtype": str(np.dtype(
                    self.cache.compute_dtype)),
                "pool_bytes": pb,
                "scale_bytes": self.cache.scale_bytes(),
                "dense_equiv_bytes": db,
                "bytes_ratio_vs_dense": round(pb / db, 4),
                "int8_weights": getattr(self.model, "int8_weights", 0),
            }
        else:
            st["kv_quant"] = None
        # tiered-KV facts (None without a host tier): capacity, the
        # device/host byte split, and the cumulative tier traffic —
        # reprefills_avoided is the host tier's whole value proposition
        # in one number
        if self.cache.host is not None:
            st["kv_tier"] = {
                "host_blocks": self.cache.host.num_blocks,
                "host_blocks_used": self.cache.host.num_used,
                "host_pool_bytes": self.cache.host_pool_bytes(),
                "device_pool_bytes": self.cache.pool_bytes(),
                "spills": self.cache.host_spills,
                "swap_ins": self.cache.host_swap_ins,
                "preempts": self._sched.preempts,
                "resumes": self._sched.resumes,
                "preempted_depth": st.get("preempted_depth", 0),
                "reprefills_avoided":
                    self._prefix.counts["reprefills_avoided"]
                    if self._prefix is not None else 0,
            }
        else:
            st["kv_tier"] = None
        # decode strategies (ISSUE 20): whether this server compiled
        # the sampling/guided step — fork groups, beam, and guided
        # submits require it (NotImplementedError otherwise)
        st["decode_strategies"] = self._strategies
        st["telemetry_enabled"] = self._tel is not None
        st["slo"] = self._tel.stats() if self._tel is not None else None
        st["tenants"] = (self._tel.tenants.snapshot()
                         if self._tel is not None else None)
        st["engine_fault"] = repr(self._fault) if self._fault else None
        if self.mesh is None:
            st["mesh"] = None
        else:
            st["mesh"] = {
                "axis": self.mesh_axis,
                "tp": int(self.mesh.shape[self.mesh_axis]),
                "devices": [str(d) for d in self.mesh.devices.flat],
                "pool_bytes": self.cache.pool_bytes(),
                "shard_pool_bytes": self.cache.shard_pool_bytes(),
                "psums_per_step": 2 * self.model.num_layers,
            }
        from ..observability.compile_insight import hbm_ledger
        # this server's HBM-ledger rows (kv_cache/params/peak_hbm);
        # empty once close() retired them
        st["memory"] = hbm_ledger().component_bytes(self._ledger_id)
        return st

    def check_slo(self, targets):
        """Burn-rate check over the cumulative SLO digests, e.g.
        ``check_slo({"ttft_ms": {"p99": 250.0}, "itl_ms": {"p50": 40}})``
        -> {"ok": bool, "checks": [...]}; see SLOTracker.check_slo."""
        if self._tel is None:
            raise RuntimeError(
                "check_slo needs telemetry; this server was built with "
                "telemetry=False")
        return self._tel.check_slo(targets)

    @property
    def telemetry(self):
        """The ServingTelemetry (SLO digests + flight recorder), or
        None when disabled."""
        return self._tel

    def health(self):
        """The /healthz payload as a plain dict — the SAME semantics
        in-process, so a fleet router health-checks its replicas
        without HTTP round-trips (serving/replica.py): status is
        "fault" once an engine fault latched, "closed" after close(),
        "ok" otherwise."""
        status = ("fault" if self._fault
                  else "closed" if self._closed else "ok")
        return {"status": status,
                "engine_fault": repr(self._fault)
                if self._fault else None,
                "pending": self.pending(),
                "iteration": self._sched.iteration}

    def serve_metrics(self, port=0, host=None):
        """Mount the stdlib telemetry endpoint (/metrics Prometheus
        exposition, /healthz, /slo, /series, /tenants) for this
        server. Binds loopback by
        default (docs/observability.md security note); returns the
        running TelemetryServer (.port, .url, .close()). Closed with
        the engine. Idempotent while a mount is live — but asking for a
        DIFFERENT explicit port/host than the live mount raises instead
        of silently returning the old endpoint (a scrape config pointed
        at the requested port would get connection-refused while this
        call looked successful)."""
        from ..observability.exporter import (check_remount,
                                              serve_metrics as _serve)
        if self._exporter is not None and not self._exporter.closed:
            check_remount(self._exporter, port, host)
            return self._exporter        # live mount: idempotent
        # health_fn overrides the handler's default "ok": a faulted or
        # closed engine must not scrape healthy (health() is the same
        # payload the fleet router reads in-process)
        self._exporter = _serve(
            port=port, host=host or "127.0.0.1",
            slo_fn=lambda: (self._tel.stats()
                            if self._tel is not None else {}),
            health_fn=self.health,
            series_fn=lambda: (
                self._tel.series.payload()
                if self._tel is not None and self._tel.series
                is not None else None),
            tenants_fn=lambda: (self._tel.tenants.snapshot()
                                if self._tel is not None else {}))
        return self._exporter
