"""Headline benchmark: ERNIE-1.0 (BERT-base-sized) pretraining
tokens/sec/chip (bf16, seq 512) — BASELINE.json's named headline metric.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} where
value is tokens/sec/chip at the best batch size of a small sweep and the
extra keys make the number auditable against BASELINE.json's >=35%-MFU north
star: "mfu" (achieved vs chip peak bf16 FLOP/s, model FLOPs counted
analytically via utils/model_stat.count_flops x3 for fwd+bwd),
"flash_engaged" (the Pallas attention kernel actually traced — a dead
kernel means the O(T^2) fallback silently ate the HBM win), "batch", and
the per-batch sweep.

Baseline (SURVEY.md §6 / BASELINE.json): the reference published no TPU
numbers, so vs_baseline compares against the reference-era published V100
fp32 per-card figure for BERT-base pretraining, ~2800 tokens/sec/card.

The whole train step (fwd + grad + adam) runs as ONE donated XLA executable
via the framework Executor; matmul path is bf16 (amp cast_model_to_bf16),
params/accum fp32.

The model sweep is a device measurement: without a TPU it exits
non-zero and prints no metric line — there is no CPU fallback. The
BENCH_*_COMPARE / BENCH_*_SAMPLE modes are host-side micro-comparisons
that run on whatever backend jax has. Every emitted JSON line names
"platform", "device_kind" and "device_count". The compile cache lives
where paddle_tpu/utils/compile_cache.py says (JAX_COMPILATION_CACHE_DIR
if set, else <checkout>/.jax_cache).

Env knobs: BENCH_MODEL (ernie [default] | bert | packed — packed-sequence
MLM, value counts REAL tokens/sec | gpt | gpt_decode — encoders
share a graph; uniform-random feed | gpt_prefill — whole-prompt KV fill,
MXU-bound serving metric | resnet — secondary images/sec metric),
BENCH_SEQ_LEN, BENCH_BATCHES (default "8,16"; pass "8,16,32" for the
full sweep), BENCH_STEPS (default 15),
BENCH_RECOMPUTE (remat policy: dots|nothing|offload),
BENCH_TINY=1 (bert_tiny config), BENCH_PEAK_TFLOPS (override the
per-chip peak),
BENCH_DUMP_HLO=<path> (archive the best batch's optimized HLO),
BENCH_HBM_FRACTION (pre-flight prune threshold, default 0.92),
BENCH_SERVING_COMPARE=1 (continuous vs static
batching on a mixed-length generation stream, plus the paged-attention
Pallas-kernel vs pure-JAX-reference step-time comparison, plus —
given >= 2 devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_
count=2 — the tp=1-vs-tp=2 mesh-sharded GenerationServer parity/
overhead section; knobs BENCH_SERVING_{REQUESTS,SLOTS,CHUNK,BLOCK,ROUNDS};
BENCH_SLO_SAMPLE=<path> additionally scrapes the live /metrics + /slo
endpoint mid-bench and lands the sample there),
BENCH_TELEMETRY_COMPARE=1 (request-level telemetry on-vs-off engine
overhead; knobs BENCH_TELEMETRY_{REQUESTS,SLOTS,ROUNDS}; acceptance
< 5%), BENCH_PREFIX_COMPARE=1 (prefix-cache on-vs-off over a
mixed-tenant stream with 80% shared prefixes: tokens/s,
blocks-allocated/request, prefix hit rate, plus a spec-decode section;
knobs BENCH_PREFIX_{REQUESTS,SLOTS,ROUNDS}; acceptance:
blocks/request strictly below the no-sharing engine and hit rate
> 0.5), BENCH_TIER_COMPARE=1 (tiered KV cache on-vs-off: host-RAM
spill pool + swap-aware preempt/resume through a starved device
pool — prefix hit rate, re-prefills avoided, peak admitted
concurrency vs the full-reservation baseline, p99 TTFT, ids pinned
bitwise across arms; knobs
BENCH_TIER_{REQUESTS,ROUNDS,BLOCKS,HOST_BLOCKS}), BENCH_FORK_COMPARE=1
(COW-forked generation: submit(n=K) fork groups vs K independent
submits of the same stream — peak-block ratio, tokens/s, COW copies —
plus paged-beam-vs-dense bitwise parity and a guided-regex section on
the same compiled signature; knobs BENCH_FORK_{K,PROMPTS,ROUNDS}),
BENCH_FLEET_COMPARE=1 (fleet router: affinity-vs-random
routing hit rate/blocks per request over a multi-tenant hot/cold
prefix storm + p99 TTFT under overload with vs without SLO-burn-rate
shedding; knobs BENCH_FLEET_{REQUESTS,REPLICAS,SLOTS,OVERLOAD}),
BENCH_CHAOS_RECOVERY=1 (self-healing fleet under a scripted
kill + hang + poison storm: worst time-to-full-strength in router
iterations x 20 ms nominal, goodput fraction, quarantine facts;
knobs BENCH_CHAOS_{REQUESTS,REPLICAS,SLOTS}; deterministic injected
clocks), BENCH_AUTOSCALE_COMPARE=1 (SLO-driven autoscaler over a
diurnal load: the SAME alternating peak/trough stream into a fleet
fixed at the floor, one fixed at the ceiling, and the autoscaled
fleet — peak TTFT p99 per arm + replica-iterations paid; knobs
BENCH_AUTOSCALE_{CYCLES,PEAK,TROUGH,MAX}; deterministic injected
clocks), BENCH_TRACE_COMPARE=1 (fleet-wide distributed tracing
on-vs-off: the SAME mixed-length stream through two 2-replica fleets,
one with a live trace capture (sampling all) and one with tracing off
— median of block-paired best-of ratios, ids pinned bitwise across
modes; knobs BENCH_TRACE_{REQUESTS,REPLICAS,SLOTS,ROUNDS}; acceptance
< 5%), BENCH_COMPILE_SAMPLE=1 (compile-observatory artifact: a tiny-GPT
Executor.explain() report, a provoked recompile storm with its key
diffs, the HBM-ledger snapshot, and the recompile-detector on-vs-off
steady-state overhead; knobs BENCH_COMPILE_{STEPS,ROUNDS,SEQ};
acceptance < 5% — the detector does NOTHING on cache hits, so the
steady-state delta is pure noise floor, and per-miss bookkeeping is
timed directly in microseconds).
"""

import json
import os
import sys
import tempfile
import time

V100_BERT_BASE_TOKENS_PER_SEC = 2800.0
# reference-era published V100 fp32 ResNet-50 training throughput/card
V100_RESNET50_IMAGES_PER_SEC = 360.0

# bf16 peak TFLOP/s per chip by device_kind substring (public specs).
PEAK_TFLOPS = [
    ("v2", 45.0),
    ("v3", 123.0),
    ("v4", 275.0),
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v6e", 918.0),
]

# platform / device_kind / device_count of this process, filled by
# main() and stamped on every emitted line: a CPU number must never be
# readable as a device number
_DEVICE = {}


def _with_device(result):
    result.update(_DEVICE)
    return result


def _peak_flops(device_kind):
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    kind = device_kind.lower()
    best = None
    for sub, tf in PEAK_TFLOPS:
        if sub in kind:
            best = tf
    if best is None:
        raise SystemExit(
            f"bench: device_kind {device_kind!r} is not in PEAK_TFLOPS "
            f"— add it with its source (or set BENCH_PEAK_TFLOPS); an "
            f"MFU against a guessed peak is not a measurement")
    return best * 1e12


def _compile_train_step(build_net, make_feed, make_opt, batch):
    """Shared bench scaffold: build program + optimizer (with the
    BENCH_RECOMPUTE wrap), count FLOPs, cast bf16, init, and return
    (step_fn, train_flops_per_step)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.utils import model_stat
    from paddle_tpu import amp

    def _phase(msg):
        print(f"bench: [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

    _phase("building program")
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        loss = build_net()
        opt = make_opt()
        # BENCH_RECOMPUTE=dots|nothing|offload: remat to fit bigger
        # batches (the usual MFU lever once HBM binds)
        rc = os.environ.get("BENCH_RECOMPUTE")
        if rc:
            opt = fluid.optimizer.RecomputeOptimizer(opt, policy=rc)
        opt.minimize(loss)
    # forward model FLOPs for this batch; training step ~ 3x (fwd + 2x bwd)
    _phase("counting flops + bf16 cast")
    fwd_flops, _per_op = model_stat.count_flops(main, batch_size=batch)
    amp.cast_model_to_bf16(main)

    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    _phase("running startup program (param init on device)")
    with scope_guard(scope):
        exe.run(startup)
    _phase("startup done; making feed")
    import jax
    from paddle_tpu.core.executor import _canon_feed
    # move the static bench batch to device ONCE (int64 policy applied
    # at the boundary first): the timed loop then measures the train
    # step itself, not N re-uploads of the same buffers — the
    # framework's device_prefetch path gives real input pipelines the
    # same overlap (core/executor.py train_from_dataset)
    feed = {k: jax.device_put(_canon_feed(k, v))
            for k, v in make_feed().items()}

    def step():
        # return_numpy=False keeps fetches as jax.Arrays so successive
        # steps pipeline under async dispatch; callers block once at
        # the end of the timed window (the standard JAX measurement)
        with scope_guard(scope):
            return exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)

    step.executor = exe
    return step, 3 * fwd_flops


def build_resnet_step(batch, image_size=224):
    """Secondary benchmark (SURVEY.md §6): ResNet-50 images/sec/chip."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    tiny = os.environ.get("BENCH_TINY") == "1"
    depth = 18 if tiny else 50
    if tiny:
        image_size = min(image_size, 64)
    rng = np.random.default_rng(0)

    def build_net():
        _i, _l, _p, loss, _a1, _a5 = resnet.build_train_net(
            depth=depth, image_shape=(3, image_size, image_size))
        return loss

    def make_feed():
        return {"img": rng.standard_normal(
            (batch, 3, image_size, image_size)).astype(np.float32),
            "label": rng.integers(0, 1000, (batch, 1)).astype(np.int64)}

    RUN_INFO.update(image_size=image_size, depth=depth)
    step, flops = _compile_train_step(
        build_net, make_feed,
        lambda: fluid.optimizer.MomentumOptimizer(learning_rate=0.1,
                                                  momentum=0.9), batch)
    return step, batch, flops          # units = images


def build_transformer_step(batch, seq_len):
    """BASELINE config #3: Transformer-base WMT14 En-De tokens/sec/chip."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    tiny = os.environ.get("BENCH_TINY") == "1"
    max_len = min(seq_len, 32 if tiny else 256)

    class _Cfg(transformer.ModelHyperParams):
        if tiny:
            src_vocab_size = 256
            trg_vocab_size = 256
            d_model = 64
            d_inner_hid = 128
            n_head = 2
            n_layer = 2
        dropout = 0.0          # deterministic timing

    rng = np.random.default_rng(0)

    def build_net():
        feeds, avg_loss, _tok = transformer.build_train_net(
            cfg=_Cfg, max_len=max_len)
        return avg_loss

    def make_feed():
        v = _Cfg.src_vocab_size
        return {
            "src_ids": rng.integers(2, v, (batch, max_len)).astype(np.int32),
            "src_len": np.full((batch, 1), max_len, np.int32),
            "tgt_ids": rng.integers(2, v, (batch, max_len)).astype(np.int32),
            "tgt_len": np.full((batch, 1), max_len, np.int32),
            "lbl_ids": rng.integers(2, v, (batch, max_len)).astype(np.int32),
        }

    RUN_INFO["seq_len"] = max_len
    step, flops = _compile_train_step(
        build_net, make_feed,
        lambda: fluid.optimizer.AdamOptimizer(learning_rate=1e-4), batch)
    return step, batch * max_len, flops          # units = tokens


def build_gpt_step(batch, seq_len):
    """Decoder-only LM (models/gpt.py): causal-attention tokens/sec/chip
    — the flash-causal training path the encoder benches don't hit."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt

    tiny = os.environ.get("BENCH_TINY") == "1"
    if tiny:
        cfg = gpt.gpt_tiny()
        seq_len = min(seq_len, cfg.max_position)
    else:
        cfg = gpt.GPTConfig(max_position=max(seq_len, 1024), dropout=0.0)
    rng = np.random.default_rng(0)

    def build_net():
        _tok, loss, _logits = gpt.build_lm_net(cfg, seq_len=seq_len)
        return loss

    def make_feed():
        return {"tokens": rng.integers(
            3, cfg.vocab_size, (batch, seq_len)).astype(np.int32)}

    RUN_INFO["seq_len"] = seq_len
    step, flops = _compile_train_step(
        build_net, make_feed,
        lambda: fluid.optimizer.AdamOptimizer(learning_rate=1e-4), batch)
    return step, batch * seq_len, flops          # units = tokens


def build_deepfm_step(batch):
    """BASELINE config #5: DeepFM CTR examples/sec/chip (sparse embedding
    + all-reduce-of-sparse-grads stress)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm

    tiny = os.environ.get("BENCH_TINY") == "1"
    nf = 10_000 if tiny else 1_000_000
    fields = 39
    rng = np.random.default_rng(0)

    def build_net():
        _i, _v, _l, avg_loss, _p = deepfm.build_train_net(
            num_features=nf, num_fields=fields, embed_dim=10)
        return avg_loss

    def make_feed():
        return {
            "feat_ids": rng.integers(0, nf, (batch, fields)).astype(np.int32),
            "feat_vals": rng.random((batch, fields)).astype(np.float32),
            "label": rng.integers(0, 2, (batch, 1)).astype(np.float32),
        }

    RUN_INFO["num_features"] = nf
    step, flops = _compile_train_step(
        build_net, make_feed,
        lambda: fluid.optimizer.AdamOptimizer(learning_rate=1e-3), batch)
    return step, batch, flops          # units = examples


def build_packed_pretrain_step(batch, seq_len):
    """Packed-MLM pretraining: the value counts REAL (non-pad)
    tokens/sec. Each row carries several short documents (lengths
    seq_len/8..seq_len/2, the short-corpus regime) kept independent by
    the in-kernel segment mask; the padded reference recipe on the same
    corpus would spend ~70% of its row slots on padding, so matching
    hardware MFU here means ~3x the useful-token throughput."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    if os.environ.get("BENCH_TINY") == "1":
        cfg = bert.bert_tiny()
        seq_len = min(seq_len, cfg.max_position_embeddings)
    else:
        cfg = bert.BertConfig(max_position_embeddings=seq_len)
    RUN_INFO["seq_len"] = seq_len

    # enough documents to fill `batch` rows, then trim to the static
    # sweep shape (mask_pos entries are per-row, so trimming is safe)
    n_docs = max(2, batch * 2)
    feed, n_rows = bert.make_packed_pretrain_feed(cfg, seq_len, n_docs,
                                                  seed=0)
    while n_rows < batch:
        n_docs *= 2
        feed, n_rows = bert.make_packed_pretrain_feed(cfg, seq_len, n_docs,
                                                      seed=0)
    feed = {k: v[:batch] for k, v in feed.items()}
    real_tokens = int((feed["segment_ids"] > 0).sum())
    RUN_INFO["packing_efficiency"] = round(real_tokens / (batch * seq_len),
                                           4)

    def build_net():
        _feeds, loss = bert.build_packed_pretrain_net(
            cfg, seq_len=seq_len,
            max_predictions=feed["mask_pos"].shape[1])
        return loss

    step, flops = _compile_train_step(
        build_net, lambda: feed,
        lambda: fluid.optimizer.AdamOptimizer(learning_rate=1e-4), batch)
    return step, real_tokens, flops              # units = REAL tokens


def build_step(batch, seq_len):
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import bert, ernie

    model = os.environ.get("BENCH_MODEL", "ernie")
    if model == "resnet":
        return build_resnet_step(batch)
    if model == "packed":
        return build_packed_pretrain_step(batch, seq_len)
    if model == "transformer":
        return build_transformer_step(batch, seq_len)
    if model == "deepfm":
        return build_deepfm_step(batch)
    if model == "gpt":
        return build_gpt_step(batch, seq_len)
    if model == "gpt_decode":
        return build_gpt_decode_step(batch, seq_len)
    if model == "gpt_prefill":
        return build_gpt_prefill_step(batch, seq_len)
    # "ernie" (default — BASELINE.json's named headline) and "bert" share
    # the encoder graph; ernie feeds go through the knowledge-masking
    # pipeline (models/ernie.py), bert feeds are uniform random.
    feed_mod = ernie if model == "ernie" else bert
    if os.environ.get("BENCH_TINY") == "1":
        cfg = bert.bert_tiny()
        seq_len = min(seq_len, cfg.max_position_embeddings)
    else:
        cfg = bert.BertConfig(max_position_embeddings=seq_len)
    RUN_INFO["seq_len"] = seq_len      # the clamped value that actually ran

    def build_net():
        feeds, total_loss, _mlm, _acc = bert.build_pretrain_net(
            cfg, seq_len=seq_len)
        return total_loss

    step, flops = _compile_train_step(
        build_net,
        lambda: feed_mod.make_pretrain_feed(cfg, seq_len, batch,
                                            dtype=np.int32),
        lambda: fluid.optimizer.AdamOptimizer(learning_rate=1e-4), batch)
    return step, batch * seq_len, flops          # units = tokens


def build_gpt_prefill_step(batch, seq_len):
    """Serving prefill benchmark: whole-prompt KV-cache fill in ONE
    flash forward (models/gpt.py build_prefill), prompt tokens/sec per
    chip. Compute-bound (MXU) unlike the bandwidth-bound decode — its
    MFU is meaningful."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt

    tiny = os.environ.get("BENCH_TINY") == "1"
    cfg = gpt.gpt_tiny() if tiny else gpt.GPTConfig(
        max_position=max(seq_len, 1024), dropout=0.0)
    p = min(seq_len, cfg.max_position)
    RUN_INFO["seq_len"] = p

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)     # materialize the params
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)
    params = gpt._cast_params(params, jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(
        3, cfg.vocab_size, (batch, p)).astype(np.int32))
    # ONE AOT compile serves both the timed step and the cost hook
    # (a jitted fn's cache is not shared with .lower().compile())
    prefill = jax.jit(gpt.build_prefill(params, cfg, p)).lower(
        prompt).compile()

    def step():
        cache, logits = prefill(prompt)
        return [logits[:, -1].astype(jnp.float32)]

    def _cost_analysis():
        ca = prefill.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return dict(ca or {})

    step.cost_analysis = _cost_analysis
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    d = cfg.hidden_size // cfg.num_heads
    # fwd-only: dense matmuls (2*N*tokens) + the causal attention term
    # (qk^T and pv: 4*H*P^2*D MACs/layer, x2 flops, /2 causal)
    flops = (2.0 * n_params * batch * p
             + cfg.num_layers * 4.0 * batch * cfg.num_heads * p * p * d
             / 2.0)
    return step, batch * p, flops


def build_gpt_decode_step(batch, seq_len):
    """Inference benchmark: KV-cache greedy decode, tokens generated
    per second per chip (the serving-side complement to the training
    headline; rides inference/decoding.py's lax.scan loop). Decode is
    memory-bandwidth-bound, so the reported MFU is expectedly tiny —
    tokens/s is the figure of merit."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt

    tiny = os.environ.get("BENCH_TINY") == "1"
    cfg = gpt.gpt_tiny() if tiny else gpt.GPTConfig()
    max_len = min(seq_len, cfg.max_position)
    RUN_INFO["seq_len"] = max_len

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)     # materialize the params
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)
    # the tested inference wiring, in serving dtype (bf16 weights+cache,
    # f32 softmax inside)
    decode = gpt.make_greedy_decoder(params, cfg, max_len,
                                     dtype=jnp.bfloat16)
    bos = jnp.zeros((batch,), jnp.int32)

    def step():
        return [decode(bos)[1]]     # scores (B,) f32

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    # fwd-only matmul FLOPs: 2 * params * tokens (attention-cache reads
    # are bandwidth, not FLOPs, at this scale)
    flops = 2.0 * n_params * batch * max_len
    return step, batch * max_len, flops


def run_async_compare(kind):
    """BENCH_ASYNC_COMPARE=1: the async-pipeline acceptance micro-bench
    (CPU backend, tiny MLP). Two comparisons, one JSON line:

    - headline `value`: steps/sec over a DYNAMIC-batch stream (32
      distinct batch sizes, several epochs) — the naive sync loop
      recompiles once per distinct shape, async+FeedBucketer holds the
      jit cache at <= 6 power-of-2 entries and pipelines dispatch.
      This is the workload the tentpole exists for, and the ratio is
      dominated by compile counts (32 vs 6), so it is robust to the
      +-15% scheduler noise of a shared 2-core container.
    - steady state: fixed-shape steps/sec for sync vs async vs
      async+bucketed (interleaved best-of-N rounds), reported alongside
      — the dispatch-overlap win alone. Expect ~0.9-1.3x HERE: the CPU
      "device" competes with the host for the same two cores, so there
      is no independent resource to overlap against (on TPU the device
      is separate silicon; see docs/performance.md).
    """
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.bucketing import FeedBucketer
    from paddle_tpu.core.executor import Scope, scope_guard

    # small-model regime on purpose: the per-step host sync the async
    # window removes is a FIXED cost, so the tiny config is where the
    # pipeline's effect is visible (and the acceptance bar lives)
    hidden = int(os.environ.get("BENCH_ASYNC_HIDDEN", 64))
    batch = int(os.environ.get("BENCH_ASYNC_BATCH", 64))
    steps = int(os.environ.get("BENCH_ASYNC_STEPS", 600))
    depth = int(os.environ.get("BENCH_ASYNC_LAYERS", 8))
    window = int(os.environ.get("BENCH_ASYNC_WINDOW", 2))
    rng = np.random.default_rng(0)

    # masked loss so the same program serves the fixed-shape loops AND
    # the bucketed dynamic-batch sweep: padded rows carry mask 0 and are
    # exact no-ops for loss and gradients
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[hidden], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        m = layers.data("batch_mask", shape=[1], dtype="float32")
        h = x
        for _ in range(depth):
            h = layers.fc(h, size=hidden, act="relu")
        per = layers.square_error_cost(layers.fc(h, size=1), y)
        loss = layers.reduce_sum(per * m) / layers.reduce_sum(m)
        fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    def fresh_exe():
        scope = Scope()
        exe = fluid.Executor(fluid.TPUPlace(0), async_window=window)
        with scope_guard(scope):
            exe.run(startup)
        return exe, scope

    def make_feed(n):
        return {"x": rng.standard_normal((n, hidden)).astype(np.float32),
                "y": rng.standard_normal((n, 1)).astype(np.float32),
                "batch_mask": np.ones((n, 1), np.float32)}

    feeds = [make_feed(batch) for _ in range(8)]   # rotate: no same-array
    #                                               shortcuts across modes

    def timed(fn, exe, scope, batches):
        with scope_guard(scope):
            fn(batches[0])                         # warm (compile done)
            exe.drain()                            # settle before timing
            t0 = time.perf_counter()
            for i in range(steps):
                fn(batches[i % len(batches)])
            exe.drain()   # close the window: dispatched steps completed
        return steps / (time.perf_counter() - t0)

    # three persistent mode setups, measured in interleaved rounds with
    # the per-mode BEST kept: a 2-core container shares its cycles with
    # whatever else runs, and one background burst must not decide which
    # MODE looks faster
    exe_s, scope_s = fresh_exe()           # 1. sync: numpy loss in hand
    exe_a, scope_a = fresh_exe()           # 2. async window
    exe_b, scope_b = fresh_exe()           # 3. async + FeedBucketer
    bucketer = FeedBucketer(mask_name="batch_mask")
    nomask = [{k: v for k, v in f.items() if k != "batch_mask"}
              for f in feeds]
    modes = {
        "sync": lambda r: timed(lambda f: exe_s.run(
            main, feed=f, fetch_list=[loss]), exe_s, scope_s, feeds),
        "async": lambda r: timed(lambda f: exe_a.run_async(
            main, feed=f, fetch_list=[loss]), exe_a, scope_a, feeds),
        "bucketed": lambda r: timed(lambda f: exe_b.run_async(
            main, feed=f, fetch_list=[loss], bucketer=bucketer),
            exe_b, scope_b, nomask),
    }
    rates = {name: 0.0 for name in modes}
    for _round in range(int(os.environ.get("BENCH_ASYNC_ROUNDS", 3))):
        for name, fn in modes.items():
            rates[name] = max(rates[name], fn(_round))
    sync_sps = rates["sync"]
    async_sps = rates["async"]
    bucketed_sps = rates["bucketed"]

    # 4. dynamic-batch stream — THE acceptance comparison. 32 DISTINCT
    #    batch sizes cycled for `epochs` passes:
    #    - naive sync loop: one XLA compile per distinct shape (32),
    #      numpy fetch + device sync every step;
    #    - async + FeedBucketer: power-of-2 padding holds the jit cache
    #      at <= 6 entries (1..32 -> {1,2,4,8,16,32}) and the in-flight
    #      window pipelines dispatch.
    sizes = list(range(1, 33))
    epochs = int(os.environ.get("BENCH_ASYNC_EPOCHS", 4))
    dyn_masked = [make_feed(n) for n in sizes]              # mask of ones
    dyn_nomask = [{k: v for k, v in f.items() if k != "batch_mask"}
                  for f in dyn_masked]
    n_dyn = len(sizes) * epochs

    exe_ds, scope_ds = fresh_exe()                          # sync baseline
    with scope_guard(scope_ds):
        t0 = time.perf_counter()
        for i in range(n_dyn):
            exe_ds.run(main, feed=dyn_masked[i % len(sizes)],
                       fetch_list=[loss])
        dyn_sync_sps = n_dyn / (time.perf_counter() - t0)
    sync_entries = exe_ds.get_stats()["jit_cache"]["size"] - 1  # - startup

    exe_d, scope_d = fresh_exe()                            # async+bucketed
    sweep_bucketer = FeedBucketer(mask_name="batch_mask")
    base_entries = exe_d.get_stats()["jit_cache"]["size"]       # startup fn
    with scope_guard(scope_d):
        t0 = time.perf_counter()
        stream = (dyn_nomask[i % len(sizes)] for i in range(n_dyn))
        dyn_out = list(exe_d.run_pipelined(
            main, stream, fetch_list=[loss], bucketer=sweep_bucketer,
            window=window, return_numpy=False))
        exe_d.drain()
        dyn_bucketed_sps = n_dyn / (time.perf_counter() - t0)
    dyn_entries = exe_d.get_stats()["jit_cache"]["size"] - base_entries
    assert len(dyn_out) == n_dyn

    speedup = dyn_bucketed_sps / dyn_sync_sps if dyn_sync_sps else None
    result = {
        "metric": "async_bucketed_speedup_vs_sync_dynamic_batches",
        "value": round(speedup, 3) if speedup else None,
        "unit": "x (async+bucketed steps/sec over the naive sync loop, "
                "32 distinct batch sizes)",
        "dynamic_batch_sizes": len(sizes),
        "dynamic_epochs": epochs,
        "dynamic_sync_steps_per_sec": round(dyn_sync_sps, 2),
        "dynamic_bucketed_steps_per_sec": round(dyn_bucketed_sps, 2),
        "dynamic_jit_cache_entries": dyn_entries,
        "dynamic_sync_jit_cache_entries": sync_entries,
        # steady-state fixed-shape rates (dispatch-overlap win alone)
        "steady_sync_steps_per_sec": round(sync_sps, 2),
        "steady_async_steps_per_sec": round(async_sps, 2),
        "steady_async_bucketed_steps_per_sec": round(bucketed_sps, 2),
        "steady_speedup": round(bucketed_sps / sync_sps, 3)
                          if sync_sps else None,
        "window": window, "batch": batch, "hidden": hidden,
        "steps": steps,
        "bucket_stats": sweep_bucketer.get_stats(),
        # provenance: each async-metrics block names the executor whose
        # workload it describes — the dynamic sweep (the headline) and
        # the steady fixed-shape loop are different runs
        "dynamic_async_metrics": exe_d.get_stats()["async"],
        "steady_async_metrics": exe_b.get_stats()["async"],
        "device_kind": kind,
    }
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_guard_compare(kind):
    """BENCH_GUARD_COMPARE=1: the robustness acceptance micro-bench
    (CPU backend, tiny MLP). Guarded vs unguarded steady-state step
    rate: the NaN/Inf sentinel is one fused isfinite reduction folded
    into the compiled step plus a one-bool-per-var host check riding
    the fetch, so the acceptance bar is overhead < 5%. Interleaved
    best-of-N rounds for the same reason as the async bench: a shared
    2-core container must not let one background burst decide which
    MODE looks faster."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard

    hidden = int(os.environ.get("BENCH_GUARD_HIDDEN", 64))
    batch = int(os.environ.get("BENCH_GUARD_BATCH", 64))
    steps = int(os.environ.get("BENCH_GUARD_STEPS", 400))
    depth = int(os.environ.get("BENCH_GUARD_LAYERS", 8))
    rng = np.random.default_rng(0)

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[hidden], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = x
        for _ in range(depth):
            h = layers.fc(h, size=hidden, act="relu")
        loss = layers.mean(layers.square_error_cost(
            layers.fc(h, size=1), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    def fresh_exe(guard):
        scope = Scope()
        exe = fluid.Executor(fluid.TPUPlace(0), guard=guard)
        with scope_guard(scope):
            exe.run(startup)
        return exe, scope

    feeds = [{"x": rng.standard_normal((batch, hidden)).astype(np.float32),
              "y": rng.standard_normal((batch, 1)).astype(np.float32)}
             for _ in range(8)]

    def timed(exe, scope):
        with scope_guard(scope):
            exe.run(main, feed=feeds[0], fetch_list=[loss])   # warm
            t0 = time.perf_counter()
            for i in range(steps):
                exe.run(main, feed=feeds[i % len(feeds)],
                        fetch_list=[loss])
        return steps / (time.perf_counter() - t0)

    exe_u, scope_u = fresh_exe(guard=False)
    exe_g, scope_g = fresh_exe(guard=True)
    rates = {"unguarded": 0.0, "guarded": 0.0}
    modes = [("unguarded", exe_u, scope_u), ("guarded", exe_g, scope_g)]
    for _round in range(int(os.environ.get("BENCH_GUARD_ROUNDS", 5))):
        # alternate mode order each round: a monotone background load
        # ramp must not systematically favor whichever mode runs first
        for name, exe, scope in (modes if _round % 2 == 0
                                 else reversed(modes)):
            rates[name] = max(rates[name], timed(exe, scope))
    overhead = (rates["unguarded"] / rates["guarded"] - 1.0) \
        if rates["guarded"] else None
    result = {
        "metric": "guard_steady_state_overhead",
        "value": round(overhead, 4) if overhead is not None else None,
        "unit": "fractional slowdown of guarded vs unguarded steady-"
                "state steps/sec (acceptance: < 0.05)",
        "unguarded_steps_per_sec": round(rates["unguarded"], 2),
        "guarded_steps_per_sec": round(rates["guarded"], 2),
        "guard_stats": exe_g.get_stats()["fault"],
        "batch": batch, "hidden": hidden, "layers": depth,
        "steps": steps,
        "device_kind": kind,
    }
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_compile_sample(kind):
    """BENCH_COMPILE_SAMPLE=1: the compile-observatory acceptance
    artifact (CPU backend, tiny GPT). Four sections in one JSON line:

    - explain: Executor.explain() for the tiny-GPT train step — FLOPs /
      bytes / peak HBM with sources (xla vs static fallback) and the
      per-primitive attribution.
    - storm: a provoked recompile storm (2 warm shapes, then 3 fresh
      unbucketed ones) — events, warnings, and the latest key diff.
    - overhead: recompile-detector on-vs-off steady-state step rate
      (order-alternating best-of rounds, the BENCH_GUARD_COMPARE
      pattern; acceptance < 5%). The detector touches ONLY the
      jit-cache miss path, so this measures the shared-container noise
      floor — the honest claim is "collection is overhead-free on
      hits"; per-miss bookkeeping cost is timed directly below.
    - tracker_miss_cost_us: mean microseconds of one observe_miss()
      against a 32-signature history — the actual price a recompile
      pays for its key diff (vs the ~10^5x larger XLA compile).
    """
    import warnings
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.observability.compile_insight import (
        RecompileStormWarning, RecompileTracker, hbm_ledger)

    seq = int(os.environ.get("BENCH_COMPILE_SEQ", 32))
    steps = int(os.environ.get("BENCH_COMPILE_STEPS", 300))
    rounds = int(os.environ.get("BENCH_COMPILE_ROUNDS", 5))
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _tokens, loss, _logits = gpt.build_lm_net(cfg, seq_len=seq)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    rng = np.random.default_rng(0)

    def feed(b):
        return {"tokens": rng.integers(0, cfg.vocab_size, (b, seq),
                                       dtype=np.int64)}

    def fresh_exe(detect):
        prev = os.environ.get("PADDLE_TPU_RECOMPILE_DETECT")
        os.environ["PADDLE_TPU_RECOMPILE_DETECT"] = "1" if detect else "0"
        try:
            scope = Scope()
            exe = fluid.Executor(fluid.TPUPlace(0))
        finally:
            if prev is None:
                os.environ.pop("PADDLE_TPU_RECOMPILE_DETECT", None)
            else:
                os.environ["PADDLE_TPU_RECOMPILE_DETECT"] = prev
        with scope_guard(scope):
            exe.run(startup)
        return exe, scope

    # -- storm + explain on the detector-on executor ---------------------
    exe, scope = fresh_exe(detect=True)
    storms = []
    with scope_guard(scope):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for b in (4, 8, 6, 10, 12):     # 2 warm, then 3 recompiles
                exe.run(main, feed=feed(b), fetch_list=[loss])
        storms = [w for w in caught
                  if issubclass(w.category, RecompileStormWarning)]
        report = exe.explain(main, feed=feed(4), fetch_list=[loss])
    rc = exe.get_stats()["recompile"]
    storm_info = {
        "events": rc["events"], "storms": rc["storms"],
        "warnings_caught": len(storms),
        "last_summary": rc["last_events"][-1]["summary"]
        if rc["last_events"] else None,
    }
    # trim the report for the artifact: per-primitive tail adds little
    per_prim = report["static"]["jaxpr"]["per_primitive"]
    report["static"]["jaxpr"]["per_primitive"] = dict(
        list(per_prim.items())[:12])

    # -- steady-state overhead: detector on vs off -----------------------
    # FRESH executor pair (the stormed one above carries extra cache
    # entries/series — the comparison must differ in the detect flag
    # and nothing else)
    exe_on, scope_on = fresh_exe(detect=True)
    exe_off, scope_off = fresh_exe(detect=False)

    def timed(e, s):
        f = feed(4)
        with scope_guard(s):
            e.run(main, feed=f, fetch_list=[loss])      # warm this shape
            t0 = time.perf_counter()
            for _ in range(steps):
                e.run(main, feed=f, fetch_list=[loss])
        return steps / (time.perf_counter() - t0)

    rates = {"detector_on": 0.0, "detector_off": 0.0}
    modes = [("detector_on", exe_on, scope_on),
             ("detector_off", exe_off, scope_off)]
    for _round in range(rounds):
        # alternate mode order each round: a monotone background ramp
        # must not systematically favor whichever mode runs first
        for name, e, s in (modes if _round % 2 == 0
                           else reversed(modes)):
            rates[name] = max(rates[name], timed(e, s))
    overhead = (rates["detector_off"] / rates["detector_on"] - 1.0) \
        if rates["detector_on"] else None

    # -- per-miss bookkeeping cost, timed directly -----------------------
    # 32-signature standing history (a realistic badly-bucketed stream;
    # the tracker caps at MAX_SIGNATURES anyway): each probe diffs
    # against it, then pops its own entry so the history — and thus the
    # per-call cost being measured — stays fixed
    tracker = RecompileTracker(stats=None, warm=1, window_s=0.0)
    base_sig = tuple((f"v{i}", (8, 32), np.dtype(np.float32))
                     for i in range(4))

    def probe_sig(i):
        return base_sig + (("x", (8 + i, 32), np.dtype(np.float32)),)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for i in range(32):
            tracker.observe_miss(1, "bench_prog", probe_sig(i),
                                 ("loss",), ("w",), i)
        hist = tracker._history[1]
        n_probe = 200
        t0 = time.perf_counter()
        for i in range(n_probe):
            tracker.observe_miss(1, "bench_prog", probe_sig(100 + i),
                                 ("loss",), ("w",), i)
            hist.pop()
        miss_us = (time.perf_counter() - t0) / n_probe * 1e6

    result = {
        "metric": "compile_detector_steady_state_overhead",
        "value": round(overhead, 4) if overhead is not None else None,
        "unit": "fractional slowdown of detector-on vs detector-off "
                "steady-state steps/sec (acceptance: < 0.05; the "
                "detector runs only on jit-cache misses, so this is "
                "the noise floor)",
        "detector_on_steps_per_sec": round(rates["detector_on"], 2),
        "detector_off_steps_per_sec": round(rates["detector_off"], 2),
        "tracker_miss_cost_us": round(miss_us, 1),
        "explain": report,
        "storm": storm_info,
        "memory_ledger": hbm_ledger().snapshot(),
        "seq_len": seq, "steps": steps, "rounds": rounds,
        "device_kind": kind,
    }
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def _scrape_slo_sample(server, kind):
    """BENCH_SLO_SAMPLE=<path>: mount the telemetry endpoint on the
    (still-warm) continuous server, scrape /metrics + /slo + /healthz
    over real loopback HTTP, and land the evidence at <path>
    (perf/slo_sample.json is the committed sample). NEVER raises: a
    failed scrape must not cost the comparison its result line — it
    logs, records a failure sample, and returns."""
    sample_path = os.environ.get("BENCH_SLO_SAMPLE")
    if not sample_path:
        return None
    exp = None
    try:
        import urllib.request
        exp = server.serve_metrics(port=0)
        t_scrape = time.perf_counter()
        prom = urllib.request.urlopen(f"{exp.url}/metrics",
                                      timeout=30).read().decode()
        slo = json.loads(urllib.request.urlopen(
            f"{exp.url}/slo", timeout=30).read().decode())
        health = json.loads(urllib.request.urlopen(
            f"{exp.url}/healthz", timeout=30).read().decode())
        scrape_ms = (time.perf_counter() - t_scrape) * 1e3
        sample = {
            "source": "live /metrics scrape during "
                      "BENCH_SERVING_COMPARE (GenerationServer."
                      "serve_metrics, loopback HTTP)",
            "scrape_ms": round(scrape_ms, 2),
            "health": health,
            "slo": slo,
            "metrics_bytes": len(prom),
            "serving_series": [ln for ln in prom.splitlines()
                               if ln.startswith("serving_")
                               and not ln.startswith("#")][:60],
            "device_kind": kind,
        }
        with open(sample_path, "w") as f:
            json.dump(_with_device(sample), f, sort_keys=True)
            f.write("\n")
        print(f"bench: slo sample scraped ({len(prom)} bytes) -> "
              f"{sample_path}", file=sys.stderr)
        return sample_path
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: slo sample scrape FAILED ({e!r}) — continuing "
              f"without it", file=sys.stderr)
        try:
            with open(sample_path, "w") as f:
                json.dump({"failed": True, "error": repr(e)}, f)
                f.write("\n")
        except OSError:
            pass
        return None
    finally:
        if exp is not None:
            exp.close()


def run_serving_compare(kind):
    """BENCH_SERVING_COMPARE=1: continuous batching (GenerationServer,
    paged KV cache) vs static batching (fixed groups over the dense
    cache) on a MIXED-LENGTH generation stream — tiny GPT on the CPU
    backend, same params, same requests, greedy both sides.

    The static baseline groups requests `slots` at a time and steps the
    whole group until its LAST lane finishes: short requests idle
    behind long ones (the tail waste continuous batching exists to
    remove), and prompts teacher-force one token per step. The
    continuous engine retires lanes the moment they finish and admits
    the next request into the freed slot. Both modes pay one host
    round-trip per step, so the comparison isolates scheduling.

    BENCH_SERVING_CHUNK defaults to 1: on the compute-bound CPU backend
    every chunk column costs real FLOPs, so a wider chunk taxes decode
    iterations; on TPU, where decode is bandwidth-bound, wider chunks
    accelerate prefill mostly for free (docs/serving.md). Honest
    reporting: tokens/sec for BOTH modes plus the iteration counts the
    speedup comes from.

    ISSUE 6 addition: the continuous engine runs the Pallas ragged
    paged attention kernel (engagement asserted), and the same stream
    is re-run on a reference-pinned server
    (PADDLE_TPU_PAGED_KERNEL=0) — per-step time and tokens/s for both
    land under "paged_attention_kernel_vs_reference", with the caveat
    that interpret-mode CPU numbers measure overhead parity, not the
    TPU HBM-traffic win."""
    import numpy as np
    import paddle_tpu as fluid
    import jax.numpy as jnp
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.inference import decoding as dec
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    n_req = int(os.environ.get("BENCH_SERVING_REQUESTS", 24))
    slots = int(os.environ.get("BENCH_SERVING_SLOTS", 4))
    chunk = int(os.environ.get("BENCH_SERVING_CHUNK", 1))
    block_size = int(os.environ.get("BENCH_SERVING_BLOCK", 8))
    rounds = int(os.environ.get("BENCH_SERVING_ROUNDS", 2))
    max_context = 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    # mixed-length stream: prompts 4..28, outputs 4..44 (seeded)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(3, cfg.vocab_size,
                          rng.integers(4, 29)).astype(np.int32),
             int(rng.integers(4, 45))) for _ in range(n_req)]
    total_gen = sum(g for _p, g in reqs)

    # -- static baseline: groups of `slots` over the dense cache -------
    import jax
    d = cfg.hidden_size // cfg.num_heads
    raw_step = gpt.build_kv_step(params, cfg, max_context)
    step = jax.jit(lambda ids, cache, t: raw_step(ids, cache, t))

    def run_static():
        iters = 0
        for g in range(0, len(reqs), slots):
            group = reqs[g:g + slots]
            lanes = len(group)
            cache = dec.init_kv_cache(lanes, cfg.num_layers,
                                      cfg.num_heads, max_context, d)
            tok = np.array([p[0] for p, _g in group], np.int32)
            # every lane steps until the group's LAST lane finishes
            horizon = max(len(p) + gen - 1 for p, gen in group)
            for t in range(horizon):
                logits, cache = step(jnp.asarray(tok), cache,
                                     jnp.asarray(t, jnp.int32))
                nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
                iters += 1
                for i, (p, _gen) in enumerate(group):
                    tok[i] = p[t + 1] if t + 1 < len(p) else nxt[i]
        return iters

    # -- continuous engine (one server reused across rounds: the fused
    #    step stays compiled, like a long-lived production server) -----
    server = GenerationServer(GPTServingModel(params, cfg),
                              num_slots=slots, block_size=block_size,
                              max_context=max_context, chunk=chunk,
                              start=False)

    def run_continuous():
        it0 = server.get_stats()["iteration"]
        futs = [server.submit(p, max_new_tokens=g) for p, g in reqs]
        server.run_until_idle()
        for f in futs:
            assert len(f.result(timeout=5).token_ids) > 0
        return server.get_stats()["iteration"] - it0

    run_static()                    # warm both compiles before timing
    run_continuous()
    static_s = cont_s = float("inf")
    static_iters = cont_iters = 0
    for _ in range(rounds):         # interleaved best-of rounds
        t0 = time.perf_counter()
        static_iters = run_static()
        static_s = min(static_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        cont_iters = run_continuous()
        cont_s = min(cont_s, time.perf_counter() - t0)

    st = server.get_stats()

    # -- tp=1 vs tp=2 (ISSUE 9): the SAME continuous stream through a
    #    GenerationServer sharded over a 2-device mesh (head-sharded
    #    pools, shard_map fused step, one psum per sub-block). Honest
    #    CPU caveat: on 2 virtual CPU devices of a shared 2-core host
    #    this measures PARITY and per-step mesh overhead (tracing,
    #    collectives emulation), not the per-chip KV-bandwidth win tp
    #    exists for — the headline here is bitwise token ids + one
    #    fused signature on the mesh. Never raises: a mesh failure is
    #    recorded, not fatal (dying numberless is this file's enemy).
    def run_stream_ids(srv):
        it0 = srv.get_stats()["iteration"]
        futs = [srv.submit(p, max_new_tokens=g) for p, g in reqs]
        srv.run_until_idle()
        ids = [list(f.result(timeout=5).token_ids) for f in futs]
        return srv.get_stats()["iteration"] - it0, ids

    def run_tp_compare():
        import jax
        if jax.device_count() < 2:
            return {"skipped": "needs >= 2 devices — run under XLA_"
                               "FLAGS=--xla_force_host_platform_device_"
                               "count=2"}
        tp_server = None
        try:
            from jax.sharding import Mesh
            mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
            tp_server = GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=block_size, max_context=max_context,
                chunk=chunk, start=False, mesh=mesh)
            _w, tp_ids = run_stream_ids(tp_server)      # warm tp=2
            _w, base_ids = run_stream_ids(server)       # same stream
            ids_match = tp_ids == base_ids
            tp1_s = tp2_s = float("inf")
            tp1_iters = tp2_iters = 0
            for r in range(max(rounds, 2)):
                pair = [("tp1", server), ("tp2", tp_server)]
                if r % 2:
                    pair.reverse()
                for tag, srv in pair:
                    t0 = time.perf_counter()
                    iters, _ids = run_stream_ids(srv)
                    dt = time.perf_counter() - t0
                    if tag == "tp1":
                        tp1_iters, tp1_s = iters, min(tp1_s, dt)
                    else:
                        tp2_iters, tp2_s = iters, min(tp2_s, dt)
            tp_st = tp_server.get_stats()
            tp_server.close()
            return {
                "token_ids_match_tp1_bitwise": ids_match,
                "tp1_step_ms": round(tp1_s / max(tp1_iters, 1) * 1e3,
                                     3),
                "tp2_step_ms": round(tp2_s / max(tp2_iters, 1) * 1e3,
                                     3),
                "tp1_tokens_per_sec": round(total_gen / tp1_s, 2),
                "tp2_tokens_per_sec": round(total_gen / tp2_s, 2),
                "step_time_ratio_tp2_over_tp1": round(
                    (tp2_s / max(tp2_iters, 1))
                    / (tp1_s / max(tp1_iters, 1)), 3),
                "tp2_fused_step_signatures":
                    tp_st["fused_step_signatures"],
                "tp2_kernel_engaged": tp_st["kernel"]["engaged"],
                "mesh": tp_st["mesh"],
                "caveat": "2 virtual CPU devices on a shared host: "
                          "measures parity + mesh-step overhead, not "
                          "the per-chip HBM-bandwidth win (pool reads "
                          "per device drop by tp on real chips)",
            }
        except Exception as e:      # noqa: BLE001 — evidence, not a gate
            print(f"bench: tp serving compare FAILED ({e!r}) — "
                  f"recording and continuing", file=sys.stderr)
            if tp_server is not None:
                # a dead server must not keep reporting a live shard
                # footprint (ledger rows / serving.mesh.* gauges) into
                # the /metrics scrape later in this same bench run
                try:
                    tp_server.close(drain=False)
                except Exception:
                    pass
            return {"failed": True, "error": repr(e)}

    tp_cmp = run_tp_compare()

    # -- kernel vs reference (ISSUE 6): the continuous server above
    #    already runs the Pallas ragged-paged-attention kernel (auto
    #    dispatch) — assert it ENGAGED, then drive the same stream
    #    through a reference-pinned server and compare per-step time.
    #    Honest caveat: under the Pallas interpreter on CPU both paths
    #    lower to XLA HLO, so these numbers measure overhead PARITY of
    #    the kernel path (dispatch, DMA loop, scratch), not the TPU
    #    HBM-traffic win the kernel exists for.
    if st["kernel"]["mode"] == "off":
        # the operator pinned the reference path: the comparison is
        # meaningless, but the bench must still emit its JSON line —
        # dying numberless is the failure mode this file exists to
        # avoid. An unexpected fallback under auto/force still asserts.
        result_kernel_skip = ("PADDLE_TPU_PAGED_KERNEL=0 pinned the "
                              "reference path; kernel comparison "
                              "skipped")
        print(json.dumps(_with_device({
            "metric": "serving_continuous_vs_static_batching_speedup",
            "value": round(static_s / cont_s, 3),
            "unit": "x (generated tokens/sec, continuous over static, "
                    "mixed-length greedy stream)",
            "continuous_tokens_per_sec": round(total_gen / cont_s, 2),
            "static_tokens_per_sec": round(total_gen / static_s, 2),
            "continuous_iterations": cont_iters,
            "static_iterations": static_iters,
            "slo_sample_file": _scrape_slo_sample(server, kind),
            "paged_attention_kernel_vs_reference": {
                "skipped": result_kernel_skip},
            "tensor_parallel_tp2_vs_tp1": tp_cmp,
            "device_kind": kind,
        })), flush=True)
        return 0
    assert st["kernel"]["engaged"] is True, st["kernel"]
    prev = os.environ.get("PADDLE_TPU_PAGED_KERNEL")
    try:
        os.environ["PADDLE_TPU_PAGED_KERNEL"] = "0"
        ref_server = GenerationServer(GPTServingModel(params, cfg),
                                      num_slots=slots,
                                      block_size=block_size,
                                      max_context=max_context,
                                      chunk=chunk, start=False)

        def run_reference():
            it0 = ref_server.get_stats()["iteration"]
            futs = [ref_server.submit(p, max_new_tokens=g)
                    for p, g in reqs]
            ref_server.run_until_idle()
            for f in futs:
                assert len(f.result(timeout=5).token_ids) > 0
            return ref_server.get_stats()["iteration"] - it0

        run_reference()             # warm the reference-path compile
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_PAGED_KERNEL", None)
        else:
            os.environ["PADDLE_TPU_PAGED_KERNEL"] = prev
    rst = ref_server.get_stats()
    assert rst["kernel"]["engaged"] is False, rst["kernel"]

    # order-alternating best-of rounds (the BENCH_GUARD_COMPARE
    # pattern): both paths see the same shared-core load drift, so a
    # background blip cannot land entirely on one side and read as a
    # kernel regression. Dispatch modes are baked into each server's
    # compiled step — the env var no longer matters here.
    ker_s = ref_s = float("inf")
    ker_iters = ref_iters = 0
    for r in range(max(rounds, 2)):
        pair = [("k", run_continuous), ("r", run_reference)]
        if r % 2:
            pair.reverse()
        for tag, fn in pair:
            t0 = time.perf_counter()
            iters = fn()
            dt = time.perf_counter() - t0
            if tag == "k":
                ker_iters, ker_s = iters, min(ker_s, dt)
            else:
                ref_iters, ref_s = iters, min(ref_s, dt)
    kernel_cmp = {
        "kernel_step_ms": round(ker_s / max(ker_iters, 1) * 1e3, 3),
        "reference_step_ms": round(ref_s / max(ref_iters, 1) * 1e3, 3),
        "kernel_tokens_per_sec": round(total_gen / ker_s, 2),
        "reference_tokens_per_sec": round(total_gen / ref_s, 2),
        "step_time_ratio_ref_over_kernel": round(
            (ref_s / max(ref_iters, 1)) / (ker_s / max(ker_iters, 1)),
            3),
        "kernel_iterations": ker_iters,
        "reference_iterations": ref_iters,
        "kernel_engaged": st["kernel"]["engaged"],
        "kernel_dispatches": st["kernel"]["kernel_dispatches"],
        "caveat": "interpret-mode CPU: both paths lower to XLA HLO, so "
                  "this measures overhead parity of the kernel path, "
                  "not the TPU HBM-traffic win (O(true length) vs "
                  "O(max_blocks) pool reads per lane per step)",
    }
    slo_sample_file = _scrape_slo_sample(server, kind)
    result = {
        "metric": "serving_continuous_vs_static_batching_speedup",
        "value": round(static_s / cont_s, 3),
        "unit": "x (generated tokens/sec, continuous over static, "
                "mixed-length greedy stream)",
        "continuous_tokens_per_sec": round(total_gen / cont_s, 2),
        "static_tokens_per_sec": round(total_gen / static_s, 2),
        "continuous_iterations": cont_iters,
        "static_iterations": static_iters,
        "slo_sample_file": slo_sample_file,
        "requests": n_req,
        "generated_tokens": total_gen,
        "prompt_len_range": [min(len(p) for p, _ in reqs),
                             max(len(p) for p, _ in reqs)],
        "output_len_range": [min(g for _, g in reqs),
                             max(g for _, g in reqs)],
        "slots": slots, "chunk": chunk, "block_size": block_size,
        "fused_step_signatures": st["fused_step_signatures"],
        "block_utilization_final": st["block_utilization"],
        "paged_attention_kernel_vs_reference": kernel_cmp,
        "tensor_parallel_tp2_vs_tp1": tp_cmp,
        "device_kind": kind,
    }
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_quant_compare(kind):
    """BENCH_QUANT_COMPARE=1: quantized vs dense serving (ISSUE 14) —
    int8 KV pools (per-row f32 scales, dequant fused into the Pallas
    kernel) against dense bf16 pools under the SAME HBM budget, one
    JSON line (perf/bench_quant.json).

    Three sections:
    (1) capacity — both servers get the byte budget a dense-bf16 pool
        of BENCH_QUANT_DENSE_BLOCKS blocks costs; the int8 side fits
        ~1.9x the blocks (ledger-pinned bytes, scales included), and a
        storm of identical requests ADMITS >= 1.8x the concurrent
        lanes on the quantized server (measured active slots after one
        admission pass, watermark 0 — pure block-pool arithmetic made
        observable);
    (2) accuracy — greedy exact-match rate of the int8 stream's ids vs
        the dense stream's (>= 0.99 on a briefly-trained model whose
        argmax is decisive; per-request bitwise flags recorded);
    (3) throughput — tokens/s both sides via order-alternating best-of
        rounds (BENCH_GUARD_COMPARE pattern), with the honest CPU
        caveat: the compute-bound CPU backend pays the quant/dequant
        ALU cost without the TPU's HBM-bandwidth win, so parity here
        is the point — the capacity ratio is the headline.

    head_dim 64 (not the test models' 8-32): the scale overhead is
    4/D of the code bytes, and the acceptance ratio (<= 0.56x dense
    bf16) needs a production-shaped head. Never raises — failures are
    recorded, not fatal."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    n_req = int(os.environ.get("BENCH_QUANT_REQUESTS", 24))
    rounds = max(2, int(os.environ.get("BENCH_QUANT_ROUNDS", 2)))
    dense_blocks = int(os.environ.get("BENCH_QUANT_DENSE_BLOCKS", 25))
    block_size, chunk, max_context = 8, 4, 96

    # production-shaped head (D=64) so the scale overhead is honest;
    # trained to CONVERGENCE on a structured corpus (4 arithmetic
    # token sequences, unambiguous continuations) so greedy argmax is
    # decisive — a near-tied untrained argmax flips on ANY logit
    # perturbation and measures tie-breaking, not quantization quality
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=128, num_layers=3,
                        num_heads=2, inner_size=512, max_position=128,
                        dropout=0.0)
    corpus = np.stack([(np.arange(16) * s + o) % 253 + 3
                       for s, o in [(1, 0), (3, 40), (5, 90),
                                    (7, 160)]]).astype(np.int32)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        _tokens, loss, _ = gpt.build_lm_net(cfg, seq_len=16)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    train_steps = int(os.environ.get("BENCH_QUANT_TRAIN_STEPS", 100))
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(train_steps):
            exe.run(main, feed={"tokens": corpus}, fetch_list=[loss])
        final_loss = float(np.asarray(exe.run(
            main, feed={"tokens": corpus}, fetch_list=[loss])[0]))
        params = gpt.load_params(scope, cfg)

    # in-distribution stream: prefixes of the learned sequences with
    # mixed prompt/output lengths (the serving shape), continuations
    # known to the model — the regime quantized serving targets
    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(n_req):
        row = corpus[int(rng.integers(len(corpus)))]
        reqs.append((row[:int(rng.integers(9, 15))].astype(np.int32),
                     int(rng.integers(6, 21))))
    total_gen = sum(g for _p, g in reqs)

    def budget_blocks(kv_dtype):
        """Blocks that fit the dense-bf16 budget for this kv_dtype
        (usable + the NULL block)."""
        probe = _paged_cache(cfg, 2, block_size, kv_dtype)
        per_block = probe.pool_bytes() // probe.num_blocks
        budget = _paged_cache(cfg, dense_blocks + 1, block_size,
                              None).pool_bytes()
        return budget // per_block

    def _paged_cache(cfg_, nb, bs, kv_dtype):
        from paddle_tpu.serving import PagedKVCache
        import jax.numpy as jnp
        return PagedKVCache(cfg_.num_layers, cfg_.num_heads,
                            cfg_.hidden_size // cfg_.num_heads, nb,
                            block_size=bs, dtype=jnp.bfloat16,
                            kv_dtype=kv_dtype)

    def build(kv_dtype, num_blocks, num_slots):
        import jax.numpy as jnp
        return GenerationServer(
            GPTServingModel(params, cfg, dtype=jnp.bfloat16),
            num_slots=num_slots, block_size=block_size,
            max_context=max_context, chunk=chunk, start=False,
            num_blocks=int(num_blocks), kv_dtype=kv_dtype)

    def run(srv):
        futs = [srv.submit(p, max_new_tokens=g) for p, g in reqs]
        srv.run_until_idle()
        return [list(f.result(timeout=10).token_ids) for f in futs]

    try:
        nb_dense = budget_blocks(None)
        nb_int8 = budget_blocks("int8")
        # (1) capacity: identical-size storm (16-token prompt + 15 new
        # = 31 positions = 4 blocks each), admissions in ONE pass
        storm_prompt = np.arange(3, 19, dtype=np.int32)
        storm_new = 15

        def admitted(kv_dtype, nb):
            srv = build(kv_dtype, nb, num_slots=64)
            for _ in range(40):
                srv.submit(storm_prompt, max_new_tokens=storm_new)
            srv.step()
            got = srv.get_stats()["active_slots"]
            # byte facts captured BEFORE close: the bench must not
            # depend on close() leaving the cache object intact
            pool_bytes = srv.cache.pool_bytes()
            per_block = pool_bytes // srv.cache.num_blocks
            srv.close(drain=False)
            return got, pool_bytes, per_block

        dense_admit, dense_bytes, _ = admitted(None, nb_dense)
        int8_admit, int8_bytes, bytes_per_block_int8 = \
            admitted("int8", nb_int8)
        # how much of the byte budget the bigger int8 pool actually
        # uses (floor-division slack only; NOT the 0.56x pin — that is
        # bytes_ratio_vs_dense below, same block count both sides)
        budget_used = int8_bytes / dense_bytes

        # (2) + (3): accuracy and throughput on the mixed stream
        dense_srv = build(None, nb_dense, num_slots=4)
        int8_srv = build("int8", nb_int8, num_slots=4)
        dense_ids = run(dense_srv)          # warm both compiles
        int8_ids = run(int8_srv)
        flat_d = [t for s in dense_ids for t in s]
        flat_q = [t for s in int8_ids for t in s]
        match = sum(a == b for a, b in zip(flat_d, flat_q)) / \
            max(len(flat_d), 1)
        dense_s = int8_s = float("inf")
        for r in range(rounds):
            pair = [("int8", int8_srv), ("dense", dense_srv)]
            if r % 2:
                pair.reverse()
            for tag, srv in pair:
                t0 = time.perf_counter()
                run(srv)
                dt = time.perf_counter() - t0
                if tag == "int8":
                    int8_s = min(int8_s, dt)
                else:
                    dense_s = min(dense_s, dt)
        qst = int8_srv.get_stats()
        result = {
            "metric": "serving_quant_int8_admitted_concurrency_ratio",
            "value": round(int8_admit / max(dense_admit, 1), 3),
            "unit": "x (concurrent requests admitted, int8 over dense "
                    "bf16, same HBM budget)",
            "hbm_budget_bytes": dense_bytes,
            "dense_blocks": int(nb_dense),
            "int8_blocks": int(nb_int8),
            "block_capacity_ratio": round(nb_int8 / nb_dense, 3),
            "int8_budget_utilization": round(budget_used, 4),
            "int8_bytes_per_block": int(bytes_per_block_int8),
            "train_steps": train_steps,
            "train_loss_final": round(final_loss, 6),
            "dense_admitted": int(dense_admit),
            "int8_admitted": int(int8_admit),
            "greedy_exact_match_rate": round(match, 4),
            "requests_bitwise_identical": sum(
                a == b for a, b in zip(dense_ids, int8_ids)),
            "requests": n_req,
            "generated_tokens": total_gen,
            "int8_tokens_per_sec": round(total_gen / int8_s, 2),
            "dense_tokens_per_sec": round(total_gen / dense_s, 2),
            "fused_step_signatures": qst["fused_step_signatures"],
            "kernel_engaged": qst["kernel"]["engaged"],
            "kv_quant": qst["kv_quant"],
            "head_dim": cfg.hidden_size // cfg.num_heads,
            "slots": 4, "chunk": chunk, "block_size": block_size,
            "caveat": "CPU backend is compute-bound: the quant/dequant "
                      "ALU cost shows, the halved HBM read traffic "
                      "does not — tokens/s parity is the bar here; "
                      "the admitted-concurrency ratio is backend-"
                      "independent block arithmetic and IS the TPU "
                      "capacity win",
        }
        dense_srv.close()
        int8_srv.close()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: quant compare FAILED ({e!r})", file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_quant_int8_admitted_concurrency_ratio",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0
    result["device_kind"] = kind
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_kernel_v2_compare(kind):
    """BENCH_KERNEL_V2_COMPARE=1: paged-attention kernel v2 (ISSUE 16)
    — the double-buffered streaming walk vs v1's full-table gather vs
    the pure-JAX reference, plus the GQA capacity section, one JSON
    line (perf/bench_kernel_v2.json).

    Three sections:
    (1) generations — the SAME trained model served three times with
        PADDLE_TPU_PAGED_KERNEL pinned to v2 / v1 / 0: token ids must
        be identical across all three (v2's online softmax is exact up
        to fp reduction order; greedy argmax on a trained model is
        decisive), tokens/s via order-alternating best-of rounds (the
        BENCH_GUARD_COMPARE pattern);
    (2) GQA capacity — a grouped-query pool (H_kv = H/2 via
        gqa_slice_kv_params) against the MHA pool under the SAME HBM
        budget: ~2x the blocks fit, and a storm of identical requests
        ADMITS ~2x the concurrent lanes (block arithmetic made
        observable, the backend-independent win — it compounds with
        int8's factor from bench_quant);
    (3) GQA fidelity — the GQA stream's ids vs the repeat-KV MHA
        server's, bitwise (the param-helper round trip is exact).

    The honest CPU caveat: under the Pallas interpreter the streamed
    DMAs execute serially, so v2's HBM-latency-hiding does not show —
    numerics and ids are the point here; the VMEM claim (O(2-block)
    scratch vs v1's O(M)) is structural and TPU-true by construction.
    Never raises — failures are recorded, not fatal."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    n_req = int(os.environ.get("BENCH_KV2_REQUESTS", 16))
    rounds = max(2, int(os.environ.get("BENCH_KV2_ROUNDS", 2)))
    dense_blocks = int(os.environ.get("BENCH_KV2_DENSE_BLOCKS", 25))
    block_size, chunk, max_context = 8, 4, 96

    # 4 heads so GQA has a real group factor (H_kv=2, g=2); trained to
    # a decisive greedy argmax (run_quant_compare's corpus idiom)
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=128, num_layers=3,
                        num_heads=4, inner_size=512, max_position=128,
                        dropout=0.0)
    corpus = np.stack([(np.arange(16) * s + o) % 253 + 3
                       for s, o in [(1, 0), (3, 40), (5, 90),
                                    (7, 160)]]).astype(np.int32)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        _tokens, loss, _ = gpt.build_lm_net(cfg, seq_len=16)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    train_steps = int(os.environ.get("BENCH_KV2_TRAIN_STEPS", 100))
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(train_steps):
            exe.run(main, feed={"tokens": corpus}, fetch_list=[loss])
        final_loss = float(np.asarray(exe.run(
            main, feed={"tokens": corpus}, fetch_list=[loss])[0]))
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(n_req):
        row = corpus[int(rng.integers(len(corpus)))]
        reqs.append((row[:int(rng.integers(9, 15))].astype(np.int32),
                     int(rng.integers(6, 21))))
    total_gen = sum(g for _p, g in reqs)

    def build(p, c, num_slots=4, num_blocks=None):
        kw = dict(num_slots=num_slots, block_size=block_size,
                  max_context=max_context, chunk=chunk, start=False)
        if num_blocks is not None:
            kw["num_blocks"] = int(num_blocks)
        return GenerationServer(GPTServingModel(p, c), **kw)

    def run(srv):
        futs = [srv.submit(p, max_new_tokens=g) for p, g in reqs]
        srv.run_until_idle()
        return [list(f.result(timeout=10).token_ids) for f in futs]

    saved_env = {k: os.environ.get(k) for k in
                 ("PADDLE_TPU_PAGED_KERNEL",
                  "PADDLE_TPU_PAGED_V2_AUTO_BYTES")}
    try:
        # (1) generations: mode is latched at TRACE time, so pin the
        # env BEFORE each server's warm-up run, then time freely
        servers, ids, mode_of = {}, {}, {"v2": "v2", "v1": "v1",
                                        "reference": "0"}
        for tag, env in mode_of.items():
            os.environ["PADDLE_TPU_PAGED_KERNEL"] = env
            srv = build(params, cfg)
            ids[tag] = run(srv)         # warm compile under the pin
            servers[tag] = srv
        assert ids["v2"] == ids["v1"] == ids["reference"], \
            "kernel generations disagree on greedy ids"
        best = {tag: float("inf") for tag in servers}
        for r in range(rounds):
            order = list(servers.items())
            if r % 2:
                order.reverse()
            for tag, srv in order:
                t0 = time.perf_counter()
                run(srv)
                best[tag] = min(best[tag],
                                time.perf_counter() - t0)
        v2_stats = servers["v2"].get_stats()["kernel"]
        v1_stats = servers["v1"].get_stats()["kernel"]
        for srv in servers.values():
            srv.close()

        # (2) GQA capacity at the same HBM budget
        from paddle_tpu.serving import PagedKVCache
        kv = cfg.num_heads // 2
        gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
        gqa_cfg = gpt.GPTConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            inner_size=cfg.inner_size, max_position=cfg.max_position,
            dropout=0.0, kv_heads=kv)
        head_dim = cfg.hidden_size // cfg.num_heads

        def pool_bytes_for(nb, kv_heads):
            return PagedKVCache(cfg.num_layers, cfg.num_heads,
                                head_dim, nb, block_size=block_size,
                                num_kv_heads=kv_heads).pool_bytes()

        budget = pool_bytes_for(dense_blocks + 1, cfg.num_heads)
        per_block_gqa = pool_bytes_for(2, kv) // 2
        nb_gqa = budget // per_block_gqa
        storm_prompt = np.arange(3, 19, dtype=np.int32)

        def admitted(p, c, nb):
            os.environ["PADDLE_TPU_PAGED_KERNEL"] = "auto"
            srv = build(p, c, num_slots=64, num_blocks=nb)
            for _ in range(40):
                srv.submit(storm_prompt, max_new_tokens=15)
            srv.step()
            got = srv.get_stats()["active_slots"]
            pool_bytes = srv.cache.pool_bytes()
            srv.close(drain=False)
            return got, pool_bytes

        mha_admit, mha_bytes = admitted(params, cfg, dense_blocks + 1)
        gqa_admit, gqa_bytes = admitted(gqa_params, gqa_cfg, nb_gqa)

        # (3) GQA fidelity: ids bitwise vs the repeat-KV MHA server
        os.environ["PADDLE_TPU_PAGED_KERNEL"] = "auto"
        rep_params = gpt.gqa_repeat_kv_params(gqa_params, cfg, kv)
        srv_g = build(gqa_params, gqa_cfg)
        srv_r = build(rep_params, cfg)
        ids_g, ids_r = run(srv_g), run(srv_r)
        gqa_kernel = srv_g.get_stats()["kernel"]
        srv_g.close()
        srv_r.close()

        result = {
            "metric": "serving_gqa_admitted_concurrency_ratio",
            "value": round(gqa_admit / max(mha_admit, 1), 3),
            "unit": "x (concurrent requests admitted, H_kv=H/2 over "
                    "MHA, same HBM budget)",
            "hbm_budget_bytes": int(budget),
            "mha_blocks": int(dense_blocks + 1),
            "gqa_blocks": int(nb_gqa),
            "block_capacity_ratio": round(nb_gqa / (dense_blocks + 1),
                                          3),
            "mha_admitted": int(mha_admit),
            "gqa_admitted": int(gqa_admit),
            "mha_pool_bytes": int(mha_bytes),
            "gqa_pool_bytes": int(gqa_bytes),
            "gqa_ids_bitwise_vs_repeat_kv": ids_g == ids_r,
            "gqa_kernel_engaged": gqa_kernel["engaged"],
            "train_steps": train_steps,
            "train_loss_final": round(final_loss, 6),
            "requests": n_req,
            "generated_tokens": total_gen,
            "generations_ids_identical": True,
            "v2_tokens_per_sec": round(total_gen / best["v2"], 2),
            "v1_tokens_per_sec": round(total_gen / best["v1"], 2),
            "reference_tokens_per_sec": round(
                total_gen / best["reference"], 2),
            "v2_step_ms_best": round(best["v2"] * 1000, 2),
            "v1_step_ms_best": round(best["v1"] * 1000, 2),
            "reference_step_ms_best": round(
                best["reference"] * 1000, 2),
            "v2_version_reported": v2_stats["version"],
            "v1_version_reported": v1_stats["version"],
            "kv_heads": kv, "q_heads": cfg.num_heads,
            "head_dim": head_dim,
            "slots": 4, "chunk": chunk, "block_size": block_size,
            "caveat": "CPU Pallas interpreter executes the streamed "
                      "DMAs serially, so v2's HBM-latency hiding does "
                      "not show in tokens/s — ids/numerics are the "
                      "bar here. The O(2-block)-vs-O(M) VMEM scratch "
                      "gap is structural (white-box pinned) and the "
                      "GQA admitted-concurrency ratio is backend-"
                      "independent block arithmetic",
        }
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: kernel v2 compare FAILED ({e!r})",
              file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_gqa_admitted_concurrency_ratio",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    result["device_kind"] = kind
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_prefix_compare(kind):
    """BENCH_PREFIX_COMPARE=1: prefix-cache block sharing on vs off
    (today's engine) over a MIXED-TENANT generation stream with 80%
    shared prefixes — tiny GPT on the CPU backend, same params, same
    requests, greedy both sides.

    The stream models the fleet shape the prefix cache exists for:
    three tenant "system prompts" (24/16/32 tokens), 80% of requests
    draw one of them plus a short unique suffix, 20% are fully private
    prompts. Headline: blocks ALLOCATED per request (the sublinear-
    memory claim — shared chunks are matched, not re-allocated) and the
    prefix hit rate; tokens/s rides along via order-alternating best-of
    rounds (the BENCH_GUARD_COMPARE pattern). Acceptance
    (perf/bench_prefix.json): sharing's blocks/request strictly below
    the no-sharing engine, hit rate > 0.5.

    A speculative-decoding section drives the same stream through a
    spec server (2-layer half-width draft, k=3) and reports accept rate
    + tokens/s with the honest CPU caveat: every verify column costs
    real FLOPs on the compute-bound CPU backend, so spec parity/ids are
    the point here — the latency win needs TPU's bandwidth-bound
    decode. Never raises: failures are recorded, not fatal (dying
    numberless is this file's enemy)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                    SpecDecodeConfig)

    n_req = int(os.environ.get("BENCH_PREFIX_REQUESTS", 40))
    slots = int(os.environ.get("BENCH_PREFIX_SLOTS", 4))
    rounds = max(2, int(os.environ.get("BENCH_PREFIX_ROUNDS", 2)))
    block_size, chunk, max_context = 8, 4, 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    tenants = [rng.integers(3, cfg.vocab_size, ln).astype(np.int32)
               for ln in (24, 16, 32)]
    reqs, shared_count = [], 0
    for _ in range(n_req):
        gen = int(rng.integers(4, 21))
        if rng.random() < 0.8:
            t = tenants[int(rng.integers(len(tenants)))]
            sfx = rng.integers(3, cfg.vocab_size,
                               int(rng.integers(1, 5))).astype(np.int32)
            reqs.append((np.concatenate([t, sfx]).astype(np.int32), gen))
            shared_count += 1
        else:
            reqs.append((rng.integers(
                3, cfg.vocab_size,
                int(rng.integers(8, 33))).astype(np.int32), gen))
    total_gen = sum(g for _p, g in reqs)

    def build(**kw):
        srv = GenerationServer(GPTServingModel(params, cfg),
                               num_slots=slots, block_size=block_size,
                               max_context=max_context, chunk=chunk,
                               start=False, **kw)
        counter = {"blocks": 0}
        real = srv.cache.allocate

        def counting_allocate(n):
            got = real(n)
            if got is not None:
                counter["blocks"] += len(got)
            return got

        srv.cache.allocate = counting_allocate
        return srv, counter

    def run(srv, counter):
        """-> (iterations, blocks allocated, ids) for one full stream."""
        counter["blocks"] = 0
        it0 = srv.get_stats()["iteration"]
        futs = [srv.submit(p, max_new_tokens=g) for p, g in reqs]
        srv.run_until_idle()
        ids = [list(f.result(timeout=5).token_ids) for f in futs]
        return srv.get_stats()["iteration"] - it0, counter["blocks"], ids

    try:
        share_srv, share_ctr = build(prefix_cache=True)
        plain_srv, plain_ctr = build()
        # cold pass warms both compiles AND measures the cold-cache
        # allocation cost; later rounds measure the warm steady state
        _i, share_cold_blocks, share_ids = run(share_srv, share_ctr)
        _i, plain_blocks, plain_ids = run(plain_srv, plain_ctr)
        ids_match = share_ids == plain_ids

        share_s = plain_s = float("inf")
        share_iters = plain_iters = share_blocks = 0
        for r in range(rounds):
            pair = [("share", share_srv, share_ctr),
                    ("plain", plain_srv, plain_ctr)]
            if r % 2:
                pair.reverse()
            for tag, srv, ctr in pair:
                t0 = time.perf_counter()
                iters, blocks, _ids = run(srv, ctr)
                dt = time.perf_counter() - t0
                if tag == "share":
                    share_iters, share_blocks = iters, blocks
                    share_s = min(share_s, dt)
                else:
                    plain_iters = iters
                    plain_s = min(plain_s, dt)
        st = share_srv.get_stats()
        pf = st["prefix"]
        hit_rate = pf["hits"] / max(pf["hits"] + pf["misses"], 1)
        result = {
            "metric": "serving_prefix_cache_blocks_per_request_ratio",
            "value": round((plain_blocks / n_req)
                           / max(share_blocks / n_req, 1e-9), 3),
            "unit": "x (blocks allocated per request, no-sharing over "
                    "sharing, warm index)",
            "requests": n_req,
            "shared_prefix_requests": shared_count,
            "generated_tokens": total_gen,
            "prefix_blocks_per_request": round(share_blocks / n_req, 3),
            "prefix_blocks_per_request_cold": round(
                share_cold_blocks / n_req, 3),
            "noshare_blocks_per_request": round(plain_blocks / n_req, 3),
            "prefix_hit_rate": round(hit_rate, 4),
            "prefix_stats": pf,
            "token_ids_match_noshare_bitwise": ids_match,
            "prefix_tokens_per_sec": round(total_gen / share_s, 2),
            "noshare_tokens_per_sec": round(total_gen / plain_s, 2),
            "prefix_iterations": share_iters,
            "noshare_iterations": plain_iters,
            "fused_step_signatures": st["fused_step_signatures"],
            "slots": slots, "chunk": chunk, "block_size": block_size,
            "caveat": "CPU backend is compute-bound, so skipped prefill "
                      "chunks shrink iteration counts more than wall "
                      "time; on TPU the blocks/request drop IS the "
                      "concurrent-users-per-chip win",
        }
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: prefix compare FAILED ({e!r})", file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_prefix_cache_blocks_per_request_ratio",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0

    # -- speculative decoding section (same stream, spec server) -------
    def run_spec():
        dcfg = gpt.GPTConfig(vocab_size=cfg.vocab_size, hidden_size=64,
                             num_layers=2, num_heads=2, inner_size=256,
                             max_position=cfg.max_position, dropout=0.0)
        dmain, dstart = framework.Program(), framework.Program()
        dmain.random_seed = dstart.random_seed = 21
        with framework.program_guard(dmain, dstart):
            gpt.build_lm_net(dcfg, seq_len=8)
        dscope = Scope()
        with scope_guard(dscope):
            exe.run(dstart)
            dparams = gpt.load_params(dscope, dcfg)
        spec_srv, spec_ctr = build(
            spec=SpecDecodeConfig(GPTServingModel(dparams, dcfg), k=3))
        _i, _b, spec_ids = run(spec_srv, spec_ctr)      # warm
        sp_s = float("inf")
        sp_iters = 0
        for _ in range(rounds):
            t0 = time.perf_counter()
            sp_iters, _b, _ids = run(spec_srv, spec_ctr)
            sp_s = min(sp_s, time.perf_counter() - t0)
        sst = spec_srv.get_stats()
        return {
            "token_ids_match_plain_bitwise": spec_ids == plain_ids,
            "accept_rate": sst["spec"]["accept_rate"],
            "spec_k": sst["spec"]["k"],
            "spec_tokens_per_sec": round(total_gen / sp_s, 2),
            "spec_iterations": sp_iters,
            "compiled_step_signatures":
                sst["compiled_step_signatures"],
            "caveat": "compute-bound CPU pays for every verify column "
                      "and the draft rollout; the section proves "
                      "bitwise parity + the <=2-signature budget, not "
                      "the TPU latency win",
        }

    try:
        result["speculative_decode"] = run_spec()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: spec section FAILED ({e!r}) — recording and "
              f"continuing", file=sys.stderr)
        result["speculative_decode"] = {"failed": True,
                                        "error": repr(e)}
    result["device_kind"] = kind
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_tier_compare(kind):
    """BENCH_TIER_COMPARE=1: tiered KV cache (host-RAM spill pool +
    swap-aware preempt/resume) on vs off over the SAME mixed-tenant
    stream through a deliberately starved device pool — tiny GPT on
    the CPU backend, same params, same requests, greedy both sides.

    The device pool is sized so the tenant prefix chains cannot all
    stay resident: without the host tier, eviction destroys chains
    (the next tenant request re-prefills from scratch) and admission
    reserves the full decode up front (concurrency ceiling). With it,
    eviction spills to host RAM and a later prefix hit swaps the
    chain back in (re-prefill avoided), while lazy admission backed
    by host-pledged blocks admits more concurrent decodes and
    preempt/resume absorbs the pressure. Headline: prefix hit rate
    ratio (host-on over host-off, warm index). Acceptance
    (perf/bench_tier.json): host-on hit rate >= host-off, re-prefills
    avoided > 0, peak admitted concurrency above the host-off
    full-reservation baseline, p99 TTFT no worse (CPU-noise caveat
    below), ids bitwise identical across arms. Never raises: failures
    are recorded, not fatal."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    n_req = int(os.environ.get("BENCH_TIER_REQUESTS", 24))
    rounds = max(2, int(os.environ.get("BENCH_TIER_ROUNDS", 2)))
    # 16 usable device blocks (+1 NULL): two 6-block decodes fit under
    # full reservation, the third must wait — that gap is the tentpole
    dev_blocks = int(os.environ.get("BENCH_TIER_BLOCKS", 17))
    host_blocks = int(os.environ.get("BENCH_TIER_HOST_BLOCKS", 32))
    slots, block_size, chunk, max_context = 3, 8, 4, 64

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    tenants = [rng.integers(3, cfg.vocab_size, ln).astype(np.int32)
               for ln in (24, 16, 32)]
    reqs, shared_count = [], 0
    for _ in range(n_req):
        gen = int(rng.integers(4, 13))
        if rng.random() < 0.8:
            t = tenants[int(rng.integers(len(tenants)))]
            sfx = rng.integers(3, cfg.vocab_size,
                               int(rng.integers(1, 5))).astype(np.int32)
            reqs.append((np.concatenate([t, sfx]).astype(np.int32), gen))
            shared_count += 1
        else:
            reqs.append((rng.integers(
                3, cfg.vocab_size,
                int(rng.integers(8, 25))).astype(np.int32), gen))
    total_gen = sum(g for _p, g in reqs)

    def build(host):
        return GenerationServer(
            GPTServingModel(params, cfg), num_slots=slots,
            block_size=block_size, num_blocks=dev_blocks,
            max_context=max_context, chunk=chunk, start=False,
            prefix_cache=True, host_kv_blocks=host_blocks if host else 0)

    def run(srv):
        """-> (peak active slots, ids, ttfts_ms) for one full stream."""
        futs = [srv.submit(p, max_new_tokens=g) for p, g in reqs]
        peak = 0
        while srv.step():
            peak = max(peak, srv._sched.active_count)
        res = [f.result(timeout=10) for f in futs]
        return (peak, [list(r.token_ids) for r in res],
                [r.ttft_ms for r in res if r.ttft_ms is not None])

    def p99(ttfts):
        s = sorted(ttfts)
        return round(s[min(len(s) - 1, int(0.99 * len(s)))], 3) \
            if s else None

    try:
        tier_srv, plain_srv = build(host=True), build(host=False)
        # cold pass warms both compiles (including the two swap
        # signatures); later rounds measure the warm steady state
        tier_peak, tier_ids, _t = run(tier_srv)
        plain_peak, plain_ids, _t = run(plain_srv)
        ids_match = tier_ids == plain_ids

        tier_s = plain_s = float("inf")
        tier_ttfts, plain_ttfts = [], []
        for r in range(rounds):
            pair = [("tier", tier_srv), ("plain", plain_srv)]
            if r % 2:
                pair.reverse()
            for tag, srv in pair:
                t0 = time.perf_counter()
                peak, _ids, ttfts = run(srv)
                dt = time.perf_counter() - t0
                if tag == "tier":
                    tier_peak = max(tier_peak, peak)
                    tier_s, tier_ttfts = min(tier_s, dt), ttfts
                else:
                    plain_peak = max(plain_peak, peak)
                    plain_s, plain_ttfts = min(plain_s, dt), ttfts

        st, pst = tier_srv.get_stats(), plain_srv.get_stats()
        pf, ppf = st["prefix"], pst["prefix"]
        hit = pf["hits"] / max(pf["hits"] + pf["misses"], 1)
        phit = ppf["hits"] / max(ppf["hits"] + ppf["misses"], 1)
        sched = tier_srv._sched
        result = {
            "metric": "serving_kv_tier_prefix_hit_rate_ratio",
            "value": round(hit / max(phit, 1e-9), 3),
            "unit": "x (prefix hit rate, host tier on over off, warm "
                    "index, starved device pool)",
            "requests": n_req,
            "shared_prefix_requests": shared_count,
            "generated_tokens": total_gen,
            "tier_hit_rate": round(hit, 4),
            "no_tier_hit_rate": round(phit, 4),
            "tier_reprefills_avoided": pf.get("reprefills_avoided", 0),
            "tier_spills": pf.get("spills", 0),
            "tier_swap_ins": pf.get("swap_ins", 0),
            "tier_host_drops": pf.get("host_drops", 0),
            "kv_tier": st["kv_tier"],
            "preempts": sched.preempts,
            "resumes": sched.resumes,
            "peak_active_tier": tier_peak,
            "peak_active_no_tier": plain_peak,
            "admitted_concurrency_gain": tier_peak - plain_peak,
            "token_ids_match_no_tier_bitwise": ids_match,
            "ttft_p99_tier_ms": p99(tier_ttfts),
            "ttft_p99_no_tier_ms": p99(plain_ttfts),
            "tier_tokens_per_sec": round(total_gen / tier_s, 2),
            "no_tier_tokens_per_sec": round(total_gen / plain_s, 2),
            "fused_step_signatures": st["fused_step_signatures"],
            "device_blocks": dev_blocks, "host_blocks": host_blocks,
            "slots": slots, "chunk": chunk, "block_size": block_size,
            "caveat": "CPU backend is compute-bound and single-stream, "
                      "so swap-in copies and avoided prefill chunks "
                      "move wall time less than iteration counts; TTFT "
                      "percentiles here bound regression, the "
                      "concurrency + re-prefill wins are the TPU story",
        }
        tier_srv.close()
        plain_srv.close()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: tier compare FAILED ({e!r})", file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_kv_tier_prefix_hit_rate_ratio",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0

    # -- lazy-admission ceiling section (no prefix sharing: the pure
    # full-reservation-vs-host-pledge concurrency gap) ----------------
    def run_ceiling():
        crng = np.random.default_rng(5)
        prompts = [crng.integers(3, cfg.vocab_size, 8).astype(np.int32)
                   for _ in range(3)]

        def drive(host):
            # 8 usable device blocks; each request needs 4 at full
            # reservation (8 prompt + 24 decode tokens) -> ceiling 2.
            # Host pledges lift admission to all 3; preempt/resume
            # absorbs the overcommit when decode tails collide.
            srv = GenerationServer(
                GPTServingModel(params, cfg), num_slots=3,
                block_size=8, num_blocks=9, max_context=64, chunk=4,
                start=False, host_kv_blocks=16 if host else 0)
            futs = [srv.submit(p, max_new_tokens=24) for p in prompts]
            peak = 0
            while srv.step():
                peak = max(peak, srv._sched.active_count)
            ids = [list(f.result(timeout=10).token_ids) for f in futs]
            sched = srv._sched
            stats = (peak, ids, sched.preempts, sched.resumes)
            srv.close()
            return stats

        hp, hids, hpre, hres = drive(host=True)
        fp, fids, _p, _r = drive(host=False)
        return {
            "peak_active_host_pledged": hp,
            "peak_active_full_reservation": fp,
            "admitted_concurrency_gain": hp - fp,
            "preempts": hpre, "resumes": hres,
            "token_ids_match_bitwise": hids == fids,
            "device_blocks": 9, "host_blocks": 16,
        }

    try:
        result["lazy_admission"] = run_ceiling()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: ceiling section FAILED ({e!r}) — recording and "
              f"continuing", file=sys.stderr)
        result["lazy_admission"] = {"failed": True, "error": repr(e)}
    result["device_kind"] = kind
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_fork_compare(kind):
    """BENCH_FORK_COMPARE=1: COW-forked generation (ISSUE 20) on the
    CPU backend — three sections, one JSON line (perf/bench_fork.json).

    1. fork vs independent: the SAME mixed-length prompt stream runs
       once as submit(n=K) fork groups (K sampling lanes aliasing the
       prompt's blocks via refcounts, copy-on-write on divergence) and
       once as K independent submits per prompt. Headline: peak-block
       ratio (fork over independent — at K=4 the lanes pay only their
       private suffixes plus the pooled COW reserve, so the acceptance
       bar is < 0.5), plus tokens/s both arms (order-alternating
       best-of rounds, the BENCH_GUARD_COMPARE pattern) and the
       group/COW counters.
    2. beam: paged beam search on the server vs the dense K-tiled
       beam_decode epilogue over the same prompt — ids BITWISE
       identical, GNMT-normalized scores to float tolerance (the
       no-dense-cache-only-decode-path acceptance), wall time both
       sides.
    3. guided: a regex-masked decode on the SAME server — the token
       mask is data, never shape, so fused_step_signatures stays 1
       across all three sections; masked steps and automaton
       violations (must be 0) recorded.
    Never raises: failures are recorded, not fatal."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.inference import decoding as dec
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import (BeamParams, GenerationServer,
                                    GPTServingModel, RegexConstraint,
                                    SamplingParams)

    K = int(os.environ.get("BENCH_FORK_K", 4))
    n_prompts = int(os.environ.get("BENCH_FORK_PROMPTS", 6))
    rounds = max(2, int(os.environ.get("BENCH_FORK_ROUNDS", 2)))

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 13
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(3)
    reqs = [(rng.integers(3, cfg.vocab_size,
                          int(rng.integers(40, 89))).astype(np.int32),
             int(rng.integers(8, 13)))
            for _ in range(n_prompts)]
    total_gen = K * sum(g for _p, g in reqs)

    # num_slots = 2K so both arms run the same lane concurrency (two
    # groups in flight vs 2K independent lanes); the pool is sized so
    # the INDEPENDENT arm never blocks on watermarks — the peak-block
    # gap is pure sharing, not admission throttling
    def build():
        return GenerationServer(
            GPTServingModel(params, cfg), num_slots=2 * K,
            block_size=8, num_blocks=2 * K * 14 + 40, max_context=128,
            chunk=16, start=False)

    def drain(srv, futs):
        """-> peak blocks in use while driving the stream to idle."""
        peak = 0
        while srv.step():
            st = srv.get_stats()
            peak = max(peak, st["blocks_total"] - st["blocks_free"])
        for f in futs:
            f.result(timeout=30)
        return peak

    def run_fork(srv):
        return drain(srv, [
            srv.submit(p, max_new_tokens=g, n=K,
                       sampling=SamplingParams(seed=i))
            for i, (p, g) in enumerate(reqs)])

    def run_indep(srv):
        return drain(srv, [
            srv.submit(p, max_new_tokens=g)
            for p, g in reqs for _ in range(K)])

    try:
        fork_srv, ind_srv = build(), build()
        fork_peak = run_fork(fork_srv)      # cold: warms the compile
        ind_peak = run_indep(ind_srv)
        fork_s = ind_s = float("inf")
        for r in range(rounds):
            pair = [("fork", fork_srv), ("indep", ind_srv)]
            if r % 2:
                pair.reverse()
            for tag, srv in pair:
                t0 = time.perf_counter()
                peak = run_fork(srv) if tag == "fork" \
                    else run_indep(srv)
                dt = time.perf_counter() - t0
                if tag == "fork":
                    fork_peak = max(fork_peak, peak)
                    fork_s = min(fork_s, dt)
                else:
                    ind_peak = max(ind_peak, peak)
                    ind_s = min(ind_s, dt)
        st = fork_srv.get_stats()
        ind_srv.close()
        result = {
            "metric": "serving_fork_group_peak_block_ratio",
            "value": round(fork_peak / max(ind_peak, 1), 3),
            "unit": "x (peak KV blocks, n=K fork groups over K "
                    "independent submits, same stream)",
            "fork_k": K, "prompts": n_prompts,
            "generated_tokens_per_pass": total_gen,
            "peak_blocks_fork": fork_peak,
            "peak_blocks_independent": ind_peak,
            "blocks_per_request_fork": round(fork_peak / n_prompts, 2),
            "blocks_per_request_independent": round(
                ind_peak / n_prompts, 2),
            "fork_tokens_per_sec": round(total_gen / fork_s, 2),
            "independent_tokens_per_sec": round(total_gen / ind_s, 2),
            "group_forks": st["group.forks"],
            "group_cow_copies": st["group.cow_copies"],
            "blocks_reclaimed_clean": st["blocks_free"]
                == st["blocks_total"],
        }
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: fork compare FAILED ({e!r})", file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_fork_group_peak_block_ratio",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0

    # -- paged beam vs the dense K-tiled epilogue (bitwise) -----------
    def run_beam():
        prompt, n_new, eos = reqs[0][0][:24], 8, 2
        d = cfg.hidden_size // cfg.num_heads
        t0 = time.perf_counter()
        step = gpt.build_kv_step(params, cfg, 64)
        cache = dec.init_kv_cache(K, cfg.num_layers, cfg.num_heads,
                                  64, d)
        for t, tok in enumerate(prompt[:-1]):
            _, cache = step(jnp.full((K,), int(tok), jnp.int32),
                            cache, t)
        ids, norm = dec.beam_decode(
            step, cache, jnp.asarray([int(prompt[-1])], jnp.int32),
            n_new, K, eos, length_penalty=0.6,
            start_t=len(prompt) - 1)
        dense_s = time.perf_counter() - t0
        ids, norm = np.asarray(ids[0]), np.asarray(norm[0])

        t0 = time.perf_counter()
        fut = fork_srv.submit(prompt, max_new_tokens=n_new,
                              eos_id=eos, beam=BeamParams(K))
        fork_srv.run_until_idle()
        hyps = fut.result(timeout=30).hypotheses
        paged_s = time.perf_counter() - t0
        bitwise = all(
            list(h.token_ids) == list(int(x) for x in ids[r])
            for r, h in enumerate(hyps))
        scores_ok = bool(np.allclose(
            [h.norm_score for h in hyps], norm, rtol=1e-5))
        return {
            "beam_size": K, "new_tokens": n_new,
            "ids_match_dense_bitwise": bitwise,
            "norm_scores_match_dense": scores_ok,
            "beam_reorders": fork_srv.get_stats()["beam.reorders"],
            "paged_wall_s": round(paged_s, 3),
            "dense_epilogue_wall_s": round(dense_s, 3),
            "paged_tokens_per_sec": round(K * n_new / paged_s, 2),
            "dense_tokens_per_sec": round(K * n_new / dense_s, 2),
            "caveat": "dense wall time includes its own step compile; "
                      "the paged side reuses the server's live fused "
                      "step — the parity bit is the point, not speed",
        }

    try:
        result["beam"] = run_beam()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: beam section FAILED ({e!r}) — recording and "
              f"continuing", file=sys.stderr)
        result["beam"] = {"failed": True, "error": repr(e)}

    # -- guided regex on the same compiled signature ------------------
    def run_guided():
        digits = {i: str(i - 3) for i in range(3, 13)}
        vocab = [digits.get(i, chr(0x4E00 + i))
                 for i in range(cfg.vocab_size)]
        c = RegexConstraint("[0-9]+", vocab)
        fut = fork_srv.submit(np.array([5, 9, 11, 2], np.int32),
                              max_new_tokens=12, eos_id=1, guided=c)
        fork_srv.run_until_idle()
        res = fut.result(timeout=30)
        st = fork_srv.get_stats()
        return {
            "pattern": "[0-9]+", "emitted": len(res.token_ids),
            "all_digits": all(3 <= t <= 12 for t in res.token_ids
                              if t != 1),
            "masked_steps": st["guided.masked_steps"],
            "violations": st["guided.violations"],
        }

    try:
        result["guided"] = run_guided()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: guided section FAILED ({e!r}) — recording and "
              f"continuing", file=sys.stderr)
        result["guided"] = {"failed": True, "error": repr(e)}

    result["fused_step_signatures"] = \
        fork_srv.get_stats()["fused_step_signatures"]
    fork_srv.close()
    result["device_kind"] = kind
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_fleet_compare(kind):
    """BENCH_FLEET_COMPARE=1: the fleet front door (ISSUE 11) on the
    CPU backend — two sections, one JSON line (perf/bench_fleet.json).

    (1) affinity vs random routing over a multi-tenant hot/cold-prefix
    storm (3 replicas, 3 tenant system prompts, 80% of requests share
    one): fleet-wide prefix hit rate and blocks ALLOCATED per request.
    Random routing scatters a tenant across replicas so every replica
    re-prefills (and re-caches) the same prefix; affinity routing
    lands a tenant on the replica already holding its blocks. Token
    ids are asserted identical across modes (routing must never change
    WHAT is generated, only where).

    (2) p99 TTFT under overload, shedding on vs off: a staggered storm
    of more requests than the fleet digests within the SLO; without
    admission control everything queues (TTFT grows with queue
    position), with burn-rate shedding the excess is rejected with
    retry-after and the ACCEPTED requests' tail stays bounded. Honest
    caveat: wall-clock TTFT on a shared-core CPU backend measures
    queueing structure, not TPU latency — the shed-vs-noshed DELTA is
    the point, its absolute values are not.

    Knobs: BENCH_FLEET_{REQUESTS,REPLICAS,SLOTS,OVERLOAD}. Never
    raises (failures are recorded, not fatal)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import (AdmissionPolicy, AdmissionRejected,
                                    FleetRouter, GenerationServer,
                                    GPTServingModel)

    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", 60))
    n_rep = int(os.environ.get("BENCH_FLEET_REPLICAS", 3))
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", 2))
    n_over = int(os.environ.get("BENCH_FLEET_OVERLOAD", 36))
    block_size, chunk, max_context = 8, 4, 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    # the fleet-shaped storm: a LONG TAIL of tenants (18 system
    # prompts, ~2-3 requests each, 85% of traffic shared). This is the
    # regime where routing policy decides the hit rate: a tenant's 2-3
    # requests scattered randomly over 3 replicas mostly land on 3
    # DIFFERENT replicas — every one a cold first-visit that
    # re-prefills and re-caches the prefix — while affinity routing
    # sends the followers to the replica the first request warmed.
    # (Head tenants with dozens of repeats amortize the first miss
    # under ANY routing; the tail does not, and real multi-tenant
    # traffic is mostly tail.)
    tenants = [rng.integers(3, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(18)]
    reqs, shared_count = [], 0
    for _ in range(n_req):
        gen = int(rng.integers(4, 13))
        if rng.random() < 0.85:
            t = tenants[int(rng.integers(len(tenants)))]
            sfx = rng.integers(3, cfg.vocab_size,
                               int(rng.integers(1, 5))).astype(np.int32)
            reqs.append((np.concatenate([t, sfx]).astype(np.int32), gen))
            shared_count += 1
        else:
            reqs.append((rng.integers(
                3, cfg.vocab_size,
                int(rng.integers(8, 33))).astype(np.int32), gen))

    def build_servers():
        # pool sized so ONE replica can cache ~2 tenants' prefix chunks
        # next to its live traffic but nowhere near all 6 — the
        # capacity split that makes routing policy matter
        servers, counters = [], []
        for _ in range(n_rep):
            srv = GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=block_size, max_context=max_context,
                chunk=chunk, start=False, prefix_cache=True,
                num_blocks=25)
            ctr = {"blocks": 0}
            real = srv.cache.allocate

            def counting(n, _real=real, _ctr=ctr):
                got = _real(n)
                if got is not None:
                    _ctr["blocks"] += len(got)
                return got

            srv.cache.allocate = counting
            servers.append(srv)
            counters.append(ctr)
        return servers, counters

    def fleet_hit_rate(servers):
        h = sum(s.get_stats()["prefix"]["hits"] for s in servers
                if not s._closed)
        m = sum(s.get_stats()["prefix"]["misses"] for s in servers
                if not s._closed)
        return h / max(h + m, 1)

    result = {"metric": "serving_fleet_affinity_vs_random_hit_rate",
              "requests": n_req, "replicas": n_rep, "slots": slots,
              "shared_prefix_requests": shared_count,
              "device_kind": kind}
    try:
        # -- section 1: affinity routing vs random scatter ------------
        servers, ctrs = build_servers()
        router = FleetRouter(servers, start=False)
        t0 = time.perf_counter()
        # staggered arrivals (one engine iteration between submits):
        # routing decisions see the caches earlier requests warmed —
        # all-at-once submission would route the whole storm against
        # cold indexes and measure nothing but load spreading
        futs = []
        for p, g in reqs:
            futs.append(router.submit(p, max_new_tokens=g))
            router.step()
        router.run_until_idle()
        aff_ids = [list(f.result(timeout=10).token_ids) for f in futs]
        aff_s = time.perf_counter() - t0
        aff_hit = fleet_hit_rate(servers)
        aff_blocks = sum(c["blocks"] for c in ctrs)
        aff_st = router.get_stats()
        sig_ok = all(s.get_stats()["fused_step_signatures"] == 1
                     for s in servers)
        router.close()

        # random baseline: same engines, seeded scatter, no router
        servers, ctrs = build_servers()
        t0 = time.perf_counter()
        futs = []
        for p, g in reqs:       # same staggered arrival pattern
            futs.append(servers[int(rng.integers(n_rep))].submit(
                p, max_new_tokens=g))
            for s in servers:
                s.step()
        live = True
        while live:
            live = any(s.step() for s in servers)
        rand_ids = [list(f.result(timeout=10).token_ids) for f in futs]
        rand_s = time.perf_counter() - t0
        rand_hit = fleet_hit_rate(servers)
        rand_blocks = sum(c["blocks"] for c in ctrs)
        for s in servers:
            s.close()
        result.update({
            "value": round(aff_hit, 4),
            "unit": "fleet prefix hit rate (affinity routing)",
            "affinity": {
                "hit_rate": round(aff_hit, 4),
                "blocks_per_request": round(aff_blocks / n_req, 3),
                "tokens_per_sec": round(
                    sum(g for _p, g in reqs) / aff_s, 2),
                "routed": {k: aff_st[k] for k in
                           ("routed", "sheds", "failovers")},
            },
            "random": {
                "hit_rate": round(rand_hit, 4),
                "blocks_per_request": round(rand_blocks / n_req, 3),
                "tokens_per_sec": round(
                    sum(g for _p, g in reqs) / rand_s, 2),
            },
            "hit_rate_delta": round(aff_hit - rand_hit, 4),
            "blocks_per_request_delta": round(
                (rand_blocks - aff_blocks) / n_req, 3),
            "token_ids_match_across_modes": aff_ids == rand_ids,
            "fused_step_signatures_all_one": sig_ok,
        })
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: fleet affinity section FAILED ({e!r})",
              file=sys.stderr)
        print(json.dumps(_with_device(
            {"metric": "serving_fleet_affinity_vs_random_hit_rate",
             "failed": True, "error": repr(e), "device_kind": kind})),
            flush=True)
        return 0

    # -- section 2: p99 TTFT under overload, shed vs no-shed ----------
    # deterministic: every replica runs an injected chaos clock that
    # ticks 20 ms per ENGINE iteration, so a queued request's TTFT is
    # literally (iterations waited) x 20 ms — queueing structure, not
    # wall-clock noise. The storm submits one request per router step,
    # far faster than 3x2 slots drain 8-token generations.
    def overload(admission):
        from paddle_tpu.robustness import ChaosInjector
        servers = []
        for _ in range(n_rep):
            ch = ChaosInjector()
            for it in range(1, 5000):
                ch.advance_clock_at(it, 20.0)
            servers.append(GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=block_size, max_context=max_context,
                chunk=chunk, start=False, prefix_cache=True,
                chaos=ch))
        router = FleetRouter(servers, start=False, admission=admission)
        prompts = [rng.integers(3, cfg.vocab_size,
                                16).astype(np.int32)
                   for _ in range(n_over)]
        futs, sheds, retry_hints = [], 0, []
        for p in prompts:
            try:
                futs.append(router.submit(p, max_new_tokens=8))
            except AdmissionRejected as rej:
                sheds += 1
                retry_hints.append(rej.retry_after_ms)
            router.step()       # staggered arrivals: one iteration
            #                     between submits, queueing builds up
        router.run_until_idle()
        ttfts = sorted(f.result(timeout=10).ttft_ms for f in futs)
        router.close()
        p99 = ttfts[min(len(ttfts) - 1,
                        int(0.99 * len(ttfts)))] if ttfts else None
        p50 = ttfts[len(ttfts) // 2] if ttfts else None
        return {"completed": len(ttfts), "shed": sheds,
                "retry_after_ms_max": max(retry_hints, default=None),
                "ttft_p50_ms": round(p50, 3) if p50 else None,
                "ttft_p99_ms": round(p99, 3) if p99 else None}

    try:
        noshed = overload(None)
        shed = overload(AdmissionPolicy(
            {"ttft_ms": {"p50": 150.0}}, retry_after_ms=50.0))
        result["overload_shedding"] = {
            "overload_requests": n_over,
            "no_shed": noshed, "shed": shed,
            "ttft_p99_delta_ms": (
                round(noshed["ttft_p99_ms"] - shed["ttft_p99_ms"], 3)
                if noshed["ttft_p99_ms"] and shed["ttft_p99_ms"]
                else None),
            "caveat": "wall-clock TTFT on a shared-core CPU backend: "
                      "the shed-vs-noshed queueing-structure delta is "
                      "the signal, the absolute ms are not (on TPU the "
                      "same admission math gates real chip latency)",
        }
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: fleet shed section FAILED ({e!r}) — recording "
              f"and continuing", file=sys.stderr)
        result["overload_shedding"] = {"failed": True, "error": repr(e)}
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_chaos_recovery(kind):
    """BENCH_CHAOS_RECOVERY=1: the self-healing fleet (ISSUE 13) under
    a scripted kill + hang + poison storm — one JSON line
    (perf/bench_chaos.json) recording how fast the fleet returns to
    full strength and how much goodput survives the faults.

    Fully deterministic: manual-drive replicas, heartbeats = router
    iterations, engine clocks injected (20 ms per engine iteration),
    recovery measured in ROUTER ITERATIONS with a nominal 20 ms/iter
    conversion — queueing/recovery STRUCTURE, not wall-clock noise
    (the honest CPU-backend caveat of every serving bench here). The
    storm: replica 0 killed, replica 1 hung (watchdog must catch it),
    and one poison request whose replay faults every engine that
    serves it (quarantined after 2 deaths). Every dead slot
    resurrects through spawn_fn under the crash-loop breaker with
    prefix re-warm. Knobs: BENCH_CHAOS_{REQUESTS,REPLICAS,SLOTS}.
    Never raises (failures are recorded, not fatal)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.robustness import (ChaosInjector, PoisonRequestError,
                                       SupervisorConfig)
    from paddle_tpu.serving import FleetRouter, GenerationServer, \
        GPTServingModel

    n_req = int(os.environ.get("BENCH_CHAOS_REQUESTS", 18))
    n_rep = int(os.environ.get("BENCH_CHAOS_REPLICAS", 3))
    slots = int(os.environ.get("BENCH_CHAOS_SLOTS", 2))
    block_size, chunk, max_context = 8, 4, 96
    ms_per_iter = 20.0      # the injected-clock convention of the
    #                         fleet overload bench: latency = structure

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    tenant = rng.integers(3, cfg.vocab_size, 16).astype(np.int32)
    reqs = []
    for i in range(n_req):
        gen = int(rng.integers(4, 10))
        if i % 3 == 0:
            reqs.append((np.concatenate([tenant, rng.integers(
                3, cfg.vocab_size, 3).astype(np.int32)]), gen))
        else:
            reqs.append((rng.integers(
                3, cfg.vocab_size,
                int(rng.integers(9, 25))).astype(np.int32), gen))
    poison = rng.integers(3, cfg.vocab_size, 12).astype(np.int32)

    result = {"metric": "serving_fleet_chaos_recovery",
              "requests": n_req, "replicas": n_rep, "slots": slots,
              "ms_per_iteration_nominal": ms_per_iter,
              "storm": {"kill_at_iteration": 3, "hang_at_iteration": 5,
                        "poison_requests": 1},
              "device_kind": kind}
    # fault postmortems (engine NonFiniteError dumps, the quarantine
    # dump) go to a scratch dir, never the cwd
    flight_dir = tempfile.mkdtemp(prefix="bench_chaos_flight_")
    try:
        # kill and hang fire FIRST (their targets must still be alive
        # when the plan lands); the poison request arrives mid-stream
        # so its failover chain plays out against the healing fleet
        chaos = (ChaosInjector()
                 .kill_replica_at(3, 0)
                 .hang_replica_at(5, 1)
                 .poison_prompt(poison))

        def spawn(_index):
            return GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=block_size, max_context=max_context,
                chunk=chunk, start=False, prefix_cache=True,
                chaos=chaos, flight_dir=flight_dir)

        servers = [spawn(i) for i in range(n_rep)]
        router = FleetRouter(
            servers, start=False, chaos=chaos, spawn_fn=spawn,
            flight_dir=flight_dir,
            supervisor=SupervisorConfig(hang_heartbeats=3,
                                        backoff_heartbeats=2,
                                        warm_chains=4))
        futs = []
        t0 = time.perf_counter()
        # staggered arrival, poison injected early so its failover
        # chain plays out inside the storm
        live_trace = []         # (router step count, live replicas)
        steps = 0

        def pump():
            nonlocal steps
            router.step()
            steps += 1
            live_trace.append(
                (steps, router.get_stats()["live_replicas"]))

        for i, (p, g) in enumerate(reqs):
            futs.append(router.submit(p, max_new_tokens=g))
            if i == 7:
                futs.append(router.submit(poison, max_new_tokens=6))
            pump()
        while router.step():
            steps += 1
            live_trace.append(
                (steps, router.get_stats()["live_replicas"]))
        wall_s = time.perf_counter() - t0

        # recovery spans: every dip below full strength -> the step
        # it returned; the worst span is the time-to-full-strength
        spans, dip_start = [], None
        for s, live in live_trace:
            if live < n_rep and dip_start is None:
                dip_start = s
            elif live >= n_rep and dip_start is not None:
                spans.append(s - dip_start)
                dip_start = None
        if dip_start is not None:       # never recovered (shouldn't)
            spans.append(live_trace[-1][0] - dip_start)
        completed, quarantined, good_tokens = 0, 0, 0
        for f in futs:
            try:
                r = f.result(timeout=10)
                completed += 1
                good_tokens += len(r.token_ids)
            except PoisonRequestError:
                quarantined += 1
            except Exception:   # noqa: BLE001 — counted as lost
                pass
        st = router.get_stats()
        submitted_tokens = sum(g for _p, g in reqs) + 6
        recovered = st["live_replicas"] == n_rep
        dipped = [s for s, live in live_trace if live < n_rep]
        # None when the fleet never returned to full strength — a
        # dashboard must not see a recovery stamp that never happened
        full_at = (max(dipped) + 1) if dipped and recovered else (
            0 if recovered else None)
        result.update({
            "value": round(max(spans, default=0) * ms_per_iter, 1),
            "unit": "worst time-to-full-strength, ms "
                    "(router iterations x 20 ms nominal)",
            "recovery": {
                "deaths": (st["replica_kills"] + st["hangs"]
                           + st["quarantines"] * 2),
                "resurrections": st["resurrections"],
                "crash_loops": st["crash_loops"],
                "hangs_detected": st["hangs"],
                "recovery_spans_iterations": spans,
                "worst_span_iterations": max(spans, default=0),
                "worst_span_ms_nominal": round(
                    max(spans, default=0) * ms_per_iter, 1),
                "fleet_full_strength_at_iteration": full_at,
                "final_live_replicas": st["live_replicas"],
                "total_router_iterations": steps,
            },
            "goodput": {
                "submitted": len(futs),
                "completed_non_poison": completed,
                "quarantined": quarantined,
                "failovers": st["failovers"],
                "tokens_delivered": good_tokens,
                "tokens_submitted": submitted_tokens,
                "goodput_fraction": round(
                    good_tokens / max(submitted_tokens, 1), 4),
            },
            "quarantine": {
                "poison_threshold": st["poison_threshold"],
                "quarantines": st["quarantines"],
                "poison_faults_fired": chaos.fired["prompt_poison"],
            },
            "wall_s": round(wall_s, 3),
            "caveat": "CPU backend, injected clocks: recovery spans "
                      "are exact ITERATION counts (deterministic); the "
                      "nominal ms conversion is for dashboard scale, "
                      "wall_s is the contended-container wall time",
            "fleet_back_to_full_strength":
                st["live_replicas"] == n_rep,
            "every_fault_fired": (
                chaos.fired["replica_kill"] == 1
                and chaos.fired["replica_hang"] == 1
                and chaos.fired["prompt_poison"] >= 2),
        })
        router.close()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: chaos recovery FAILED ({e!r})", file=sys.stderr)
        result.update({"failed": True, "error": repr(e)})
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_autoscale_compare(kind):
    """BENCH_AUTOSCALE_COMPARE=1: the SLO-driven autoscaler (ISSUE 19)
    over a diurnal load — alternating 4x-overload peaks and calm
    troughs — in three arms fed IDENTICAL request streams: a fleet
    fixed at the floor (what the trough needs), a fleet fixed at the
    ceiling (what the peak needs), and the autoscaled fleet
    (floor..ceiling, scale-up-fast / scale-down-slow hysteresis).
    One JSON line (perf/bench_autoscale.json) recording peak-phase
    TTFT p99 per arm and the capacity each arm paid
    (replica-iterations: live accepting replicas summed over router
    iterations).

    The claim under measure: the autoscaler buys (most of) the
    fixed-at-ceiling arm's peak latency for (much less than) its
    capacity bill — and returns to the floor in the troughs. Fully
    deterministic: in-process replicas, injected engine clocks
    (tick_clock), TTFT measured on the injected clock, capacity in
    iterations. Knobs: BENCH_AUTOSCALE_{CYCLES,PEAK,TROUGH,MAX}.
    Never raises (failures are recorded, not fatal)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.robustness import ChaosInjector
    from paddle_tpu.robustness.supervisor import AutoscalerConfig
    from paddle_tpu.serving import FleetRouter, GenerationServer, \
        GPTServingModel

    cycles = int(os.environ.get("BENCH_AUTOSCALE_CYCLES", 2))
    peak_req = int(os.environ.get("BENCH_AUTOSCALE_PEAK", 28))
    trough_req = int(os.environ.get("BENCH_AUTOSCALE_TROUGH", 48))
    max_rep = int(os.environ.get("BENCH_AUTOSCALE_MAX", 3))
    slots, block_size, chunk, max_context = 3, 8, 4, 64

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 13
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    # one scripted diurnal stream, replayed bitwise into every arm
    rng = np.random.default_rng(19)
    peaks = [[(rng.integers(3, cfg.vocab_size,
                            int(rng.integers(6, 14))).astype(np.int32), 6)
              for _ in range(peak_req)] for _ in range(cycles)]
    troughs = [[(rng.integers(3, cfg.vocab_size, 4).astype(np.int32), 1)
                for _ in range(trough_req)] for _ in range(cycles)]

    result = {"metric": "serving_fleet_autoscale_compare",
              "cycles": cycles, "peak_requests": peak_req,
              "trough_requests": trough_req, "slots_per_replica": slots,
              "floor_replicas": 1, "ceiling_replicas": max_rep,
              "device_kind": kind}

    def run_arm(n_start, autoscale):
        chaos = ChaosInjector().tick_clock(0)

        def spawn(_index):
            return GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=block_size, max_context=max_context,
                chunk=chunk, start=False, prefix_cache=True,
                chaos=chaos, telemetry=True, slo_window_s=0.12)

        asc_cfg = None
        if autoscale:
            asc_cfg = AutoscalerConfig(
                min_replicas=1, max_replicas=max_rep,
                targets={"ttft_ms": {"p99": 100.0}},
                up_threshold=1.0, down_threshold=0.25,
                up_samples=2, down_samples=6, cooldown_heartbeats=4)
        router = FleetRouter(
            [spawn(i) for i in range(n_start)], start=False,
            chaos=chaos, spawn_fn=spawn,
            signals=autoscale, signals_every=1 if autoscale else 16,
            autoscale=asc_cfg)
        cap = {"iters": 0, "replica_iters": 0, "replica_ms": 0.0}
        size_trace = []

        def pump(ms):
            chaos.tick_clock(ms)
            more = router.step()
            live = sum(1 for r in router.replicas() if r.accepting())
            cap["iters"] += 1
            cap["replica_iters"] += live
            cap["replica_ms"] += live * ms
            if not size_trace or size_trace[-1][1] != live:
                size_trace.append((router.iteration, live))
            return more

        peak_ttft, trough_ttft = [], []
        for c in range(cycles):
            # staggered arrival (2 per iteration, identical in every
            # arm): a scale-up mid-peak can actually absorb the tail
            # of the burst — all-at-once admission would pin every
            # request to the pre-scale fleet and measure nothing
            futs = []
            for i in range(0, len(peaks[c]), 2):
                for p, g in peaks[c][i:i + 2]:
                    futs.append(router.submit(p, max_new_tokens=g))
                pump(20.0)
            while pump(20.0):
                pass
            for f in futs:
                r = f.result(timeout=10)
                if r.ttft_ms is not None:
                    peak_ttft.append(float(r.ttft_ms))
            for p, g in troughs[c]:
                f = router.submit(p, max_new_tokens=g)
                pump(40.0)
                while pump(40.0):
                    pass
                r = f.result(timeout=10)
                if r.ttft_ms is not None:
                    trough_ttft.append(float(r.ttft_ms))
        asc = router.autoscaler
        arm = {
            "peak_ttft_p99_ms": round(
                float(np.percentile(peak_ttft, 99)), 2),
            "peak_ttft_mean_ms": round(float(np.mean(peak_ttft)), 2),
            "trough_ttft_mean_ms": round(
                float(np.mean(trough_ttft)), 2),
            "router_iterations": cap["iters"],
            "replica_iterations": cap["replica_iters"],
            "replica_ms_injected": round(cap["replica_ms"], 1),
            "fleet_size_trace": size_trace[:32],
            "final_live": sum(1 for r in router.replicas()
                              if r.accepting()),
        }
        if asc is not None:
            arm["autoscaler"] = {k: v for k, v in asc.stats().items()
                                 if k != "config"}
        router.close()
        return arm

    try:
        arms = {"fixed_floor": run_arm(1, False),
                "fixed_ceiling": run_arm(max_rep, False),
                "autoscale": run_arm(1, True)}
        a, lo, hi = (arms["autoscale"], arms["fixed_floor"],
                     arms["fixed_ceiling"])
        result.update({
            "arms": arms,
            "value": a["peak_ttft_p99_ms"],
            "unit": "autoscaled peak TTFT p99, injected-clock ms",
            "peak_p99_vs_floor": round(
                a["peak_ttft_p99_ms"] / max(lo["peak_ttft_p99_ms"],
                                            1e-9), 3),
            "capacity_vs_ceiling": round(
                a["replica_ms_injected"] / max(hi["replica_ms_injected"],
                                               1e-9), 3),
            "scaled_up": a["autoscaler"]["scale_ups"] >= 1,
            "scaled_down": a["autoscaler"]["scale_downs"] >= 1,
            "returned_to_floor": a["final_live"] == 1,
            "caveat": "CPU backend, injected clocks: TTFT is exact on "
                      "the injected 20/40 ms-per-iteration clock "
                      "(queueing structure, not wall time) and "
                      "capacity is replica-ms on that same injected "
                      "clock, not device-seconds; on real "
                      "accelerators the "
                      "scale-up ALSO pays process spawn + checkpoint "
                      "reload + cache re-warm, which this in-process "
                      "arm does not model — treat the capacity ratio "
                      "as the ceiling of the win, not the win",
        })
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: autoscale compare FAILED ({e!r})", file=sys.stderr)
        result.update({"failed": True, "error": repr(e)})
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_telemetry_compare(kind):
    """BENCH_TELEMETRY_COMPARE=1: request-level telemetry overhead —
    the SAME mixed-length greedy stream through two GenerationServers,
    telemetry on (lifecycle hooks + SLO digests + flight ring; the
    default) vs telemetry=False (the bare PR-6 engine), order-
    alternating rounds (the BENCH_GUARD_COMPARE pattern so shared-core
    load drift cannot land on one side). Acceptance (ISSUE 7):
    overhead < 5%. Trace-request sampling stays at its env default but
    the recorder is OFF (production posture: hooks live, capture
    idle); SLO digests and the flight ring run at full rate."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationServer, GPTServingModel

    # the true effect (~2-4% on this backend) is well below the
    # per-stream noise (±10% bursts on the shared container), so the
    # workload is sized for the estimator: 48 requests ≈ 0.4 s per
    # stream and 30 alternating rounds give each mode's minimum enough
    # samples to converge on its uncontended floor through the bursts
    n_req = int(os.environ.get("BENCH_TELEMETRY_REQUESTS", 48))
    slots = int(os.environ.get("BENCH_TELEMETRY_SLOTS", 4))
    # floor of 1: a tiny BENCH_TELEMETRY_ROUNDS must degrade to fewer/
    # smaller blocks, never die numberless on an empty ratio list
    rounds = max(1, int(os.environ.get("BENCH_TELEMETRY_ROUNDS", 30)))
    max_context = 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    reqs = [(rng.integers(3, cfg.vocab_size,
                          rng.integers(4, 29)).astype(np.int32),
             int(rng.integers(4, 45))) for _ in range(n_req)]
    total_gen = sum(g for _p, g in reqs)

    servers = {
        "on": GenerationServer(GPTServingModel(params, cfg),
                               num_slots=slots, block_size=8,
                               max_context=max_context, chunk=1,
                               start=False, telemetry=True),
        "off": GenerationServer(GPTServingModel(params, cfg),
                                num_slots=slots, block_size=8,
                                max_context=max_context, chunk=1,
                                start=False, telemetry=False),
    }

    def run_stream(server):
        futs = [server.submit(p, max_new_tokens=g) for p, g in reqs]
        server.run_until_idle()
        for f in futs:
            assert len(f.result(timeout=5).token_ids) > 0

    for s in servers.values():      # warm both compiles before timing
        run_stream(s)
    best = {"on": float("inf"), "off": float("inf")}
    ratios = []
    per_round = {}
    order = list(servers.items())
    for r in range(rounds):
        pair = order if r % 2 == 0 else list(reversed(order))
        times = {}
        for name, s in pair:
            t0 = time.perf_counter()
            run_stream(s)
            times[name] = time.perf_counter() - t0
            best[name] = min(best[name], times[name])
        ratios.append(times["on"] / times["off"])
        for name in servers:
            per_round.setdefault(name, []).append(times[name])
    # headline: median of BLOCK-PAIRED best-of ratios. Contention on
    # this shared-core container only ever ADDS time, so a per-mode
    # MINIMUM recovers that mode's uncontended floor — but a global
    # min-of-all-rounds needs both modes to catch a quiet moment
    # (asymmetric luck reads as overhead), and a per-round paired
    # median's ~0.35 s windows are shorter than the bursts (adjacent-
    # pair ratios stay burst-correlated; observed spread −7%..+26%).
    # So: take per-mode minima within each block of 6 time-adjacent
    # alternating rounds (recovers floors under bursts shorter than a
    # block), ratio the two minima per block (time-adjacent, immune to
    # slow drift), and take the median across blocks (robust to a
    # fully-contended block). Global best-of and the paired per-round
    # median ride along as cross-checks. The estimator itself is
    # _block_paired_overhead — shared with run_trace_compare, so a
    # future fix lands in every on-vs-off bench at once.
    block_ratios, overhead = _block_paired_overhead(
        per_round["on"], per_round["off"], rounds)
    ratios.sort()
    paired_median = ratios[len(ratios) // 2] - 1.0
    st_on = servers["on"].get_stats()
    result = {
        "metric": "serving_telemetry_overhead",
        "value": round(overhead, 4),
        "unit": "fractional slowdown of telemetry-on vs telemetry-off, "
                "median of block-paired best-of-6-rounds ratios, mixed-"
                "length greedy stream (acceptance: < 0.05)",
        "block_ratios": [round(x - 1.0, 4) for x in block_ratios],
        "best_of_overhead": round(best["on"] / best["off"] - 1.0, 4),
        "paired_median_overhead": round(paired_median, 4),
        "round_ratios": [round(x - 1.0, 4) for x in ratios],
        "telemetry_on_tokens_per_sec": round(total_gen / best["on"], 2),
        "telemetry_off_tokens_per_sec": round(total_gen / best["off"],
                                              2),
        "requests": n_req, "generated_tokens": total_gen,
        "slots": slots, "rounds": rounds,
        "slo_windows_completed":
            st_on["slo"]["windows_completed"],
        "slo_cumulative_ttft_p99_ms":
            st_on["slo"]["cumulative"].get("ttft_ms", {}).get("p99"),
        "flight_entries": st_on["slo"]["flight"]["entries"],
        "trace_requests_mode": st_on["slo"]["trace_requests"]["mode"],
        "device_kind": kind,
    }
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def _block_paired_overhead(per_round_on, per_round_off, rounds,
                           block=6):
    """The ONE block-paired best-of estimator the on-vs-off overhead
    benches share (run_telemetry_compare has the full rationale:
    contention on this shared-core container only ever ADDS time, so
    per-mode minima within each block of `block` time-adjacent
    alternating rounds recover the uncontended floors, block-paired
    ratios kill slow drift, and the median across blocks survives a
    fully-contended block; a non-multiple round count yields a shorter
    tail block rather than silently dropping measured rounds).
    Returns (sorted block ratios, median overhead)."""
    b = min(block, rounds)      # < block rounds: one (degenerate) block
    block_ratios = sorted(
        min(per_round_on[i:i + b]) / min(per_round_off[i:i + b])
        for i in range(0, rounds, b))
    return block_ratios, block_ratios[len(block_ratios) // 2] - 1.0


def run_trace_compare(kind):
    """BENCH_TRACE_COMPARE=1: fleet-wide distributed tracing overhead
    (ISSUE 15) — the SAME mixed-length greedy stream through two
    2-replica FleetRouters, one with a LIVE trace capture (sampling
    all: context minting + route instants + span-tree emission into
    per-replica recorders) and one with tracing off (context minting
    only — the production idle posture), order-alternating rounds with
    the BENCH_TELEMETRY_COMPARE block-paired best-of estimator.
    Acceptance (ISSUE 15): steady-state overhead < 5%, token ids
    BITWISE identical across modes. Never raises (failures are
    recorded, not fatal)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import (FleetRouter, GenerationServer,
                                    GPTServingModel)

    n_req = int(os.environ.get("BENCH_TRACE_REQUESTS", 36))
    n_rep = int(os.environ.get("BENCH_TRACE_REPLICAS", 2))
    slots = int(os.environ.get("BENCH_TRACE_SLOTS", 4))
    rounds = max(1, int(os.environ.get("BENCH_TRACE_ROUNDS", 24)))
    max_context = 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    reqs = [(rng.integers(3, cfg.vocab_size,
                          rng.integers(4, 29)).astype(np.int32),
             int(rng.integers(4, 33))) for _ in range(n_req)]
    total_gen = sum(g for _p, g in reqs)

    result = {"metric": "serving_fleet_trace_overhead",
              "requests": n_req, "replicas": n_rep, "slots": slots,
              "rounds": rounds, "device_kind": kind}
    try:
        def fleet(traced):
            servers = [GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=8, max_context=max_context, chunk=1,
                start=False) for _ in range(n_rep)]
            return FleetRouter(servers, start=False, trace=traced,
                               trace_sample="all")

        routers = {"on": fleet(True), "off": fleet(False)}

        def run_stream(router):
            futs = [router.submit(p, max_new_tokens=g)
                    for p, g in reqs]
            router.run_until_idle()
            return [list(f.result(timeout=10).token_ids)
                    for f in futs]

        ids = {}
        for name, r in routers.items():    # warm compiles untimed
            ids[name] = run_stream(r)
        if ids["on"] != ids["off"]:
            raise AssertionError(
                "tracing-on vs tracing-off token ids diverged")
        best = {"on": float("inf"), "off": float("inf")}
        per_round = {"on": [], "off": []}
        order = list(routers.items())
        for rnd in range(rounds):
            pair = order if rnd % 2 == 0 else list(reversed(order))
            times = {}
            for name, r in pair:
                t0 = time.perf_counter()
                run_stream(r)
                times[name] = time.perf_counter() - t0
                best[name] = min(best[name], times[name])
            for name in per_round:
                per_round[name].append(times[name])
        block_ratios, overhead = _block_paired_overhead(
            per_round["on"], per_round["off"], rounds)
        st = routers["on"].get_stats()
        dump = routers["on"].dump_trace()
        result.update({
            "value": round(overhead, 4),
            "unit": "fractional slowdown of tracing-on vs tracing-off, "
                    "median of block-paired best-of-6-rounds ratios, "
                    "mixed-length fleet stream (acceptance: < 0.05)",
            "block_ratios": [round(x - 1.0, 4) for x in block_ratios],
            "best_of_overhead": round(best["on"] / best["off"] - 1.0,
                                      4),
            "tracing_on_tokens_per_sec": round(total_gen / best["on"],
                                               2),
            "tracing_off_tokens_per_sec": round(
                total_gen / best["off"], 2),
            "generated_tokens": total_gen,
            "ids_bitwise_identical": True,
            "trace": {
                "completed_traces": st["trace"]["completed_total"],
                "merged_dump_events": len(dump["traceEvents"]),
                "process_groups": len(dump["otherData"]["sources"]),
                "truncated": dump["otherData"]["truncated"],
            },
            "caveat": "CPU backend: overhead parity is the bar "
                      "off-TPU; the ~0.25 ms fused step makes every "
                      "per-iteration microsecond visible, so this "
                      "bound is conservative for real hardware",
        })
        for r in routers.values():
            r.close()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: trace compare FAILED ({e!r})", file=sys.stderr)
        result.update({"failed": True, "error": repr(e)})
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def run_signals_compare(kind):
    """BENCH_SIGNALS_COMPARE=1: fleet health signals overhead
    (ISSUE 17) — the SAME tenant-tagged mixed-length greedy stream
    through two 2-replica FleetRouters behind identical (loose, never-
    shedding) admission, one with the full signal plane live (engine
    series sampling, registry sampling + windowed burn-rate series +
    alert-rule evaluation per router heartbeat, per-tenant ledgers)
    and one with signals=False and series_capacity=0 telemetry — the
    plane's true off posture. Order-alternating rounds with the
    BENCH_TELEMETRY_COMPARE block-paired best-of estimator.
    Acceptance (ISSUE 17): steady-state overhead < 5%, token ids
    BITWISE identical across modes. Never raises (failures are
    recorded, not fatal)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.observability.alerts import AlertRule
    from paddle_tpu.observability.serving_telemetry import \
        ServingTelemetry
    from paddle_tpu.serving import (FleetRouter, GenerationServer,
                                    GPTServingModel)
    from paddle_tpu.serving.router import AdmissionPolicy

    n_req = int(os.environ.get("BENCH_SIGNALS_REQUESTS", 36))
    n_rep = int(os.environ.get("BENCH_SIGNALS_REPLICAS", 2))
    slots = int(os.environ.get("BENCH_SIGNALS_SLOTS", 4))
    # 48 rounds (8 paired blocks of 6): the plane's true cost profiled
    # out under 1%, so the estimate is noise-bound — fewer blocks let
    # one bad block swing the median past the 5% acceptance bar
    rounds = max(1, int(os.environ.get("BENCH_SIGNALS_ROUNDS", 48)))
    max_context = 96

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        params = gpt.load_params(scope, cfg)

    rng = np.random.default_rng(0)
    reqs = [(rng.integers(3, cfg.vocab_size,
                          rng.integers(4, 29)).astype(np.int32),
             int(rng.integers(4, 33))) for _ in range(n_req)]
    tenants = [f"tenant{i % 4}" for i in range(n_req)]
    total_gen = sum(g for _p, g in reqs)

    result = {"metric": "serving_fleet_signals_overhead",
              "requests": n_req, "replicas": n_rep, "slots": slots,
              "rounds": rounds, "device_kind": kind}
    try:
        # admission IDENTICAL on both arms (its submit-path burn check
        # predates this plane); the arms differ ONLY in the signal
        # plane. Loose targets + a huge threshold: the burn series is
        # computed every heartbeat but nothing ever sheds, so both
        # arms route the same stream.
        def admission():
            return AdmissionPolicy({"ttft_ms": {"p99": 1e9}},
                                   burn_threshold=1e9)

        def fleet(signals):
            servers = [GenerationServer(
                GPTServingModel(params, cfg), num_slots=slots,
                block_size=8, max_context=max_context, chunk=1,
                start=False,
                telemetry=(True if signals else ServingTelemetry(
                    series_capacity=0)))
                for _ in range(n_rep)]
            rules = [AlertRule.threshold_rule(
                         "queue-backlog", "serving.queue_depth",
                         float(4 * slots * n_rep), for_s=0.05),
                     AlertRule.burn_rate(
                         "slo-burn", "slo.window_burn.ttft_ms.p99",
                         1.0, fast_s=0.5, slow_s=2.0),
                     AlertRule.absence(
                         "engine-stale", "engine.step_ms",
                         window_s=60.0)] if signals else None
            return FleetRouter(servers, start=False, signals=signals,
                               admission=admission(),
                               alert_rules=rules)

        routers = {"on": fleet(True), "off": fleet(False)}

        def run_stream(router, tagged):
            futs = [router.submit(p, max_new_tokens=g,
                                  tenant=(t if tagged else None))
                    for (p, g), t in zip(reqs, tenants)]
            router.run_until_idle()
            return [list(f.result(timeout=10).token_ids)
                    for f in futs]

        ids = {}
        for name, r in routers.items():    # warm compiles untimed
            ids[name] = run_stream(r, tagged=(name == "on"))
        if ids["on"] != ids["off"]:
            raise AssertionError(
                "signals-on vs signals-off token ids diverged")
        best = {"on": float("inf"), "off": float("inf")}
        per_round = {"on": [], "off": []}
        order = list(routers.items())
        for rnd in range(rounds):
            pair = order if rnd % 2 == 0 else list(reversed(order))
            times = {}
            for name, r in pair:
                t0 = time.perf_counter()
                run_stream(r, tagged=(name == "on"))
                times[name] = time.perf_counter() - t0
                best[name] = min(best[name], times[name])
            for name in per_round:
                per_round[name].append(times[name])
        block_ratios, overhead = _block_paired_overhead(
            per_round["on"], per_round["off"], rounds)
        st = routers["on"].get_stats()
        sig = routers["on"].dump_signals()
        tenants_seen = sig["tenants"]["tenants"]
        result.update({
            "value": round(overhead, 4),
            "unit": "fractional slowdown of signals-on vs signals-off, "
                    "median of block-paired best-of-6-rounds ratios, "
                    "tenant-tagged mixed-length fleet stream "
                    "(acceptance: < 0.05)",
            "block_ratios": [round(x - 1.0, 4) for x in block_ratios],
            "best_of_overhead": round(best["on"] / best["off"] - 1.0,
                                      4),
            "signals_on_tokens_per_sec": round(total_gen / best["on"],
                                               2),
            "signals_off_tokens_per_sec": round(
                total_gen / best["off"], 2),
            "generated_tokens": total_gen,
            "ids_bitwise_identical": True,
            "signals": {
                "fleet_points": st["signals"]["fleet_points"],
                "live_stores": st["signals"]["live_stores"],
                "alert_rules": st["signals"]["alerts"]["rules"],
                "alert_evaluations":
                    st["signals"]["alerts"]["evaluations"],
                "tenants": sorted(tenants_seen),
                "tenant_decode_tokens": {
                    k: v["decode_tokens"]
                    for k, v in sorted(tenants_seen.items())},
            },
            "caveat": "CPU backend: overhead parity is the bar "
                      "off-TPU; the ~0.25 ms fused step makes every "
                      "per-heartbeat microsecond visible, so this "
                      "bound is conservative for real hardware",
        })
        for r in routers.values():
            r.close()
    except Exception as e:      # noqa: BLE001 — evidence, not a gate
        print(f"bench: signals compare FAILED ({e!r})", file=sys.stderr)
        result.update({"failed": True, "error": repr(e)})
    print(json.dumps(_with_device(result)), flush=True)
    return 0


def bench_one(batch, seq_len, n_steps):
    import numpy as np
    from paddle_tpu.ops.pallas import flash

    import jax

    def _phase(msg):
        print(f"bench: [{time.strftime('%H:%M:%S')}] b{batch} {msg}",
              file=sys.stderr, flush=True)

    trace0 = flash.TRACE_COUNT
    t_build0 = time.perf_counter()
    step, tokens_per_step, step_flops = build_step(batch, seq_len)
    t_build = time.perf_counter() - t_build0
    # warmup: first call compiles (~20-40s on TPU), second confirms cache
    _phase("tracing + XLA compile (first step)")
    t_c0 = time.perf_counter()
    jax.block_until_ready(step())
    t_compile = time.perf_counter() - t_c0
    jax.block_until_ready(step())
    print(f"bench: batch={batch} build {t_build:.1f}s "
          f"compile+first-step {t_compile:.1f}s", file=sys.stderr)
    flash_engaged = flash.TRACE_COUNT > trace0

    t0 = time.perf_counter()
    out = None
    for _ in range(n_steps):
        out = step()
    # steps dispatched asynchronously (return_numpy=False); one block
    # closes the timed window — per-step host sync would serialize a
    # host round trip into every step
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    _phase(f"timed loop done: {n_steps} steps in {dt:.1f}s")
    assert np.isfinite(np.asarray(out[0])).all(), \
        "loss went non-finite during bench"
    # cross-check the analytic FLOPs/step against XLA's own cost model;
    # a big gap means the MFU denominator (and so MFU itself) is suspect
    xla_flops = None
    try:
        _phase("fetching cost analysis")
        exe = getattr(step, "executor", None)
        if exe is not None:
            xla_flops = float(exe.last_cost_analysis().get("flops", 0)) or None
        elif hasattr(step, "cost_analysis"):
            # non-Executor steps (gpt_prefill) expose their own hook
            xla_flops = float(step.cost_analysis().get("flops", 0)) or None
    except Exception as e:
        print(f"bench: cost_analysis unavailable: {e}", file=sys.stderr)
    if xla_flops:
        ratio = step_flops / xla_flops
        print(f"bench: flops cross-check analytic/xla = {ratio:.2f} "
              f"(analytic {step_flops:.3e}, xla {xla_flops:.3e})",
              file=sys.stderr)
    # NOTE: the allocator's peak is PROCESS-lifetime (monotonic across the
    # batch sweep) — meaningful for the largest batch, an upper bound for
    # the others; the JSON key says so.
    mem_gb = None
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            mem_gb = round(stats["peak_bytes_in_use"] / 2**30, 3)
    except Exception:
        pass
    hlo_text = None
    if os.environ.get("BENCH_DUMP_HLO"):
        try:
            # cheap: _last_compiled() is already memoized by the
            # cost-analysis call above
            _phase("serializing optimized HLO text")
            hlo_text = step.executor.last_compiled_text()
            _phase(f"HLO text {len(hlo_text) / 2**20:.1f} MiB")
        except Exception as e:
            print(f"bench: HLO dump unavailable: {e}", file=sys.stderr)
    return {
        "hlo_text": hlo_text,
        "batch": batch,
        "tokens_per_sec": tokens_per_step * n_steps / dt,
        "model_flops_per_sec": step_flops * n_steps / dt,
        "xla_flops_per_step": xla_flops,
        "peak_mem_gb_process": mem_gb,
        "flash_engaged": bool(flash_engaged),
        # batch-DEPENDENT build facts ride the per-batch record, not
        # RUN_INFO (which every batch overwrites): the emitted value must
        # describe the batch that won the sweep
        "packing_efficiency": RUN_INFO.pop("packing_efficiency", None),
    }


def _hbm_limit_bytes():
    """Device memory capacity per XLA's allocator (None off-TPU)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_limit")
    except Exception:
        return None


def _project_peak_bytes(points, batch):
    """HBM pre-flight projection for a batch LARGER than any run so far.

    The allocator peak is process-lifetime monotonic, so only the
    strictly-increasing (batch, peak) subsequence carries information:
    with two such points the activation slope is (p2-p1)/(b2-b1) on top
    of the fixed params+opt-state floor; with one point no linear split
    is possible and the caller falls back to the "HBM already nearly
    full" check. Returns None when no projection is justified."""
    pts = []
    for b, p in points:
        if p and (not pts or (b > pts[-1][0] and p > pts[-1][1])):
            pts.append((b, p))
    if len(pts) < 2:
        return None
    (b1, p1), (b2, p2) = pts[-2], pts[-1]
    slope = (p2 - p1) / (b2 - b1)
    return p2 + max(slope, 0.0) * (batch - b2)


def _looks_like_oom(err):
    import re
    s = repr(err).lower()
    # word-bounded "oom" catches XLA's "OOM when allocating ..." without
    # tripping on identifiers like "bloom" in tracebacks
    return ("resource_exhausted" in s or "out of memory" in s
            or "exceeds the memory" in s
            or re.search(r"\boom\b", s) is not None)


_SWEEP = []          # completed batch results (the hard watchdog reads it)
RUN_INFO = {}        # facts recorded by the build fns (image_size, depth)
_EMITTED = False
import threading as _threading
_EMIT_LOCK = _threading.Lock()


def _emit(sweep, seq_len, kind, peak):
    """Exactly-once JSON emission — callable from the watchdog thread AND
    main, so the flag flips under a lock and the winner prints alone."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED or not sweep:
            return
        _EMITTED = True
    best = max(sweep, key=lambda r: r["tokens_per_sec"])
    model = os.environ.get("BENCH_MODEL", "ernie")
    tiny = os.environ.get("BENCH_TINY") == "1"
    if model == "resnet":
        # under BENCH_TINY the run is ResNet-18 — name what actually ran
        arch = f"resnet{RUN_INFO.get('depth', 50)}"
        metric = f"{arch}_train_images_per_sec_per_chip"
        unit = "images/s/chip"
        rate_key = "images_per_sec"
        baseline = V100_RESNET50_IMAGES_PER_SEC
    elif model == "transformer":
        metric = ("transformer_tiny" if tiny else "transformer_base_wmt14") \
            + "_train_tokens_per_sec_per_chip"
        unit = "tokens/s/chip"
        rate_key = "tokens_per_sec"
        baseline = None        # no reference figure recorded for this config
    elif model == "deepfm":
        metric = "deepfm_ctr_train_examples_per_sec_per_chip"
        unit = "examples/s/chip"
        rate_key = "examples_per_sec"
        baseline = None
    elif model == "gpt":
        metric = ("gpt_tiny" if tiny else "gpt_base") \
            + "_lm_train_tokens_per_sec_per_chip"
        unit = "tokens/s/chip"
        rate_key = "tokens_per_sec"
        baseline = None
        if not best["flash_engaged"]:
            print("bench: WARNING — Pallas flash attention did NOT "
                  "engage on the causal LM path", file=sys.stderr)
    elif model == "packed":
        metric = ("ernie_packed_tiny" if tiny else "ernie_packed_base") \
            + "_pretrain_real_tokens_per_sec_per_chip"
        unit = "real tokens/s/chip"
        rate_key = "tokens_per_sec"
        # same basis as the headline: useful content tokens per second
        baseline = V100_BERT_BASE_TOKENS_PER_SEC
        if not best["flash_engaged"]:
            print("bench: WARNING — Pallas flash attention did NOT "
                  "engage on the packed path (segment masking rides it)",
                  file=sys.stderr)
    elif model == "gpt_decode":
        # single-token KV-cache steps never touch the flash kernel;
        # decode is bandwidth-bound so tokens/s is the figure of merit
        metric = ("gpt_tiny" if tiny else "gpt_base") \
            + "_kv_decode_tokens_per_sec_per_chip"
        unit = "tokens/s/chip"
        rate_key = "tokens_per_sec"
        baseline = None
    elif model == "gpt_prefill":
        metric = ("gpt_tiny" if tiny else "gpt_base") \
            + "_prefill_prompt_tokens_per_sec_per_chip"
        unit = "tokens/s/chip"
        rate_key = "tokens_per_sec"
        baseline = None
        if not best["flash_engaged"]:
            print("bench: WARNING — Pallas flash attention did NOT "
                  "engage on the prefill path", file=sys.stderr)
    else:
        # ernie and bert share the BERT-base-sized graph; name what ran
        arch = "ernie" if model == "ernie" else "bert"
        metric = (f"{arch}_tiny" if tiny else
                  f"{arch}_base") + "_pretrain_tokens_per_sec_per_chip"
        unit = "tokens/s/chip"
        rate_key = "tokens_per_sec"
        baseline = V100_BERT_BASE_TOKENS_PER_SEC
        if not best["flash_engaged"]:
            print("bench: WARNING — Pallas flash attention did NOT "
                  "engage; the number below rides the O(T^2) XLA "
                  "fallback", file=sys.stderr)
    result = {
        "metric": metric,
        "value": round(best["tokens_per_sec"], 2),
        "unit": unit,
        # the ratio is only meaningful for the full configs with a recorded
        # reference figure; tiny smoke runs and figure-less configs emit null
        "vs_baseline": (None if tiny or baseline is None else
                        round(best["tokens_per_sec"] / baseline, 3)),
        "mfu": round(best["mfu"], 4),
        # XLA's own FLOPs count for one step (None if unavailable): lets a
        # reader audit the analytic MFU denominator against the compiler's
        "xla_flops_per_step": best.get("xla_flops_per_step"),
        # process-lifetime allocator peak (upper bound for non-max batches)
        "peak_mem_gb_process": best.get("peak_mem_gb_process"),
        "batch": best["batch"],
        "device_kind": kind,
        "peak_tflops": peak / 1e12,
        "sweep": [{"batch": r["batch"],
                   rate_key: round(r["tokens_per_sec"], 2),
                   "mfu": round(r["mfu"], 4)} for r in sweep],
    }
    hlo_path = os.environ.get("BENCH_DUMP_HLO")
    if hlo_path and best.get("hlo_text"):
        try:
            d = os.path.dirname(hlo_path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(hlo_path, "w") as f:
                f.write(best["hlo_text"])
            result["hlo_path"] = hlo_path
        except OSError as e:
            print(f"bench: HLO dump write failed: {e}", file=sys.stderr)
    _with_device(result)
    if tiny:
        result["tiny"] = True
    if model == "resnet":
        result["image_size"] = RUN_INFO.get("image_size")
    elif model == "deepfm":
        result["num_features"] = RUN_INFO.get("num_features")
    else:
        result["seq_len"] = RUN_INFO.get("seq_len", seq_len)
        result["flash_engaged"] = best["flash_engaged"]
        if model == "packed":
            result["packing_efficiency"] = best.get("packing_efficiency")
    print(json.dumps(result), flush=True)


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    _DEVICE.update(platform=devs[0].platform, device_kind=kind,
                   device_count=len(devs))

    if os.environ.get("BENCH_ASYNC_COMPARE") == "1":
        # async-pipeline micro-comparison: its own emission path; the
        # MFU/sweep scaffold below is for the model benches
        return run_async_compare(kind)

    if os.environ.get("BENCH_GUARD_COMPARE") == "1":
        # NaN/Inf-sentinel overhead micro-comparison (robustness layer)
        return run_guard_compare(kind)

    if os.environ.get("BENCH_SERVING_COMPARE") == "1":
        # continuous-batching vs static-batching on a mixed-length
        # generation stream (serving layer)
        return run_serving_compare(kind)

    if os.environ.get("BENCH_TELEMETRY_COMPARE") == "1":
        # request-level telemetry overhead (observability layer)
        return run_telemetry_compare(kind)

    if os.environ.get("BENCH_PREFIX_COMPARE") == "1":
        # prefix-cache sharing + speculative decoding on a mixed-tenant
        # 80%-shared-prefix stream (serving layer)
        return run_prefix_compare(kind)

    if os.environ.get("BENCH_QUANT_COMPARE") == "1":
        # int8-vs-dense quantized serving: same-HBM-budget admitted
        # concurrency, greedy exact-match rate, tokens/s (serving layer)
        return run_quant_compare(kind)

    if os.environ.get("BENCH_TIER_COMPARE") == "1":
        # tiered KV cache: host-RAM spill pool + preempt/resume on vs
        # off through a starved device pool (serving layer)
        return run_tier_compare(kind)

    if os.environ.get("BENCH_FORK_COMPARE") == "1":
        # COW-forked generation: fork groups vs independent submits
        # (peak blocks + tokens/s) + paged-beam bitwise parity +
        # guided regex, one compiled signature (serving layer)
        return run_fork_compare(kind)

    if os.environ.get("BENCH_KERNEL_V2_COMPARE") == "1":
        # paged kernel v2 vs v1 vs reference + GQA capacity at the
        # same HBM budget (serving layer)
        return run_kernel_v2_compare(kind)

    if os.environ.get("BENCH_FLEET_COMPARE") == "1":
        # fleet router: affinity-vs-random routing hit rate + p99 TTFT
        # under overload with/without SLO shedding (serving layer)
        return run_fleet_compare(kind)

    if os.environ.get("BENCH_CHAOS_RECOVERY") == "1":
        # self-healing fleet under a scripted kill/hang/poison storm:
        # time-to-full-strength + goodput (robustness layer)
        return run_chaos_recovery(kind)

    if os.environ.get("BENCH_AUTOSCALE_COMPARE") == "1":
        # SLO-driven autoscaler over a diurnal load: peak TTFT vs
        # fixed floor/ceiling fleets + the capacity each arm paid
        # (robustness layer)
        return run_autoscale_compare(kind)

    if os.environ.get("BENCH_TRACE_COMPARE") == "1":
        # fleet-wide distributed tracing on-vs-off steady-state
        # overhead + bitwise id parity (observability layer)
        return run_trace_compare(kind)

    if os.environ.get("BENCH_SIGNALS_COMPARE") == "1":
        # fleet health signals (series store + alert rules + tenant
        # ledgers) on-vs-off steady-state overhead + bitwise id
        # parity (observability layer)
        return run_signals_compare(kind)

    if os.environ.get("BENCH_COMPILE_SAMPLE") == "1":
        # compile-observatory artifact: explain() report + recompile
        # storm + HBM ledger + detector overhead (observability layer)
        return run_compile_sample(kind)

    # the model sweep measures the device: no chip, no number
    if jax.default_backend() != "tpu":
        print(f"bench: no TPU (jax.default_backend() is "
              f"{jax.default_backend()!r}); the model sweep is a device "
              f"measurement and does not run elsewhere", file=sys.stderr)
        return 2
    peak = _peak_flops(kind)
    seq_len = int(os.environ.get("BENCH_SEQ_LEN", 512))
    # BENCH_BATCHES / BENCH_STEPS widen the sweep; the persistent
    # compile cache makes a second, fuller run cheap
    n_steps = int(os.environ.get("BENCH_STEPS", 15))
    batches = [int(b) for b in
               os.environ.get("BENCH_BATCHES", "8,16").split(",")]
    # soft budget: stop sweeping more batch sizes once exceeded
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 1500))
    # hard watchdog: a compile or step that hangs past this is a
    # failure, not a shorter sweep
    hard_s = float(os.environ.get("BENCH_HARD_TIMEOUT", 3000))
    import threading

    def _hard():
        if _EMITTED:
            return          # main already printed (or is printing): let it
        print(f"bench: hard timeout after {hard_s:.0f}s with "
              f"{len(_SWEEP)} of {len(batches)} batch size(s) done — "
              f"no result", file=sys.stderr)
        os._exit(2)

    hard_timer = threading.Timer(hard_s, _hard)
    hard_timer.daemon = True
    hard_timer.start()

    hbm_limit = _hbm_limit_bytes()
    hbm_frac = float(os.environ.get("BENCH_HBM_FRACTION", 0.92))
    mem_points = []        # (batch, peak_bytes) of successful runs
    max_ok = 0             # largest batch that ran (any smaller one fits)
    oom_floor = None       # smallest batch that OOMed (larger can't fit)
    peak_poisoned = False  # an OOM pins the lifetime peak near the limit,
    #                        making later memory_stats reads meaningless

    t_start = time.perf_counter()
    for batch in batches:
        if oom_floor is not None and batch >= oom_floor:
            print(f"bench: pre-flight prune batch={batch}: batch "
                  f"{oom_floor} already OOMed", file=sys.stderr)
            continue
        if hbm_limit and batch > max_ok and mem_points:
            proj = _project_peak_bytes(mem_points, batch)
            last_peak = mem_points[-1][1]
            if proj is not None and proj > hbm_frac * hbm_limit:
                print(f"bench: pre-flight prune batch={batch}: projected "
                      f"peak {proj / 2**30:.1f}GiB > {hbm_frac:.0%} of "
                      f"{hbm_limit / 2**30:.1f}GiB HBM", file=sys.stderr)
                continue
            if proj is None and last_peak > hbm_frac * hbm_limit:
                print(f"bench: pre-flight prune batch={batch}: HBM already "
                      f"{last_peak / hbm_limit:.0%} full at batch "
                      f"{mem_points[-1][0]}", file=sys.stderr)
                continue
        try:
            r = bench_one(batch, seq_len, n_steps)
        except Exception as e:
            # running out of HBM bounds the sweep; anything else is a
            # broken program, and a partial sweep would hide it
            if not _looks_like_oom(e):
                raise
            print(f"bench: batch {batch} out of memory: {e}",
                  file=sys.stderr)
            oom_floor = batch if oom_floor is None else min(oom_floor,
                                                            batch)
            peak_poisoned = True
            continue
        max_ok = max(max_ok, batch)
        if r.get("peak_mem_gb_process") and not peak_poisoned:
            mem_points.append((batch, r["peak_mem_gb_process"] * 2**30))
        r["mfu"] = r["model_flops_per_sec"] / peak
        print(f"bench: batch={batch} {r['tokens_per_sec']:.1f} tok/s "
              f"mfu={r['mfu']:.3f} flash={r['flash_engaged']}",
              file=sys.stderr)
        _SWEEP.append(r)
        if len(_SWEEP) > 1:
            # the optimized HLO text is tens of MB for the full models;
            # keep only the best-so-far batch's copy
            best_so_far = max(_SWEEP, key=lambda x: x["tokens_per_sec"])
            for x in _SWEEP:
                if x is not best_so_far:
                    x["hlo_text"] = None
        elapsed = time.perf_counter() - t_start
        if elapsed > budget and batch != batches[-1]:
            print(f"bench: time budget {budget:.0f}s exhausted after "
                  f"batch {batch}; skipping the rest", file=sys.stderr)
            break
    hard_timer.cancel()
    sweep = _SWEEP
    if not sweep:
        print("bench: every batch size failed", file=sys.stderr)
        return 1

    _emit(sweep, seq_len, kind, peak)
    return 0




if __name__ == "__main__":
    sys.exit(main())
