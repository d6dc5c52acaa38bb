"""HLO-audit perf tripwires (VERDICT r2 item 7) — perf properties that
can regress silently and burn the scarce real-TPU window on diagnosis.
Asserted on Executor.last_compiled_text(), the optimized HLO of the
step executable that actually ran, so they hold on CPU exactly as the
equivalent property holds on TPU:

(a) one dp step emits exactly ONE all-reduce op — XLA's combiner fuses
    every gradient into a single bucket; N small all-reduces instead
    would serialize ICI latency per-tensor.
(b) after amp.cast_model_to_bf16 no f32 dot survives anywhere in the
    step — an f32 dot on the fwd/bwd path would run the MXU at half
    rate (the optimizer update math is dot-free, so the assert is
    global).
(c) remat policies actually change the compiled graph: the
    save-nothing policy recomputes forward dots in the backward pass,
    so its HLO carries strictly more dot ops than the checkpoint-dots
    policy at equal numerics.
(d) a dp x sp step carries collective-permute ops — the ring-attention
    K/V rotation; losing them means the sp auto-dispatch regressed to
    the dense O(T^2) fallback.
(e) conv analogue of (b): no f32 convolution operands after the bf16
    cast (ResNet-class models halve their MXU rate otherwise).
"""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.parallel.mesh import make_mesh

# the call site "all-reduce(" appears once per op; references like
# get-tuple-element(%all-reduce.7) don't match (no open paren after name)
_ALL_REDUCE_OP = re.compile(r"\ball-reduce(?:-start)?\(")
# StableHLO (pre-backend-opt) dot op with its full type signature
_DOT_GENERAL = re.compile(r"stablehlo\.dot_general.*")


def _mlp(depth=3, width=64):
    x = layers.data("x", shape=[32], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    h = x
    for _ in range(depth):
        h = layers.fc(h, size=width, act="relu")
    logits = layers.fc(h, size=10)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    return loss


def _feed(batch=16):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((batch, 32)).astype(np.float32),
            "label": rng.integers(0, 10, (batch, 1)).astype(np.int64)}


def test_dp_step_has_one_fused_grad_allreduce():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        compiled = fluid.CompiledProgram(main).with_mesh(make_mesh(dp=8))
        exe.run(compiled, feed=_feed(), fetch_list=[loss])
    txt = exe.last_compiled_text()
    n_ar = len(_ALL_REDUCE_OP.findall(txt))
    assert n_ar == 1, (
        f"expected ONE fused gradient all-reduce, found {n_ar} — the "
        f"combiner stopped bucketing (per-tensor ICI latency on TPU)")


def test_bf16_cast_leaves_no_f32_dots():
    from paddle_tpu import amp

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    amp.cast_model_to_bf16(main)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
    # lowered StableHLO: the CPU backend would legalize bf16 dots to
    # f32 in the OPTIMIZED text, hiding exactly the property under test
    dots = _DOT_GENERAL.findall(exe.last_lowered_text())
    assert dots, "no dots at all — the audit net lost its matmuls"
    f32 = [d for d in dots if "xf32>" in d]
    assert not f32, (
        f"{len(f32)} of {len(dots)} dots touch f32 operands after "
        f"cast_model_to_bf16 (half MXU rate on TPU): {f32[:3]}")


# the operand types of a lowered dot_general: `: (tensor<..>, tensor<..>)`
_DOT_OPERANDS = re.compile(r": \((tensor<[^>]*>), (tensor<[^>]*>)\)")


@pytest.mark.parametrize("flash_path", ["0", "1"], ids=["xla", "flash"])
def test_bf16_cast_leaves_no_f32_dots_in_bert(flash_path, monkeypatch):
    """(b) for a two-layer BERT from models/bert.py, whose attention is
    the fused multihead_attention op: after the cast no dot of the step
    takes an f32 operand, on the XLA attention path or through the flash
    kernels (interpreted here, so their tile products are in the text).
    The f32 a dot returns is its accumulator, not an operand."""
    from paddle_tpu import amp
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import flash

    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", flash_path)
    cfg = bert.bert_tiny()
    cfg.num_hidden_layers = 2
    seq_len = 64
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _feeds, loss, _mlm, _acc = bert.build_pretrain_net(
            cfg, seq_len=seq_len)
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    amp.cast_model_to_bf16(main)
    scope = Scope()
    exe = fluid.Executor()
    traces0 = flash.TRACE_COUNT_BY_DTYPE.get("bfloat16", 0)
    with scope_guard(scope):
        exe.run(startup)
        out, = exe.run(main, feed=bert.make_pretrain_feed(cfg, seq_len, 2),
                       fetch_list=[loss])
    assert np.isfinite(out).all()
    if flash_path == "1":
        assert flash.TRACE_COUNT_BY_DTYPE["bfloat16"] - traces0 == 2
    dots = _DOT_GENERAL.findall(exe.last_lowered_text())
    assert dots, "no dots at all — the audit net lost its matmuls"
    f32 = [d for d in dots
           if any(t.endswith("xf32>") for t in _DOT_OPERANDS.search(d).groups())]
    assert not f32, (
        f"{len(f32)} of {len(dots)} dots take f32 operands after "
        f"cast_model_to_bf16 (half MXU rate on TPU): {f32[:3]}")


def _dot_count(policy):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        loss = _mlp(depth=4)
        opt = fluid.optimizer.SGDOptimizer(0.1)
        if policy is not None:
            opt = fluid.optimizer.RecomputeOptimizer(opt, policy=policy)
        opt.minimize(loss)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        out, = exe.run(main, feed=_feed(), fetch_list=[loss])
    # lowered text: remat's duplicated fwd computation is visible here;
    # backend CSE could merge it in the optimized module
    return len(_DOT_GENERAL.findall(exe.last_lowered_text())), float(
        np.asarray(out).ravel()[0])


def test_remat_policies_change_saved_intermediates():
    dots_none, loss_none = _dot_count(None)
    dots_nothing, loss_nothing = _dot_count("nothing")
    dots_dots, loss_dots = _dot_count("dots")
    # numerics must not change — remat is a memory/FLOPs trade only
    assert loss_none == pytest.approx(loss_nothing, rel=1e-5)
    assert loss_none == pytest.approx(loss_dots, rel=1e-5)
    # save-nothing recomputes fwd dots in the bwd pass
    assert dots_nothing > dots_dots, (
        f"policy=nothing emitted {dots_nothing} dots vs {dots_dots} for "
        f"policy=dots — remat is not rematerializing")
    assert dots_nothing > dots_none, (
        f"policy=nothing ({dots_nothing} dots) should exceed the "
        f"no-remat baseline ({dots_none})")


def test_sp_step_emits_ring_collective_permute():
    """(d) sequence parallelism must actually ride the ring: a dp x sp
    BERT step's compiled HLO carries collective-permute ops (the K/V
    rotation). If the auto-dispatch to ring attention silently stops
    engaging, attention falls back to full T^2 per chip and the HLO
    loses the permutes — this trips before a hardware window would."""
    import jax
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import make_mesh

    cfg = bert.bert_tiny()
    seq_len, batch = 64, 4
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _feeds, total_loss, _mlm, _acc = bert.build_pretrain_net(
            cfg, seq_len=seq_len)
        fluid.optimizer.AdamOptimizer(1e-4).minimize(total_loss)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
        compiled = fluid.CompiledProgram(main).with_mesh(mesh)
        feed = bert.make_pretrain_feed(cfg, seq_len, batch)
        exe.run(compiled, feed=feed, fetch_list=[total_loss])
    txt = exe.last_compiled_text()
    n_cp = len(re.findall(r"\bcollective-permute(?:-start)?\(", txt))
    assert n_cp > 0, (
        "no collective-permute in the dp x sp step — ring attention "
        "did not engage (sequence parallelism is running the dense "
        "O(T^2) fallback)")


def test_bf16_cast_leaves_no_f32_convs():
    """(e) conv path analogue of (b): after cast_model_to_bf16 a conv
    net's lowered step must carry no f32 convolution operands — ResNet
    MFU halves if convs miss the bf16 MXU path."""
    from paddle_tpu import amp

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("img", shape=[3, 16, 16], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.conv2d(x, num_filters=8, filter_size=3, padding=1,
                          act="relu")
        h = layers.conv2d(h, num_filters=8, filter_size=3, padding=1,
                          act="relu")
        h = layers.pool2d(h, pool_size=16, pool_type="avg",
                          global_pooling=True)
        logits = layers.fc(layers.flatten(h), size=10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    amp.cast_model_to_bf16(main)
    scope = Scope()
    exe = fluid.Executor()
    rng = np.random.default_rng(0)
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={
            "img": rng.standard_normal((4, 3, 16, 16)).astype(np.float32),
            "label": rng.integers(0, 10, (4, 1)).astype(np.int64)},
            fetch_list=[loss])
    txt = exe.last_lowered_text()
    convs = re.findall(r"stablehlo\.convolution.*", txt)
    assert convs, "no convolutions in the audit net"
    f32 = [c for c in convs if "xf32>" in c]
    assert not f32, (
        f"{len(f32)} of {len(convs)} convs touch f32 operands after "
        f"cast_model_to_bf16: {f32[:2]}")


_DP_STEP_CACHE = {}


def _run_dp_step(mesh_kwargs, n_devices):
    key = tuple(sorted(mesh_kwargs.items()))
    if key in _DP_STEP_CACHE:
        return _DP_STEP_CACHE[key]
    import jax
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        mesh = make_mesh(devices=jax.devices()[:n_devices], **mesh_kwargs)
        compiled = fluid.CompiledProgram(main).with_mesh(mesh)
        exe.run(compiled, feed=_feed(), fetch_list=[loss])
    n_params = len(main.global_block().all_parameters())
    _DP_STEP_CACHE[key] = (exe.last_compiled_text(), n_params)
    return _DP_STEP_CACHE[key]


@pytest.mark.parametrize("mesh_kwargs,n_dev", [({"dp": 8}, 8),
                                               ({"dp": 2, "sp": 2}, 4)])
def test_step_has_no_host_transfers(mesh_kwargs, n_dev):
    """(f) VERDICT r3 #9: a compiled train step must stay ON DEVICE —
    any infeed/outfeed/send/recv or host memory-space annotation in the
    optimized HLO means a hidden host round-trip per step (an MFU killer
    that profiles as idle device time)."""
    txt, _ = _run_dp_step(mesh_kwargs, n_dev)
    for marker in ("infeed", "outfeed", " send(", " recv(",
                   "send-start", "recv-start", "S(5)",
                   "MoveToHost", "MoveToDevice"):
        assert marker not in txt, (
            f"host-transfer marker {marker!r} found in the compiled "
            f"{mesh_kwargs} step")


@pytest.mark.parametrize("mesh_kwargs,n_dev", [({"dp": 8}, 8),
                                               ({"dp": 2, "sp": 2}, 4)])
def test_donated_state_is_aliased(mesh_kwargs, n_dev):
    """(g) VERDICT r3 #9: the Executor donates the train state, and XLA
    must actually alias those buffers (input_output_alias in the entry
    header) — silent de-donation doubles peak HBM (params + opt state
    held twice), the difference between fitting a model and OOM."""
    txt, n_params = _run_dp_step(mesh_kwargs, n_dev)
    header = txt.splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
    assert m, f"no input_output_alias in the {mesh_kwargs} step header"
    n_alias = len(re.findall(r"\{\d+\}:", m.group(1)))
    # state = params + optimizer accumulators (momentum: one per param);
    # at minimum every parameter buffer must alias
    assert n_alias >= n_params, (
        f"only {n_alias} aliased buffers for {n_params} params in the "
        f"{mesh_kwargs} step — donation is not reaching XLA")


def test_kv_decode_scan_stays_on_device():
    """The KV-cache decode loop (gpt.generate) must
    compile to one on-device scan: a host transfer per generated token
    would turn serving latency into host round trips x max_len."""
    import jax.numpy as jnp
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt

    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    with scope_guard(Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        from paddle_tpu.core.executor import global_scope
        params = gpt.load_params(global_scope(), cfg)
    decode = gpt.make_greedy_decoder(params, cfg, max_len=16,
                                     dtype=jnp.bfloat16)
    import jax
    bos = jnp.zeros((2,), jnp.int32)
    lowered = jax.jit(decode).lower(bos)
    txt = lowered.compile().as_text()
    for marker in ("infeed", "outfeed", " send(", " recv(",
                   "send-start", "recv-start", "S(5)",
                   "MoveToHost", "MoveToDevice"):
        assert marker not in txt, (
            f"host-transfer marker {marker!r} in the decode loop")
    # bf16 serving: the KV-cache scan carry itself must be bf16 — the
    # bf16 WEIGHTS alone would satisfy a bare "bf16 in txt" check while
    # an f32 cache silently doubles the bandwidth decode is bound by.
    # Assert on the LOWERED (source-truth) IR: the CPU backend's
    # compiled HLO legalizes bf16 compute through f32 scratch buffers,
    # which is backend detail, not the serving dtype.
    # cache shape = (batch=2, heads=4, max_len=16, d=128/4=32)
    src = lowered.as_text()
    assert "bf16[2,4,16,32]" in src.replace("tensor<2x4x16x32xbf16>",
                                            "bf16[2,4,16,32]"), \
        "KV cache is not bf16 in the lowered IR"
    assert "tensor<2x4x16x32xf32>" not in src and \
        "f32[2,4,16,32]" not in src, \
        "f32 cache-shaped tensors in the bf16-serving decode source"


def test_packed_step_materializes_no_quadratic_mask(monkeypatch):
    """(h) packed-sequence attention must keep O(T) segment-id vectors
    in HBM — if the (T, T) cross-segment mask ever materializes in the
    compiled step (e.g. someone reroutes segment_ids through
    segment_mask_bias on the flash path), every encoder layer pays a
    quadratic HBM tensor and the packing win evaporates. T=96 collides
    with no other dimension of the tiny config (hidden 256, d_head 64,
    ffn 1024, vocab 1024), so any '96,96]' shape in the HLO is the
    mask."""
    from paddle_tpu.models import bert

    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    # keep the kernel's own score TILE below (T, T): with the default
    # block (128, clamped to T) the blockwise tile would itself be
    # (96, 96) and trip the scan
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "32")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "32")
    cfg = bert.bert_tiny()
    cfg.num_hidden_layers = 2
    T = 96
    feed, _n_rows = bert.make_packed_pretrain_feed(cfg, T, n_docs=6,
                                                   seed=0)
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _feeds, loss = bert.build_packed_pretrain_net(
            cfg, seq_len=T, max_predictions=feed["mask_pos"].shape[1])
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    txt = exe.last_compiled_text()
    quad = re.findall(r"\S*96,96\]\S*", txt)
    assert not quad, (
        f"(T, T) tensors materialized on the packed path: {quad[:3]}")
