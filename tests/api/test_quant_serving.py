"""Quantized serving end-to-end (ISSUE 14): int8 KV-cache blocks with
per-row f32 scales, int8 weights behind AnalysisConfig.enable_int8,
and every composition the paged stack already ships — prefix sharing,
speculative decoding, fleet handoff — running against quantized pools.

The accuracy contract is pinned as EXACT-MATCH RATE against the dense
engine on the PR-5 acceptance stream (staggered arrivals, mixed
prompt/output lengths, one mid-stream cancel): greedy ids from int8
pools must reproduce the dense ids at a floor asserted here. The
capacity contract is pinned in
BYTES: an int8 pool (scales included) costs <= 0.56x the same block
count dense in bf16, and the HBM ledger reports the true quantized
size, never the dense equivalent.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                PagedKVCache, SpecDecodeConfig)

pytestmark = [pytest.mark.serving, pytest.mark.quant]


@pytest.fixture(scope="module")
def trained():
    """Briefly-trained tiny GPT (test_serving_tp's idiom): greedy
    argmax must be decisive — int8 rounding perturbs logits by ~1e-2,
    and an untrained model's near-ties would flip on noise instead of
    measuring quantization quality."""
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _tokens, loss, _ = gpt.build_lm_net(cfg, seq_len=16)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    rng = np.random.default_rng(0)
    seq = rng.integers(3, cfg.vocab_size, (4, 16)).astype(np.int32)
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(30):
            exe.run(main, feed={"tokens": seq}, fetch_list=[loss])
        params = gpt.load_params(scope, cfg)
    return cfg, scope, params


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _drive_staggered_stream(srv):
    """The PR-5 acceptance scenario verbatim (test_serving_tp shares
    it): staggered arrivals, mixed lengths, one mid-stream cancel.
    Returns the three surviving requests' token ids."""
    p1 = np.array([5, 9, 11, 2, 7], np.int32)
    p2 = np.array([7] * 11, np.int32)
    f1 = srv.submit(p1, max_new_tokens=8)
    f2 = srv.submit(p2, max_new_tokens=6)
    for _ in range(2):
        srv.step()
    f3 = srv.submit(np.array([3, 4], np.int32), max_new_tokens=10)
    f4 = srv.submit(np.array([12, 13, 14, 15, 16, 17, 18], np.int32),
                    max_new_tokens=12)
    srv.step()
    assert f4.cancel()
    srv.run_until_idle()
    assert f4.cancelled()
    return [list(f.result(timeout=5).token_ids) for f in (f1, f2, f3)]


def _exact_match_rate(a_seqs, b_seqs):
    a = [t for s in a_seqs for t in s]
    b = [t for s in b_seqs for t in s]
    assert len(a) == len(b)
    return sum(x == y for x, y in zip(a, b)) / len(a)


# ---------------------------------------------------------------------------
# capacity: bytes pinned, scales included
# ---------------------------------------------------------------------------

def test_int8_pool_bytes_beat_056x_dense_bf16():
    """The acceptance ratio at a REALISTIC head_dim (64): int8 codes +
    per-row f32 scales <= 0.56x the same block count in dense bf16.
    (Tiny test models with head_dim 8 pay proportionally more scale
    overhead — the ratio is (D + 4) / 2D — which is exactly why the
    scale pool must be counted, never hidden.)"""
    q = PagedKVCache(4, 2, 64, 32, block_size=16, dtype=jnp.bfloat16,
                     kv_dtype="int8")
    d = PagedKVCache(4, 2, 64, 32, block_size=16, dtype=jnp.bfloat16)
    assert q.scale_bytes() > 0
    assert q.pool_bytes() == q.dense_pool_bytes(jnp.int8) + \
        q.scale_bytes()
    ratio = q.pool_bytes() / d.pool_bytes()
    assert ratio <= 0.56, ratio
    assert q.dense_pool_bytes() == d.pool_bytes()   # same blocks, bf16


def test_int8_admits_11_lanes_where_dense_admits_6_at_one_byte_budget():
    """The capacity claim as block arithmetic made observable, at
    head_dim 64: the bytes of a 26-block dense bf16 pool hold 48 int8
    blocks (scales included), so a storm of 4-block requests admits 11
    lanes (47 usable blocks) where dense admits 6 (25 usable). A
    request is a 16-token prompt + 15 new = 31 positions at block_size
    8."""
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=128, num_layers=1,
                        num_heads=2, inner_size=256, max_position=128,
                        dropout=0.0)
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor().run(startup)
        params = gpt.load_params(scope, cfg)

    def pool(nb, kv_dtype):
        return PagedKVCache(cfg.num_layers, cfg.num_heads, 64, nb,
                            block_size=8, dtype=jnp.bfloat16,
                            kv_dtype=kv_dtype)

    budget = pool(26, None).pool_bytes()
    nb_int8 = budget // (pool(2, "int8").pool_bytes() // 2)
    assert nb_int8 == 48

    def admitted(kv_dtype, nb):
        srv = GenerationServer(
            GPTServingModel(params, cfg, dtype=jnp.bfloat16),
            num_slots=12, block_size=8, max_context=96, chunk=4,
            start=False, num_blocks=nb, kv_dtype=kv_dtype)
        prompt = np.arange(3, 19, dtype=np.int32)
        for _ in range(12):
            srv.submit(prompt, max_new_tokens=15)
        srv._sched.plan()       # admission only: no device step
        got = srv._sched.active_count
        srv.close(drain=False)
        return got

    assert admitted(None, 26) == 6
    assert admitted("int8", nb_int8) == 11


def test_ledger_reports_true_quantized_bytes(trained):
    """get_stats()["memory"] kv rows carry int8+scales bytes — the
    watermark/capacity math (shrink-by-tp from PR 9 included) keys off
    pool_bytes, so a dense-f32-sized row would overstate residency
    ~3.5x."""
    cfg, _scope, params = trained
    srv = _server(params, cfg, kv_dtype="int8")
    try:
        st = srv.get_stats()
        assert st["memory"]["kv_cache"] == srv.cache.pool_bytes()
        assert srv.cache.pool_bytes() < srv.cache.dense_pool_bytes()
        kq = st["kv_quant"]
        assert kq["kv_dtype"] == "int8"
        assert kq["pool_bytes"] == srv.cache.pool_bytes()
        assert kq["scale_bytes"] == srv.cache.scale_bytes()
        assert kq["dense_equiv_bytes"] == srv.cache.dense_pool_bytes()
        assert 0 < kq["bytes_ratio_vs_dense"] < 1
        # shard byte math stays consistent (tp=1: shard == logical)
        assert srv.cache.shard_pool_bytes() == srv.cache.pool_bytes()
    finally:
        srv.close()


def test_quant_gauges_published_and_retired(trained):
    cfg, _scope, params = trained
    srv = _server(params, cfg, kv_dtype="int8")
    reg = global_registry()
    label = {"server": srv._ledger_id}
    g_pool = reg.gauge("serving.kv.quant.pool_bytes")
    g_saved = reg.gauge("serving.kv.quant.bytes_saved")
    assert g_pool.labels(**label).value() == srv.cache.pool_bytes()
    assert g_saved.labels(**label).value() == \
        srv.cache.dense_pool_bytes() - srv.cache.pool_bytes()
    srv.close()
    # a closed server must not keep reporting a quantization saving:
    # both series drop their label set on close (either close path)
    assert label not in [lbl for lbl, _c in g_pool.series()]
    assert label not in [lbl for lbl, _c in g_saved.series()]


def test_dense_server_has_no_quant_surface(trained):
    cfg, _scope, params = trained
    srv = _server(params, cfg)
    try:
        st = srv.get_stats()
        assert st["kv_quant"] is None
        assert not srv.cache.quantized
        assert srv.cache.scale_bytes() == 0
        assert srv.cache.pool_bytes() == srv.cache.dense_pool_bytes()
    finally:
        srv.close()


def test_kv_dtype_bf16_alias(trained):
    cfg, _scope, params = trained
    srv = _server(params, cfg, kv_dtype="bf16")
    try:
        assert srv.cache.dtype == jnp.bfloat16
        assert not srv.cache.quantized
        fut = srv.submit([5, 9, 11], max_new_tokens=4)
        srv.run_until_idle()
        assert len(fut.result(timeout=5).token_ids) == 4
        assert srv.get_stats()["kernel"]["engaged"] is True
    finally:
        srv.close()


def test_bad_kv_dtype_raises():
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache(1, 2, 8, 4, kv_dtype="fp8")


# ---------------------------------------------------------------------------
# accuracy: the acceptance stream, int8 vs dense
# ---------------------------------------------------------------------------

def test_staggered_stream_int8_exact_match_floor(trained):
    """THE accuracy pin: int8 KV greedy ids vs dense on the staggered
    mixed-length stream with a mid-stream cancel. The floor is
    asserted here and the measured rate recorded in the failure
    message; the invariants around it (one signature, kernel engaged,
    every block reclaimed) must survive quantization untouched."""
    cfg, _scope, params = trained
    dense = _server(params, cfg)
    dense_ids = _drive_staggered_stream(dense)
    dense.close()
    q = _server(params, cfg, kv_dtype="int8")
    q_ids = _drive_staggered_stream(q)
    rate = _exact_match_rate(dense_ids, q_ids)
    assert rate >= 0.9, f"int8 exact-match rate {rate} < 0.9 floor"
    st = q.get_stats()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"]["engaged"] is True
    assert st["blocks_free"] == st["blocks_total"]
    assert st["cancelled"] == 1 and st["retired"] == 3
    q.close()


def test_int8_weights_exact_match_floor(trained):
    """int8 weights ON TOP of int8 KV (the full enable_int8 stack) vs
    the dense server — the weight-side accuracy delta pin."""
    cfg, _scope, params = trained
    dense = _server(params, cfg)
    dense_ids = _drive_staggered_stream(dense)
    dense.close()
    model = GPTServingModel(params, cfg).quantize_int8()
    assert model.int8_weights == 6 * cfg.num_layers
    # idempotent: a second call must not re-quantize quantized codes
    assert model.quantize_int8().int8_weights == 6 * cfg.num_layers
    srv = GenerationServer(model, num_slots=3, block_size=8,
                           max_context=64, chunk=4, start=False,
                           kv_dtype="int8")
    w_ids = _drive_staggered_stream(srv)
    rate = _exact_match_rate(dense_ids, w_ids)
    assert rate >= 0.9, f"int8 weights+KV exact-match {rate} < 0.9"
    assert srv.get_stats()["fused_step_signatures"] == 1
    assert srv.get_stats()["kv_quant"]["int8_weights"] == \
        6 * cfg.num_layers
    srv.close()


def test_int8_weights_under_mesh_raise(trained):
    """The documented limit: int8 weights are single-device for now
    (the tp shard rules name the dense weight keys) — a mesh build
    must fail loudly, not serve silently-wrong shardings."""
    import jax
    from jax.sharding import Mesh
    cfg, _scope, params = trained
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    m = GPTServingModel(params, cfg).quantize_int8()
    with pytest.raises(NotImplementedError, match="int8 weights"):
        m.build_fused_step(8, mesh=mesh)


# ---------------------------------------------------------------------------
# composition: prefix sharing + spec decode on int8 pools
# ---------------------------------------------------------------------------

def test_prefix_sharing_on_int8_pools(trained):
    """Shared-prefix requests on quantized pools: the chain index
    carries block ids, the scales ride the parallel pool by the same
    id, so hits/refcounts/COW behave identically — and a full-cover
    COW copies the scale rows with the codes."""
    cfg, _scope, params = trained
    srv = _server(params, cfg, kv_dtype="int8", prefix_cache=True)
    try:
        shared = np.arange(3, 19, dtype=np.int32)       # 2 full chunks
        # first tenant prefills (and registers) the shared chunks...
        f0 = srv.submit(np.concatenate([shared, [40]]).astype(np.int32),
                        max_new_tokens=4)
        srv.run_until_idle()
        # ...later arrivals match them instead of re-prefilling
        futs = [f0] + [srv.submit(np.concatenate(
            [shared, [41 + i]]).astype(np.int32), max_new_tokens=4)
            for i in range(2)]
        srv.run_until_idle()
        ids = [list(f.result(timeout=5).token_ids) for f in futs]
        st = srv.get_stats()
        assert st["prefix"]["hits"] > 0
        assert st["fused_step_signatures"] == 1
        assert st["kernel"]["engaged"] is True
        assert all(len(i) == 4 for i in ids)
        # full-cover COW path on quantized pools: same prompt twice
        f_a = srv.submit(shared, max_new_tokens=3)
        srv.run_until_idle()
        f_b = srv.submit(shared, max_new_tokens=3)
        srv.run_until_idle()
        assert list(f_a.result(timeout=5).token_ids) == \
            list(f_b.result(timeout=5).token_ids)
        assert st["prefix"] is not None
    finally:
        srv.close()


def test_spec_decode_on_int8_pools(trained):
    """Speculative decoding with int8 target AND draft pools: greedy
    acceptance stays bitwise vs the plain int8 server (every committed
    id is the target's), inside the <=2-signature budget."""
    cfg, _scope, params = trained
    dcfg = gpt.GPTConfig(vocab_size=cfg.vocab_size, hidden_size=64,
                         num_layers=2, num_heads=2, inner_size=256,
                         max_position=cfg.max_position, dropout=0.0)
    dmain, dstart = framework.Program(), framework.Program()
    dmain.random_seed = dstart.random_seed = 21
    with framework.program_guard(dmain, dstart):
        gpt.build_lm_net(dcfg, seq_len=8)
    dscope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(dscope):
        exe.run(dstart)
        dparams = gpt.load_params(dscope, dcfg)
    plain = _server(params, cfg, kv_dtype="int8")
    f0 = plain.submit([5, 9, 11], max_new_tokens=8)
    plain.run_until_idle()
    plain_ids = list(f0.result(timeout=5).token_ids)
    plain.close()
    spec = _server(params, cfg, kv_dtype="int8",
                   spec=SpecDecodeConfig(GPTServingModel(dparams, dcfg),
                                         k=3))
    assert spec._draft_cache.quantized      # draft pool halves too
    f1 = spec.submit([5, 9, 11], max_new_tokens=8)
    spec.run_until_idle()
    assert list(f1.result(timeout=5).token_ids) == plain_ids
    st = spec.get_stats()
    assert st["compiled_step_signatures"] <= 2
    spec.close()


# ---------------------------------------------------------------------------
# fleet handoff: adopt_block_from validation + scale carry
# ---------------------------------------------------------------------------

def test_adopt_block_carries_scales_between_quantized_pools():
    from paddle_tpu.serving import kv_cache as kvc
    src = PagedKVCache(2, 2, 8, 6, block_size=4, dtype=jnp.float32,
                       kv_dtype="int8")
    dst = PagedKVCache(2, 2, 8, 9, block_size=4, dtype=jnp.float32,
                       kv_dtype="int8")      # num_blocks may differ
    rng = np.random.default_rng(1)
    k, v = (jnp.asarray(rng.standard_normal((1, 4, 2, 8)), jnp.float32)
            for _ in range(2))
    bidx = np.full((1, 4), 2, np.int32)
    off = np.arange(4, dtype=np.int32)[None, :]
    for li in range(2):
        p = src.pools[li]
        kvp, ks, vs = kvc.write_block_kv_quant(
            p["kv"], p["k_scale"], p["v_scale"], k, v, bidx, off)
        src.pools[li] = dict(kv=kvp, k_scale=ks, v_scale=vs)
    want = [{n: np.asarray(a[2]) for n, a in p.items()}
            for p in src.pools]
    dst.adopt_block_from(src, 2, 5)
    for li in range(2):
        for name in ("kv", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(dst.pools[li][name][5]), want[li][name])
        # K and V codes differ, and each half kept its own scale rows
        kq, vq = kvc.split_kv(want[li]["kv"])
        assert (kq != vq).any()
        assert (want[li]["k_scale"] != want[li]["v_scale"]).any()


def test_adopt_block_quantized_dense_mismatch_raises():
    """The ISSUE-14 bugfix pin: a quantized<->dense handoff must raise
    the friendly ValueError, BOTH directions, instead of astype-copying
    garbage KV into the decode tier."""
    q = PagedKVCache(1, 2, 8, 4, block_size=4, dtype=jnp.float32,
                     kv_dtype="int8")
    d = PagedKVCache(1, 2, 8, 4, block_size=4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="quantized and a dense"):
        d.adopt_block_from(q, 1, 1)
    with pytest.raises(ValueError, match="quantized and a dense"):
        q.adopt_block_from(d, 1, 1)
    # dense<->dense float casts remain legitimate (bf16 prefill tier
    # feeding an f32 decode tier)
    b = PagedKVCache(1, 2, 8, 4, block_size=4, dtype=jnp.bfloat16)
    d.adopt_block_from(b, 1, 1)
    # geometry mismatch still raises its own message first
    g = PagedKVCache(1, 2, 4, 4, block_size=4, dtype=jnp.float32,
                     kv_dtype="int8")
    with pytest.raises(ValueError, match="matching pool geometry"):
        q.adopt_block_from(g, 1, 1)


def test_fleet_router_rejects_mixed_quantization(trained):
    """A mixed quantized/dense fleet must fail at CONSTRUCTION, not
    when the first shared-prefix handoff hits adopt_block_from's
    mismatch error inside the router worker."""
    from paddle_tpu.serving import FleetRouter
    cfg, _scope, params = trained
    dense = _server(params, cfg, prefix_cache=True)
    quant = _server(params, cfg, prefix_cache=True, kv_dtype="int8")
    try:
        with pytest.raises(ValueError, match="kv_dtype"):
            FleetRouter([dense, quant], start=False)
        # a uniformly-quantized fleet constructs (and closes) fine
        q2 = _server(params, cfg, prefix_cache=True, kv_dtype="int8")
        router = FleetRouter([quant, q2], start=False)
        router.close()
    finally:
        dense.close()


# ---------------------------------------------------------------------------
# AnalysisConfig.enable_int8 (the Fluid quant/ -> TPU mapping)
# ---------------------------------------------------------------------------

def test_enable_int8_program_path_accuracy_and_metrics(tmp_path):
    """Weight+activation PTQ on the Predictor program path: per-channel
    weight rewrite + calibrated static activation scales, output delta
    bounded, inference.int8.* counters moved."""
    from paddle_tpu import inference, layers
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        out = layers.fc(h, size=4)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path / "mlp"), ["x"],
                                      [out], exe, main_program=main)
    rng = np.random.default_rng(0)
    feeds = [{"x": rng.standard_normal((4, 8)).astype(np.float32)}
             for _ in range(4)]
    p_fp = inference.create_predictor(
        inference.AnalysisConfig(str(tmp_path / "mlp")))
    ref = p_fp.run(feeds[0])[0]
    reg = global_registry()
    w0 = reg.counter("inference.int8.weights").value()
    a0 = reg.counter("inference.int8.calibrated_activations").value()
    p_q = inference.create_predictor(
        inference.AnalysisConfig(str(tmp_path / "mlp"))
        .enable_int8(calibration_feeds=feeds))
    got = p_q.run(feeds[0])[0]
    assert p_q.int8_weight_tensors == 2        # both fc weights
    assert p_q.int8_calibrated_activations >= 1
    assert reg.counter("inference.int8.weights").value() == \
        w0 + p_q.int8_weight_tensors
    assert reg.counter(
        "inference.int8.calibrated_activations").value() == \
        a0 + p_q.int8_calibrated_activations
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.1, rel
    # per-channel: the inserted weight quant op carries quant_axis=1
    qops = [op for op in p_q.program.global_block().ops
            if op.type.startswith("fake_channel_wise_quantize")]
    assert qops and all(op.attr("quant_axis") == 1 for op in qops)


def test_enable_int8_generation_end_to_end(trained, tmp_path):
    """enable_int8 + enable_generation: the served engine runs int8
    weights AND int8 KV, matches the dense predictor server's ids at
    the accuracy floor, and keeps the one-signature budget."""
    from paddle_tpu import inference
    cfg, scope, _params = trained
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _tokens, _loss, logits = gpt.build_lm_net(cfg, seq_len=8)
    with scope_guard(scope):
        exe = fluid.Executor()
        fluid.io.save_inference_model(str(tmp_path / "gpt"), ["tokens"],
                                      [logits], exe, main_program=main)

    def serve(acfg):
        acfg.enable_generation(cfg, num_slots=2, block_size=8,
                               max_context=64, chunk=4)
        srv = inference.create_predictor(acfg).generation_server(
            start=False)
        fut = srv.submit([5, 9, 11], max_new_tokens=8)
        srv.run_until_idle()
        ids = list(fut.result(timeout=5).token_ids)
        st = srv.get_stats()
        srv.close()
        return ids, st

    dense_ids, _ = serve(inference.AnalysisConfig(str(tmp_path / "gpt")))
    q_ids, qst = serve(inference.AnalysisConfig(str(tmp_path / "gpt"))
                       .enable_int8())
    rate = sum(a == b for a, b in zip(dense_ids, q_ids)) / len(dense_ids)
    assert rate >= 0.9, f"enable_int8 generation exact-match {rate}"
    assert qst["kv_quant"]["kv_dtype"] == "int8"
    assert qst["kv_quant"]["int8_weights"] == 6 * cfg.num_layers
    assert qst["fused_step_signatures"] == 1
    assert qst["kernel"]["engaged"] is True
