"""Grouped-query attention end to end through the serving engine
(ISSUE 16): ``GPTConfig(kv_heads=)`` + ``PagedKVCache(num_kv_heads=)``.

The acceptance argument rides on the param helpers
(models/gpt.gqa_slice_kv_params / gqa_repeat_kv_params): slicing keeps
each group's FIRST head's wk/wv columns (bk/bv rows) and repeating
expands them back — an exact round trip — so a GQA server and a
repeat-KV MHA server compute the SAME attention values and must emit
BITWISE-identical token ids through a mixed-length staggered stream
with a mid-stream cancel, on one fused-step signature, while the GQA
pools hold exactly H/H_kv fewer bytes.

Also pinned here: construction-time validation (H % H_kv, model vs
server), adopt_block_from's both-geometries mismatch message, the HBM
ledger/get_stats H_kv truth (heads vs q_heads, kv_quant's
dense_equiv_bytes on the H_kv geometry), int8 x GQA composition, and
engine engagement on kernel v2 (the auto VMEM ceiling forced down).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.serving import GenerationServer, GPTServingModel
from paddle_tpu.serving import kv_cache as kvc

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.gpt_tiny()                      # 4 heads -> groups of 2
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, gpt.load_params(scope, cfg)


def _gqa_cfg(cfg, kv_heads):
    return gpt.GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        inner_size=cfg.inner_size, max_position=cfg.max_position,
        dropout=0.0, kv_heads=kv_heads)


def _server(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    kw.setdefault("telemetry", False)
    return GenerationServer(model, **kw)


def _staggered_stream(srv):
    """The acceptance traffic: staggered arrivals, mixed prompt and
    output lengths, one mid-stream cancel."""
    f1 = srv.submit(np.array([5, 9, 11, 2, 7], np.int32),
                    max_new_tokens=8)
    f2 = srv.submit(np.array([7] * 11, np.int32), max_new_tokens=6)
    for _ in range(2):
        srv.step()
    f3 = srv.submit(np.array([3, 4], np.int32), max_new_tokens=10)
    f4 = srv.submit(np.array([12, 13, 14, 15, 16, 17, 18], np.int32),
                    max_new_tokens=12)
    srv.step()
    assert f4.cancel()
    srv.run_until_idle()
    ids = [list(f.result(timeout=5).token_ids) for f in (f1, f2, f3)]
    assert f4.cancelled()
    st = srv.get_stats()
    srv.close()
    return ids, st


# ---------------------------------------------------------------------------
# the param helpers the bitwise argument rides on
# ---------------------------------------------------------------------------

def test_gqa_param_helpers_round_trip_exact(tiny_gpt):
    cfg, params = tiny_gpt
    sliced = gpt.gqa_slice_kv_params(params, cfg, 2)
    l0, s0 = params["l0"], sliced["l0"]
    h, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    assert s0["wk"].shape == (cfg.hidden_size, 2 * d)
    assert s0["bv"].shape == (2 * d,)
    assert l0["wq"] is s0["wq"]               # q/o untouched, not copied
    # repeat expands back; re-slicing recovers the sliced tree EXACTLY
    rep = gpt.gqa_repeat_kv_params(sliced, cfg, 2)
    assert rep["l0"]["wk"].shape == (cfg.hidden_size, h * d)
    again = gpt.gqa_slice_kv_params(rep, cfg, 2)
    np.testing.assert_array_equal(np.asarray(again["l0"]["wk"]),
                                  np.asarray(s0["wk"]))
    np.testing.assert_array_equal(np.asarray(again["l0"]["bv"]),
                                  np.asarray(s0["bv"]))
    for fn in (gpt.gqa_slice_kv_params, gpt.gqa_repeat_kv_params):
        with pytest.raises(ValueError, match="must divide num_heads"):
            fn(params, cfg, 3)


# ---------------------------------------------------------------------------
# acceptance: GQA server bitwise vs repeat-KV MHA server
# ---------------------------------------------------------------------------

def test_gqa_stream_bitwise_matches_repeat_kv_dense(tiny_gpt,
                                                    monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    kv = 2
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
    rep_params = gpt.gqa_repeat_kv_params(gqa_params, cfg, kv)

    srv_gqa = _server(GPTServingModel(gqa_params, _gqa_cfg(cfg, kv)))
    assert srv_gqa.cache.num_kv_heads == kv
    assert srv_gqa.cache.num_heads == cfg.num_heads
    ids_gqa, st_gqa = _staggered_stream(srv_gqa)

    srv_rep = _server(GPTServingModel(rep_params, cfg))
    assert srv_rep.cache.num_kv_heads == cfg.num_heads
    ids_rep, st_rep = _staggered_stream(srv_rep)

    assert ids_gqa == ids_rep                 # BITWISE, whole stream
    for st in (st_gqa, st_rep):
        assert st["fused_step_signatures"] == 1
        assert st["kernel"]["engaged"] is True
        assert st["kernel"]["fallback_dispatches"] == 0
        assert st["cancelled"] == 1 and st["retired"] == 3
        assert st["blocks_free"] == st["blocks_total"]
    # the pool's block carries H_kv heads, and says so
    assert st_gqa["kernel"]["pool_block_shape"][0] == kv
    assert st_rep["kernel"]["pool_block_shape"][0] == cfg.num_heads


def test_gqa_engages_kernel_v2(tiny_gpt, monkeypatch):
    """Force the auto VMEM ceiling to zero so the GQA server's fused
    step traces the STREAMING kernel — ids must not move (v2's online
    softmax is argmax-stable at this scale) and the engine must report
    the generation it compiled."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    kv = 2
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
    srv1 = _server(GPTServingModel(gqa_params, _gqa_cfg(cfg, kv)))
    ids_v1, st_v1 = _staggered_stream(srv1)
    assert st_v1["kernel"]["version"] == "v1"
    assert st_v1["kernel"]["name"] == "paged_attention_v1"
    monkeypatch.setenv("PADDLE_TPU_PAGED_V2_AUTO_BYTES", "1")
    srv2 = _server(GPTServingModel(gqa_params, _gqa_cfg(cfg, kv)))
    ids_v2, st_v2 = _staggered_stream(srv2)
    assert st_v2["kernel"]["engaged"] is True
    assert st_v2["kernel"]["version"] == "v2"
    assert st_v2["kernel"]["name"] == "paged_attention_v2"
    assert st_v2["kernel"]["fallback_dispatches"] == 0
    assert ids_v2 == ids_v1


# ---------------------------------------------------------------------------
# capacity: pool bytes divide by exactly H/H_kv, ledger/stats H_kv truth
# ---------------------------------------------------------------------------

def test_gqa_pool_bytes_divide_by_group_factor():
    mha = kvc.PagedKVCache(4, 4, 32, 9, block_size=8)
    gqa = kvc.PagedKVCache(4, 4, 32, 9, block_size=8, num_kv_heads=2)
    mqa = kvc.PagedKVCache(4, 4, 32, 9, block_size=8, num_kv_heads=1)
    assert mha.pool_bytes() == 2 * gqa.pool_bytes()
    assert mha.pool_bytes() == 4 * mqa.pool_bytes()
    assert gqa.pools[0]["kv"].shape == (9, 2, 8, 2 * 32)
    # int8 composes: codes AND scales shrink with H_kv, and the dense
    # equivalent stays on the SAME H_kv geometry (the honest
    # denominator — the GQA saving is a separate factor)
    q_mha = kvc.PagedKVCache(4, 4, 32, 9, block_size=8,
                             kv_dtype="int8")
    q_gqa = kvc.PagedKVCache(4, 4, 32, 9, block_size=8,
                             kv_dtype="int8", num_kv_heads=2)
    assert q_mha.pool_bytes() == 2 * q_gqa.pool_bytes()
    assert q_mha.scale_bytes() == 2 * q_gqa.scale_bytes()
    assert q_mha.dense_pool_bytes() == 2 * q_gqa.dense_pool_bytes()
    assert q_gqa.pools[0]["k_scale"].shape == (9, 2, 8)


def test_gqa_admits_twice_the_lanes_at_one_byte_budget(tiny_gpt,
                                                       monkeypatch):
    """The same arithmetic made observable: the bytes of a 26-block
    MHA pool hold 52 blocks at H_kv = H/2, so a storm of 4-block
    requests (16-token prompt + 15 new at block_size 8) admits 12
    lanes (51 usable blocks) where MHA admits 6 (25 usable)."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, 2)

    def pool_bytes(nb, kv_heads):
        return kvc.PagedKVCache(cfg.num_layers, cfg.num_heads, 32, nb,
                                block_size=8,
                                num_kv_heads=kv_heads).pool_bytes()

    nb_gqa = pool_bytes(26, 4) // (pool_bytes(2, 2) // 2)
    assert nb_gqa == 52

    def admitted(model, nb):
        srv = _server(model, num_slots=13, max_context=96, num_blocks=nb)
        prompt = np.arange(3, 19, dtype=np.int32)
        for _ in range(13):
            srv.submit(prompt, max_new_tokens=15)
        srv._sched.plan()       # admission only: no device step
        got = srv._sched.active_count
        srv.close(drain=False)
        return got

    assert admitted(GPTServingModel(params, cfg), 26) == 6
    assert admitted(GPTServingModel(gqa_params, _gqa_cfg(cfg, 2)),
                    nb_gqa) == 12


def test_gqa_ledger_and_stats_report_kv_truth(tiny_gpt, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    kv = 2
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
    srv = _server(GPTServingModel(gqa_params, _gqa_cfg(cfg, kv)),
                  kv_dtype="int8")
    try:
        from paddle_tpu.observability.compile_insight import hbm_ledger
        rows = {r["name"]: r
                for r in hbm_ledger().snapshot()["entries"]
                if r["component"] == srv._ledger_id}
        det = rows["kv_pool"]["detail"]
        # physical head count vs model-side head count, both on the row
        assert det["heads"] == kv
        assert det["q_heads"] == cfg.num_heads
        assert rows["kv_pool"]["bytes"] == srv.cache.pool_bytes()
        assert det["dense_equiv_bytes"] == srv.cache.dense_pool_bytes()
        fut = srv.submit([5, 9, 11], max_new_tokens=4)
        srv.run_until_idle()
        assert len(fut.result(timeout=5).token_ids) == 4
        st = srv.get_stats()
        q = st["kv_quant"]
        assert q["pool_bytes"] == srv.cache.pool_bytes()
        assert q["dense_equiv_bytes"] == srv.cache.dense_pool_bytes()
        assert q["pool_bytes"] < q["dense_equiv_bytes"]
        assert st["kernel"]["engaged"] is True
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# construction-time validation + adopt_block_from geometry
# ---------------------------------------------------------------------------

def test_gqa_bad_geometry_raises_at_construction(tiny_gpt):
    cfg, params = tiny_gpt
    with pytest.raises(ValueError, match="must divide num_heads"):
        kvc.PagedKVCache(4, 4, 32, 9, block_size=8, num_kv_heads=3)
    with pytest.raises(ValueError, match="must divide num_heads"):
        GPTServingModel(params, _gqa_cfg(cfg, 3))

    # a model object whose kv_heads dodged GPTServingModel's own check
    # still cannot build a server (GenerationServer validates too —
    # third-party model shims included)
    class Shim:
        pass

    model = GPTServingModel(params, cfg)
    shim = Shim()
    shim.__dict__.update(model.__dict__)
    shim.__class__ = type("ShimModel", (GPTServingModel,),
                          {"num_kv_heads": 3})
    with pytest.raises(ValueError, match="must divide num_heads"):
        _server(shim)


def test_adopt_block_rejects_mismatched_kv_heads():
    src = kvc.PagedKVCache(2, 4, 16, 6, block_size=8, num_kv_heads=2)
    dst = kvc.PagedKVCache(2, 4, 16, 6, block_size=8, num_kv_heads=4)
    with pytest.raises(ValueError, match=r"H_kv=2.*H_kv=4"):
        dst.adopt_block_from(src, 1, 1)
    # matching H_kv transfers fine (num_blocks may differ)
    dst2 = kvc.PagedKVCache(2, 4, 16, 9, block_size=8, num_kv_heads=2)
    src.pools = [{k: v.at[1].set(1.0) for k, v in p.items()}
                 for p in src.pools]
    dst2.adopt_block_from(src, 1, 3)
    assert float(np.asarray(dst2.pools[0]["kv"][3]).min()) == 1.0
