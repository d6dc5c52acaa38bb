"""Continuous-batching serving engine (paddle_tpu/serving/).

Tiering: everything here is tier-1 (`serving` marker, no sleeps — time
comes from injected clocks; the one threaded test only blocks on
Future.result timeouts). The contract under test:

- the paged KV pool allocates/frees blocks and reports utilization;
- paged attention == dense attention (the kernel-level spec);
- the scheduler admits by priority, chunk-prefills, backpressures on
  the block watermark, cancels on deadline (injected clock) and client
  cancel, and reclaims blocks every time;
- the engine serves a mixed-length staggered stream with EXACTLY ONE
  compiled fused-step signature, streams tokens, and drains on close;
- the Predictor/AnalysisConfig.enable_generation entry point works end
  to end from a saved model dir.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.inference import decoding as dec
from paddle_tpu.models import gpt
from paddle_tpu.robustness import ChaosInjector
from paddle_tpu.serving import (DeadlineExceeded, GenerationServer,
                                GPTServingModel, PagedKVCache,
                                SpecDecodeConfig)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, scope, gpt.load_params(scope, cfg)


def _reference_greedy(params, cfg, prompt, n_new, max_len=64):
    """Dense-cache per-token loop: teacher-force the prompt, then
    greedy — the engine must reproduce these ids exactly."""
    d = cfg.hidden_size // cfg.num_heads
    step = gpt.build_kv_step(params, cfg, max_len)
    cache = dec.init_kv_cache(1, cfg.num_layers, cfg.num_heads, max_len, d)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = step(jnp.asarray([tok], jnp.int32), cache, t)
    out = []
    t = len(prompt)
    cur = int(np.argmax(np.asarray(logits)[0]))
    out.append(cur)
    for _ in range(n_new - 1):
        logits, cache = step(jnp.asarray([cur], jnp.int32), cache, t)
        cur = int(np.argmax(np.asarray(logits)[0]))
        out.append(cur)
        t += 1
    return out


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------

def test_pool_allocate_free_accounting():
    pool = PagedKVCache(num_layers=2, num_heads=2, head_dim=4,
                        num_blocks=9, block_size=4)
    assert pool.usable_blocks == 8 and pool.num_free == 8
    a = pool.allocate(3)
    b = pool.allocate(5)
    assert pool.num_free == 0 and pool.allocate(1) is None
    assert serving.NULL_BLOCK not in a + b
    assert pool.utilization() == 1.0
    pool.free(a)
    assert pool.num_free == 3
    assert pool.blocks_for_tokens(9) == 3   # ceil(9/4)
    with pytest.raises(ValueError):
        pool.free([serving.NULL_BLOCK])


def test_paged_attention_matches_dense():
    """The pure-JAX paged op is the semantic spec: gather-by-table plus
    position masking must equal dense attention over the same KV."""
    rng = np.random.default_rng(0)
    b, h, c, d, bs, m = 2, 2, 3, 4, 4, 4
    t_max = m * bs
    k = rng.standard_normal((b, h, t_max, d)).astype(np.float32)
    v = rng.standard_normal((b, h, t_max, d)).astype(np.float32)
    q = rng.standard_normal((b, h, c, d)).astype(np.float32)
    q_pos = np.array([[4, 5, 6], [9, 10, 11]], np.int32)
    # scatter the dense KV into a shuffled pool via per-row tables
    pool_k = np.zeros((1 + b * m, h, bs, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    tables = np.zeros((b, m), np.int32)
    order = rng.permutation(np.arange(1, 1 + b * m))
    for i in range(b):
        for j in range(m):
            blk = order[i * m + j]
            tables[i, j] = blk
            pool_k[blk] = k[i, :, j * bs:(j + 1) * bs, :]
            pool_v[blk] = v[i, :, j * bs:(j + 1) * bs, :]
    out = serving.paged_attention(
        jnp.asarray(q), serving.fuse_kv(pool_k, pool_v),
        jnp.asarray(tables), jnp.asarray(q_pos))
    # dense reference with the same masking + f32 softmax
    s = np.einsum("bhcd,bhtd->bhct", q, k) / np.sqrt(d)
    mask = np.arange(t_max)[None, None, None, :] <= q_pos[:, None, :, None]
    s = np.where(mask, s, -1e9)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhct,bhtd->bhcd", p, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# scheduler behavior (engine-driven, manual pump, injected clocks)
# ---------------------------------------------------------------------------

def test_mixed_length_stream_one_signature_and_exact_tokens(tiny_gpt):
    """The acceptance scenario: staggered arrivals, different prompt and
    output lengths, one mid-stream cancel — every surviving request gets
    exactly the dense-reference ids, and the whole run compiles ONE
    fused-step signature."""
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg)
    p1 = np.array([5, 9, 11, 2, 7], np.int32)
    p2 = np.array([7] * 11, np.int32)
    f1 = srv.submit(p1, max_new_tokens=8)
    f2 = srv.submit(p2, max_new_tokens=6)
    for _ in range(2):              # two iterations in, then more arrive
        srv.step()
    p3 = np.array([3, 4], np.int32)
    p4 = np.array([12, 13, 14, 15, 16, 17, 18], np.int32)
    f3 = srv.submit(p3, max_new_tokens=10)
    f4 = srv.submit(p4, max_new_tokens=12)
    srv.step()
    assert f4.cancel()              # mid-stream cancel
    srv.run_until_idle()
    for fut, prompt, n in ((f1, p1, 8), (f2, p2, 6), (f3, p3, 10)):
        res = fut.result(timeout=5)
        assert res.finish_reason == "length"
        assert list(res.token_ids) == _reference_greedy(params, cfg,
                                                        prompt, n)
    assert f4.cancelled()
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1, st
    assert st["cancelled"] == 1 and st["retired"] == 3
    assert st["blocks_free"] == st["blocks_total"]   # everything reclaimed
    assert st["active_slots"] == 0 and st["queue_depth"] == 0


def test_eos_stops_generation(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    prompt = np.array([5, 9, 11], np.int32)
    ref = _reference_greedy(params, cfg, prompt, 8)
    eos = ref[2]                    # the third generated token, as eos
    k = ref.index(eos)              # (may repeat earlier — stop there)
    srv = _server(params, cfg)
    res = srv.submit(prompt, max_new_tokens=8, eos_id=eos)
    srv.run_until_idle()
    out = res.result(timeout=5)
    assert out.finish_reason == "eos"
    assert list(out.token_ids) == ref[:k + 1]   # stops AT the eos token


def test_priority_order_and_fifo_within_priority(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg, num_slots=1)
    finish_order = []
    futs = {}
    futs["first"] = srv.submit([5, 6], max_new_tokens=2)
    srv.step()                      # "first" owns the only slot
    futs["low"] = srv.submit([7, 8], max_new_tokens=2, priority=5)
    futs["high"] = srv.submit([9, 10], max_new_tokens=2, priority=0)
    futs["low2"] = srv.submit([11, 12], max_new_tokens=2, priority=5)
    for name, f in futs.items():
        f.add_done_callback(lambda _f, n=name: finish_order.append(n))
    srv.run_until_idle()
    assert finish_order == ["first", "high", "low", "low2"]


def test_watermark_backpressure_defers_admission(tiny_gpt):
    """Pool sized for ~one request: the second stays QUEUED (not
    failed) until the first retires and frees its blocks."""
    cfg, _scope, params = tiny_gpt
    # 4 usable blocks x 8 = 32 positions; each request reserves
    # ceil((4+20)/8)=3 blocks, so two cannot run concurrently
    srv = _server(params, cfg, num_blocks=5, max_context=32,
                  num_slots=3)
    f1 = srv.submit([5, 6, 7, 8], max_new_tokens=20)
    f2 = srv.submit([9, 10, 11, 12], max_new_tokens=20)
    srv.step()
    st = srv.get_stats()
    assert st["active_slots"] == 1 and st["queue_depth"] == 1
    srv.run_until_idle()
    assert len(f1.result(5).token_ids) == 20
    assert len(f2.result(5).token_ids) == 20
    assert srv.get_stats()["blocks_free"] == 4


def test_explicit_watermark_keeps_headroom(tiny_gpt):
    """watermark_blocks holds admission even when the allocation WOULD
    fit: headroom stays free for the lanes already running."""
    cfg, _scope, params = tiny_gpt
    # 8 usable blocks; each request reserves 3; watermark 3 blocks
    srv = _server(params, cfg, num_blocks=9, max_context=32,
                  watermark_blocks=3, num_slots=3)
    f1 = srv.submit([5, 6, 7, 8], max_new_tokens=20)
    f2 = srv.submit([9, 10, 11, 12], max_new_tokens=20)
    srv.step()
    st = srv.get_stats()
    # 5 blocks free >= 3 needed, but 5 - 3 < watermark: f2 must wait
    assert st["active_slots"] == 1 and st["queue_depth"] == 1
    assert st["blocks_free"] == 5
    srv.run_until_idle()
    assert len(f1.result(5).token_ids) == 20
    assert len(f2.result(5).token_ids) == 20


def test_oversized_request_rejected_at_submit(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg, num_blocks=5, max_context=32)
    with pytest.raises(ValueError, match="max_context"):
        srv.submit([1] * 30, max_new_tokens=10)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit([1, 2], max_new_tokens=0)


def test_deadline_cancel_reclaims_blocks_chaos_clock(tiny_gpt):
    """Deadline expiry is an exact iteration count under the chaos
    clock — no sleeps. The slot and blocks come back to the pool and
    the waiting request then runs to completion."""
    cfg, _scope, params = tiny_gpt
    chaos = ChaosInjector()
    for it in range(1, 40):
        chaos.advance_clock_at(it, ms=100)     # 10 iterations/second
    srv = _server(params, cfg, num_blocks=4, max_context=32,
                  chaos=chaos)
    slow = srv.submit([5, 6, 7], max_new_tokens=20, deadline_ms=450)
    queued = srv.submit([9, 10], max_new_tokens=3)
    srv.run_until_idle()
    with pytest.raises(DeadlineExceeded):
        slow.result(timeout=5)
    assert len(queued.result(timeout=5).token_ids) == 3
    st = srv.get_stats()
    assert st["deadline_cancels"] == 1
    assert st["blocks_free"] == st["blocks_total"]
    assert chaos.fired["clock_advance"] > 0


def test_chaos_mid_stream_cancel(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    chaos = ChaosInjector().cancel_request_at(3, index=0)
    srv = _server(params, cfg, chaos=chaos)
    victim = srv.submit([5, 6], max_new_tokens=30)
    bystander = srv.submit([7, 8], max_new_tokens=5)
    srv.run_until_idle()
    with pytest.raises(serving.RequestCancelled):
        victim.result(timeout=5)
    assert len(bystander.result(timeout=5).token_ids) == 5
    assert chaos.fired["cancel"] == 1
    assert srv.get_stats()["cancelled"] == 1


def test_streaming_callbacks_match_result(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg)
    seen = []
    fut = srv.submit([5, 9, 11], max_new_tokens=6,
                     stream=lambda rid, tok: seen.append((rid, tok)))
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert [t for _rid, t in seen] == list(res.token_ids)
    assert all(rid == res.request_id for rid, _t in seen)


def test_chunked_prefill_counts_prompt_tokens(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg, chunk=4)
    fut = srv.submit(np.arange(2, 13, dtype=np.int32),  # 11 prompt tokens
                     max_new_tokens=2)
    srv.run_until_idle()
    fut.result(timeout=5)
    st = srv.get_stats()
    assert st["prefill_tokens"] == 11
    assert st["generated_tokens"] == 2
    # 11 tokens at chunk 4 -> 3 prefill iterations + 1 decode iteration
    assert st["iteration"] >= 4


def test_idle_steps_do_not_count_iterations(tiny_gpt):
    """An idle plan() (nothing queued/active/cancelling) is not an
    iteration: the threaded worker's poll loop must not inflate the
    counter that chaos plans key off."""
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg)
    assert srv.step() is False
    assert srv.get_stats()["iteration"] == 0
    srv.submit([5, 6], max_new_tokens=2)
    srv.run_until_idle()
    n = srv.get_stats()["iteration"]
    assert n >= 2
    assert srv.step() is False
    assert srv.get_stats()["iteration"] == n


def test_threaded_server_drains_on_close(tiny_gpt):
    """The submit/Future surface under the real worker thread: futures
    resolve without manual pumping and close() finishes in-flight work
    before returning."""
    cfg, _scope, params = tiny_gpt
    srv = _server(params, cfg, start=True)
    futs = [srv.submit([5 + i, 9], max_new_tokens=3 + i)
            for i in range(5)]
    outs = [f.result(timeout=120) for f in futs]
    for i, res in enumerate(outs):
        assert len(res.token_ids) == 3 + i
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit([1, 2], max_new_tokens=2)
    assert srv.get_stats()["blocks_free"] == srv.get_stats()["blocks_total"]


def test_serving_metrics_land_in_global_registry(tiny_gpt):
    from paddle_tpu.observability.metrics import global_registry
    cfg, _scope, params = tiny_gpt
    reg = global_registry()
    base = reg.counter("serving.generated_tokens").value()
    srv = _server(params, cfg)
    srv.submit([5, 6], max_new_tokens=4)
    srv.run_until_idle()
    assert reg.counter("serving.generated_tokens").value() == base + 4
    assert reg.histogram("serving.ttft_ms").summary()["count"] >= 1


def test_iteration_trace_spans_recorded(tiny_gpt):
    from paddle_tpu.observability.tracing import get_recorder
    cfg, _scope, params = tiny_gpt
    rec = get_recorder()
    rec.start()
    try:
        srv = _server(params, cfg)
        srv.submit([5, 6], max_new_tokens=3)
        srv.run_until_idle()
    finally:
        rec.stop()
    spans = [e for e in rec.events()
             if e.get("name") == "serving.iteration"]
    rec.clear()
    assert len(spans) >= 3          # prefill + decode iterations
    assert all(e["cat"] == "serving" for e in spans)
    assert spans[0]["args"]["lanes"] >= 1


def test_predictor_enable_generation_entry_point(tiny_gpt, tmp_path):
    """AnalysisConfig.enable_generation -> Predictor.generation_server
    from a SAVED model dir reproduces the direct-scope server's ids."""
    from paddle_tpu import inference
    cfg, scope, params = tiny_gpt
    # re-build a fresh program around the initialized scope for export
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        tokens, _loss, logits = gpt.build_lm_net(cfg, seq_len=8)
    with scope_guard(scope):
        exe = fluid.Executor()
        fluid.io.save_inference_model(str(tmp_path / "gpt"), ["tokens"],
                                      [logits], exe, main_program=main)
    acfg = inference.AnalysisConfig(str(tmp_path / "gpt"))
    acfg.enable_generation(cfg, num_slots=2, block_size=8,
                           max_context=64, chunk=4)
    pred = inference.create_predictor(acfg)
    srv = pred.generation_server(start=False)
    prompt = np.array([5, 9, 11], np.int32)
    fut = srv.submit(prompt, max_new_tokens=6)
    srv.run_until_idle()
    assert list(fut.result(timeout=5).token_ids) == \
        _reference_greedy(params, cfg, prompt, 6)
    assert srv.get_stats()["fused_step_signatures"] == 1


def test_generation_not_enabled_raises(tmp_path, tiny_gpt):
    from paddle_tpu import inference
    cfg, scope, _params = tiny_gpt
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _tokens, _loss, logits = gpt.build_lm_net(cfg, seq_len=8)
    with scope_guard(scope):
        exe = fluid.Executor()
        fluid.io.save_inference_model(str(tmp_path / "g2"), ["tokens"],
                                      [logits], exe, main_program=main)
    pred = inference.create_predictor(str(tmp_path / "g2"))
    with pytest.raises(RuntimeError, match="enable_generation"):
        pred.generation_server()


# ---------------------------------------------------------------------------
# host phases of step() as leaf spans (docs/serving.md)
# ---------------------------------------------------------------------------

LEAVES = ("serving.plan", "serving.feed", "serving.dispatch",
          "serving.fetch", "serving.commit", "serving.account")


def _traced_run(params, cfg, prompts, **kw):
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.observability.tracing import get_recorder
    reg = global_registry()
    names = ("serving.valid_columns", "serving.padded_columns",
             "serving.iterations")
    before = {n: reg.counter(n).value() for n in names}
    wait0 = reg.histogram("serving.queue_wait_ms").summary()
    rec = get_recorder()
    rec.start()
    try:
        srv = _server(params, cfg, **kw)
        futs = [srv.submit(p, max_new_tokens=n) for p, n in prompts]
        srv.run_until_idle()
        for f in futs:
            f.result(timeout=30)
    finally:
        rec.stop()
    events = rec.events()
    rec.clear()
    delta = {n: reg.counter(n).value() - before[n] for n in names}
    wait1 = reg.histogram("serving.queue_wait_ms").summary()
    delta["queue_wait_ms"] = wait1["sum"] - wait0["sum"]
    delta["admissions"] = wait1["count"] - wait0["count"]
    return srv, events, delta


def test_leaf_spans_tile_every_iteration(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    prompts = [([5, 6, 7, 8, 9, 10], 4), ([3, 4], 6), ([1], 2),
               ([2, 2, 2], 3)]         # 4 requests on 3 slots: one queues
    srv, events, delta = _traced_run(params, cfg, prompts)
    s, c = srv._sched.num_slots, srv._sched.chunk
    iters = {e["args"]["iteration"]: e for e in events
             if e["name"] == "serving.iteration"}
    assert len(iters) == delta["serving.iterations"] >= 6
    by_iter = {}
    for e in events:
        if e["name"] in LEAVES and not e["args"].get("idle"):
            by_iter.setdefault(e["args"]["iteration"], []).append(e)
    assert set(by_iter) == set(iters)
    valid = padded = 0
    for it, leaves in by_iter.items():
        leaves.sort(key=lambda e: e["ts"])
        # one of each, in order, on one thread, none overlapping
        assert tuple(e["name"] for e in leaves) == LEAVES, (it, leaves)
        assert len({e["tid"] for e in leaves}) == 1
        for a, b in zip(leaves, leaves[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 0.002, (a, b)
        # serving.iteration: from the start of feed to the end of fetch
        # (the span engine.iter_ms_p50 reads; its extent must not move)
        whole, feed, fetch = iters[it], leaves[1], leaves[3]
        assert whole["tid"] == feed["tid"]
        assert 0 <= feed["ts"] - whole["ts"] < 200
        assert 0 <= (whole["ts"] + whole["dur"]) \
            - (fetch["ts"] + fetch["dur"]) + 0.002 < 200
        # the iteration record: the (S, C) grid and who filled it
        args = whole["args"]
        assert args["valid_columns"] + args["padded_columns"] == s * c
        assert args["lanes"] == len(args["lanes_qc"])
        assert sum(q for q, _ctx in args["lanes_qc"]) \
            == args["valid_columns"]
        assert all(1 <= q <= c and ctx >= q for q, ctx in args["lanes_qc"])
        # a 64-token table is one group of the paged walk: all of it live
        assert args["walk_groups_live"] == args["walk_groups"] \
            == args["lanes"]
        valid += args["valid_columns"]
        padded += args["padded_columns"]
    assert delta["serving.valid_columns"] == valid
    assert delta["serving.padded_columns"] == padded
    assert valid + padded == len(iters) * s * c
    # every fed token is a column: prompts, and each generated token
    # but a request's last, which is never fed back
    assert valid == sum(len(p) + n - 1 for p, n in prompts)
    # the idle poll that ends run_until_idle plans and does nothing else
    idle = [e for e in events if e["args"].get("idle")]
    assert [e["name"] for e in idle] == ["serving.plan"]


def test_iteration_record_counts_the_walks_live_groups(monkeypatch):
    """A table of three groups of 128 key positions (320 tokens of
    16-token blocks), a lane of 5 tokens beside one past 128: the
    record says how many groups the paged walk has (`walk_groups`) and
    how many hold a token (`walk_groups_live`), and the stream over the
    grouped walk is the reference path's."""
    from paddle_tpu.ops.pallas import paged
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, inner_size=64, max_position=320,
                        dropout=0.0)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 5
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor().run(startup)
    params = gpt.load_params(scope, cfg)
    rng = np.random.default_rng(0)
    prompts = [(list(rng.integers(1, 64, 126)), 6), ([3, 4, 5], 14)]
    kw = dict(num_slots=2, block_size=16, max_context=320, chunk=16)
    assert paged.walk_group(16, 320 // 16) == 8

    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    srv, events, _delta = _traced_run(params, cfg, prompts, **kw)
    assert srv.get_stats()["kernel"]["engaged"] is True
    records = [e["args"] for e in events
               if e["name"] == "serving.iteration"]
    for args in records:
        assert args["walk_groups"] == 3 * args["lanes"]
        assert args["walk_groups_live"] == sum(
            -(-ctx // 128) for _q, ctx in args["lanes_qc"])
        assert args["lanes"] <= args["walk_groups_live"] \
            <= args["walk_groups"]
    # the long lane crosses into its second group while it decodes
    assert {a["walk_groups_live"] for a in records} >= {1, 2, 3}

    def ids(server_kw):
        srv = _server(params, cfg, **kw, **server_kw)
        futs = [srv.submit(p, max_new_tokens=n) for p, n in prompts]
        srv.run_until_idle()
        return [list(f.result(timeout=30).token_ids) for f in futs]

    kernel_ids = ids({})
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    assert kernel_ids == ids({})


def test_draft_step_is_a_child_of_feed(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    spec = SpecDecodeConfig(GPTServingModel(params, cfg), k=2)
    _srv, events, _delta = _traced_run(params, cfg, [([5, 6, 7], 5)],
                                       spec=spec)
    drafts = [e for e in events if e["name"] == "serving.draft"]
    feeds = {e["args"]["iteration"]: e for e in events
             if e["name"] == "serving.feed"}
    assert drafts and len(drafts) == len(feeds)
    for d in drafts:
        f = feeds[d["args"]["iteration"]]
        assert f["ts"] <= d["ts"] and \
            d["ts"] + d["dur"] <= f["ts"] + f["dur"] + 0.002


def test_admit_instant_shares_ids_with_the_request_tree(tiny_gpt):
    cfg, _scope, params = tiny_gpt
    prompts = [([5, 6, 7, 8], 3), ([3, 4], 2), ([1], 2), ([9, 9], 2)]
    _srv, events, delta = _traced_run(params, cfg, prompts)
    admits = [e for e in events if e["name"] == "serving.admit"]
    roots = {e["args"]["rid"]: e for e in events
             if e["name"].startswith("request ")}
    assert len(admits) == len(prompts) == delta["admissions"]
    assert {e["args"]["rid"] for e in admits} == set(roots)
    for e in admits:
        root = roots[e["args"]["rid"]]
        assert e["ph"] == "i" and e["cat"] == "serving.request"
        assert e["tid"] == root["tid"] == f"serving slot {e['args']['slot']}"
        assert e["args"]["slot"] == root["args"]["slot"]
        assert e["args"]["iteration"] == root["args"]["admit_iteration"]
        assert e["args"]["blocks"] >= 1
        # admission closes the tree's queue span
        queue = next(q for q in events if q["name"] == "queue"
                     and q["args"]["rid"] == e["args"]["rid"])
        assert abs(queue["ts"] + queue["dur"] - e["ts"]) < 1.0
    # what the histogram observed is what the instants carry
    assert sum(e["args"]["queue_wait_ms"] for e in admits) \
        == pytest.approx(delta["queue_wait_ms"], abs=1e-3)


# ---------------------------------------------------------------------------
# every pool rewriter in one server (ISSUE 26): each donates the pools,
# so a reference kept across any of them would read a deleted array
# ---------------------------------------------------------------------------

def test_every_pool_rewriter_in_one_server_keeps_reference_ids(tiny_gpt):
    """Prefix cache, host tier, a fork group and a speculative draft in
    one server, pumped through admission, COW, spill, swap-in and
    retirement: the ids are the dense per-token loop's, and every
    iteration's fused step consumed the pools it was handed."""
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.serving import SamplingParams
    cfg, _scope, params = tiny_gpt
    rng = np.random.default_rng(26)
    a, b, c = (rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (17, 16, 11))
    want = {k: _reference_greedy(params, cfg, p, 6)
            for k, p in (("a", a), ("b", b), ("c", c))}
    reg = global_registry()
    before = [reg.counter(n).value() for n in
              ("serving.kv.pool_donations", "serving.iterations")]

    chaos = ChaosInjector()
    srv = _server(params, cfg, num_slots=4, num_blocks=48,
                  prefix_cache=True, host_kv_blocks=8, chaos=chaos,
                  spec=SpecDecodeConfig(GPTServingModel(params, cfg), k=3))

    def run(*prompts):
        futs = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run_until_idle()
        return [list(f.result(timeout=5).token_ids) for f in futs]

    # admission, chunked prefill, retirement: a's and b's chunks are
    # indexed as they go
    assert run(a, b) == [want["a"], want["b"]]
    # a's chain goes to the host tier; the hit below swaps it back in
    chaos.spill_chain_at(srv._sched.iteration + 1, 2)
    # b is covered whole by its two cached blocks, so its last token is
    # fed again into a shared block: copy-on-write. The group forks
    # three lanes off one prefill of c
    group = srv.submit(c, max_new_tokens=6,
                       sampling=SamplingParams(n=3, temperature=0.0))
    assert run(a, b) == [want["a"], want["b"]]
    lanes = group.result(timeout=5).lanes
    assert [list(l.token_ids) for l in lanes] == [want["c"]] * 3

    st = srv.get_stats()
    assert chaos.fired["spill"] == 2
    assert st["kv_tier"]["spills"] >= 2 and st["kv_tier"]["swap_ins"] >= 2
    assert st["prefix"]["hits"] >= 4 and st["prefix"]["cow_copies"] >= 1
    assert st["group.forks"] == 2
    assert st["spec"]["proposed"] > 0
    assert st["fused_step_signatures"] == 1
    assert srv.cache.num_free == \
        srv.cache.usable_blocks - st["prefix"]["entries"]
    donated, iterations = (reg.counter(n).value() - v for n, v in zip(
        ("serving.kv.pool_donations", "serving.iterations"), before))
    assert donated == iterations == st["iteration"]
    srv.close()
