"""Compile observatory (ISSUE 8): XLA cost/memory attribution with
static fallback, Executor.explain(), the HBM ledger (+ /memory
endpoint), and recompile-storm detection."""

import io
import json
import os
import sys
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability import compile_insight as ci
from paddle_tpu.observability.compile_insight import (
    HBMLedger, RecompileStormWarning, RecompileTracker, hbm_ledger)
from paddle_tpu.observability.metrics import MetricsRegistry, global_registry

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(_REPO, "tools"))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _mlp_programs(hidden=16):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=hidden, act="relu")
        loss = layers.mean(layers.square_error_cost(
            layers.fc(h, size=1), y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _mlp_feed(b):
    return {"x": np.ones((b, 8), np.float32),
            "y": np.ones((b, 1), np.float32)}


def _storm_exe(shapes=(8, 16, 12, 20, 24)):
    """Fresh MLP executor driven through `shapes`; returns
    (exe, scope, main, loss, caught_storm_warnings)."""
    main, startup, loss = _mlp_programs()
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for b in shapes:
                exe.run(main, feed=_mlp_feed(b), fetch_list=[loss])
    storms = [w for w in caught
              if issubclass(w.category, RecompileStormWarning)]
    return exe, scope, main, loss, storms


@pytest.fixture(scope="module")
def gpt_train():
    """Tiny-tiny GPT train program (Adam: optimizer moments exist),
    startup run — the explain() acceptance target."""
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=2, inner_size=128, max_position=64,
                        dropout=0.0)
    seq = 16
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        _tokens, loss, _logits = gpt.build_lm_net(cfg, seq_len=seq)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
    rng = np.random.default_rng(0)

    def feed(b=4):
        return {"tokens": rng.integers(0, cfg.vocab_size, (b, seq),
                                       dtype=np.int64)}

    yield cfg, main, loss, exe, scope, feed
    exe.close()


# ---------------------------------------------------------------------------
# static analyzer
# ---------------------------------------------------------------------------

def test_analyze_jaxpr_counts_dot_flops_exactly():
    import jax

    def f(a, b):
        return (a @ b).sum()

    rep = ci.analyze_jaxpr(jax.make_jaxpr(f)(
        jnp.ones((8, 16)), jnp.ones((16, 4))))
    # dot: 2*M*N*K = 2*8*4*16 = 1024; reduce_sum over 32 elems
    assert rep["per_primitive"]["dot_general"] == 1024
    assert rep["flops"] == 1024 + 32
    assert rep["out_bytes"] == 4          # f32 scalar


def test_analyze_jaxpr_scan_multiplies_flops_not_bytes():
    import jax

    def f(x):
        def body(c, _):
            return c * 2.0, ()
        c, _ = jax.lax.scan(body, x, None, length=5)
        return c

    rep = ci.analyze_jaxpr(jax.make_jaxpr(f)(jnp.ones((4, 4))))
    # mul runs 5x (flops), but only one iteration is live at a time
    # (intermediate bytes counted once)
    assert rep["per_primitive"]["mul"] == 5 * 16
    assert rep["intermediate_bytes"] <= 2 * 16 * 4


def test_analyze_jaxpr_layout_ops_are_free():
    import jax

    def f(a):
        return jnp.transpose(a).reshape(-1)[:8]

    rep = ci.analyze_jaxpr(jax.make_jaxpr(f)(jnp.ones((4, 8))))
    assert rep["flops"] == 0


def test_analyze_program_attribution(gpt_train):
    cfg, main, _loss, _exe, scope, feed = gpt_train
    # int32: what the executor's int64 policy feeds the device
    feeds = {k: np.asarray(v, np.int32) for k, v in feed(4).items()}
    state = {n: scope.get(n) for n in scope.names()
             if scope.get(n) is not None}
    rep = ci.analyze_program(main, feeds=feeds, state=state)
    assert rep["train"] and rep["batch_size"] == 4
    assert rep["flops"] == 3 * rep["fwd_flops"] > 0
    assert rep["per_op_type"]           # mul/matmul attribution exists
    # Adam: two moment tensors per param -> optimizer ~2x param bytes
    assert rep["param_bytes"] > 0
    assert rep["optimizer_bytes"] > 1.5 * rep["param_bytes"]
    assert rep["feed_bytes"] == 4 * 16 * 4      # int64 canonzd to int32
    assert rep["activation_bytes"] > 0


# ---------------------------------------------------------------------------
# Executor.explain — the acceptance surface
# ---------------------------------------------------------------------------

def test_explain_gpt_static_fallback(gpt_train):
    """Acceptance: explain() returns flops/bytes/peak-HBM for a GPT
    Program on the CPU backend via the static fallback path."""
    _cfg, main, loss, exe, scope, feed = gpt_train
    with scope_guard(scope):
        rep = exe.explain(main, feed=feed(), fetch_list=[loss],
                          backend=False)
    assert rep["source"] == {"flops": "static", "bytes": "static",
                             "peak_hbm": "static"}
    assert rep["flops"] > 0
    assert rep["bytes_accessed"] > 0
    assert rep["peak_hbm_bytes"] > 0
    assert rep["xla"] == {"cost": None, "memory": None}
    # the memory section unifies param + optimizer bytes
    assert rep["memory"]["param_bytes"] > 0
    assert rep["memory"]["optimizer_bytes"] > rep["memory"]["param_bytes"]
    # peak must at least hold the resident state it closes over
    assert rep["peak_hbm_bytes"] >= (rep["memory"]["param_bytes"]
                                     + rep["memory"]["optimizer_bytes"])
    assert rep["static"]["jaxpr"]["per_primitive"].get(
        "dot_general", 0) > 0


def test_explain_backend_auto_and_crosscheck(gpt_train):
    _cfg, main, loss, exe, scope, feed = gpt_train
    with scope_guard(scope):
        rep = exe.explain(main, feed=feed(), fetch_list=[loss])
    assert rep["flops"] > 0 and rep["peak_hbm_bytes"] > 0
    # the static column always rides along as the cross-check; when the
    # backend reported (this CPU container does), the two flops counts
    # describe the same executable and must agree within tool error
    static = rep["static"]["jaxpr"]["flops"]
    assert static > 0
    if rep["source"]["flops"] == "xla":
        assert 0.2 < rep["flops"] / static < 5.0
    # explain() is read-free: no step ran
    assert rep["fetches"] == [loss.name]


def test_explain_registers_peak_in_ledger_and_reports_history(gpt_train):
    _cfg, main, loss, exe, scope, feed = gpt_train
    # batch 6: a shape no earlier explain() pre-warmed, so this run()
    # really compiles and creates the per-(program, shapes) history
    with scope_guard(scope):
        exe.run(main, feed=feed(6), fetch_list=[loss])
        steps_before = exe.get_stats()["steps"]
        rep = exe.explain(main, feed=feed(6), fetch_list=[loss],
                          backend=False)
        assert exe.get_stats()["steps"] == steps_before
    assert rep["compile_ms"] and rep["compile_ms"]["count"] >= 1
    own = hbm_ledger().component_bytes(exe._exe_id)
    assert own.get("peak_hbm") == rep["peak_hbm_bytes"]
    assert own.get("params", 0) > 0         # miss-path registration
    assert own.get("optimizer", 0) > own["params"]


# ---------------------------------------------------------------------------
# recompile-storm detection
# ---------------------------------------------------------------------------

def test_recompile_storm_warns_and_names_offending_var():
    """Acceptance: 3 distinct unbucketed shapes past the warm threshold
    raise a storm warning whose key diff names the offending feed."""
    exe, _scope, _main, _loss, storms = _storm_exe()
    assert len(storms) == 1
    msg = str(storms[0].message)
    assert "x: 20x8:float32 -> 24x8:float32" in msg
    assert "FeedBucketer" in msg
    st = exe.get_stats()["recompile"]
    assert st["events"] == 3 and st["storms"] == 1
    assert st["window_events"] == 3
    ev = st["last_events"][-1]
    assert {c["var"] for c in ev["changed"]} == {"x", "y"}
    assert ev["changed"][0]["kind"] == "shape"
    # process-wide metrics recorded (zz coverage lint rides on these)
    assert global_registry().get("executor.recompile.events").value() >= 3
    assert global_registry().get("executor.recompile.storms").value() >= 1
    exe.close()


def test_storm_warns_once_per_burst():
    exe, scope, main, loss, storms = _storm_exe()
    assert len(storms) == 1
    with scope_guard(scope):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for b in (28, 36):      # still inside the latched burst
                exe.run(main, feed=_mlp_feed(b), fetch_list=[loss])
    again = [w for w in caught
             if issubclass(w.category, RecompileStormWarning)]
    assert not again
    assert exe.get_stats()["recompile"]["storms"] == 1
    assert exe.get_stats()["recompile"]["events"] == 5
    exe.close()


def test_recompile_cause_rides_compile_span_trace_args():
    """Satellite: Perfetto shows WHY a warm program recompiled — the
    key diff lands in the compile span's args."""
    from paddle_tpu.observability.tracing import get_recorder
    rec = get_recorder()
    rec.start()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RecompileStormWarning)
            _storm_exe(shapes=(8, 16, 12))[0].close()
    finally:
        rec.stop()
    compiles = [e for e in rec.events()
                if e["name"] == "executor.compile"]
    diffs = [e["args"] for e in compiles if "key_diff" in e["args"]]
    assert diffs, "no compile span carried a key diff"
    assert any("x: " in a["key_diff"] and "nearest_signature" in a
               for a in diffs)
    # warm compiles carry no diff (first two of this program + startup)
    assert len(diffs) < len(compiles)
    rec.clear()


def test_recompile_detector_env_disable(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RECOMPILE_DETECT", "0")
    exe, _scope, _main, _loss, storms = _storm_exe()
    assert not storms
    st = exe.get_stats()["recompile"]
    assert st["enabled"] is False and st["events"] == 0
    exe.close()


def test_diff_prefers_nearest_signature():
    tracker = RecompileTracker(stats=None, warm=1, storm=99)
    f32 = np.dtype(np.float32)
    tracker.observe_miss(1, "p", (("a", (8, 4), f32), ("b", (8, 1), f32)),
                         ("loss",), ("w",), 0)
    tracker.observe_miss(1, "p", (("a", (64, 4), f32), ("b", (64, 1), f32)),
                         ("loss",), ("w",), 1)
    # (8,4)/(64,1): one var matches the first sig, one the second —
    # nearest (1 change) beats the 2-change candidates
    ev = tracker.observe_miss(
        1, "p", (("a", (8, 4), f32), ("b", (64, 1), f32)),
        ("loss",), ("w",), 2)
    assert len(ev["changed"]) == 1
    # identical feeds with a different fetch list: named as such
    ev2 = tracker.observe_miss(
        1, "p", (("a", (8, 4), f32), ("b", (64, 1), f32)),
        ("loss", "acc"), ("w",), 3)
    assert ev2["summary"] == "fetch_list changed"


def test_clear_caches_retires_compile_series_ledger_and_history():
    """Satellite bugfix: freed jit entries must not keep reporting —
    per-entry compile_ms series, ledger rows and the recompile history
    all retire on clear_caches()."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RecompileStormWarning)
        exe, scope, main, loss, _ = _storm_exe(shapes=(8, 16))
    st = exe.get_stats()
    assert len(st["compile_ms"]) >= 2
    assert st["memory"]["own"].get("params", 0) > 0
    exe.clear_caches()
    st = exe.get_stats()
    assert st["compile_ms"] == []
    assert st["memory"]["own"] == {}
    assert st["recompile"]["events"] == 0
    # after the wipe the same shape is a COLD compile again, not a
    # post-warm recompile event
    with scope_guard(scope):
        exe.run(main, feed=_mlp_feed(8), fetch_list=[loss])
    assert exe.get_stats()["recompile"]["events"] == 0
    exe.close()


def test_diff_names_extra_key_component_change():
    """A miss whose feeds never moved must name the cache-key part that
    did (program version, mesh) — not claim the state set changed."""
    tracker = RecompileTracker(stats=None, warm=1, storm=99)
    f32 = np.dtype(np.float32)
    sig = (("a", (8, 4), f32),)
    tracker.observe_miss(1, "p", sig, ("loss",), ("w",), 0,
                         extra_sig=(("program version", 3),
                                    ("mesh", None)))
    ev = tracker.observe_miss(1, "p", sig, ("loss",), ("w",), 1,
                              extra_sig=(("program version", 4),
                                         ("mesh", None)))
    assert ev["summary"] == "program version changed (3 -> 4)"


def test_snapshot_events_cumulative_past_ring_bound():
    """snapshot()['events'] tracks the cumulative count, not the
    truncated postmortem ring length."""
    tracker = RecompileTracker(stats=None, warm=1, storm=999,
                               window_s=0.0)
    tracker.MAX_EVENTS = 2
    f32 = np.dtype(np.float32)
    for i in range(5):
        tracker.observe_miss(1, "p", (("a", (8 + i, 4), f32),),
                             ("loss",), ("w",), i)
    assert tracker.snapshot()["events"] == 4    # first miss = warm-up
    assert len(tracker.events()) == 2           # ring stays bounded


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def test_ledger_merges_programs_sharing_scope():
    """A train program and its clone(for_test=True) eval program run
    over the SAME scope arrays — the ledger must account each var name
    once, not once per program."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        loss = layers.mean(layers.square_error_cost(
            layers.fc(h, size=1), y))
        test_prog = main.clone(for_test=True)
        fluid.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_mlp_feed(8), fetch_list=[loss])
        own_train = hbm_ledger().component_bytes(exe._exe_id)
        exe.run(test_prog, feed=_mlp_feed(8), fetch_list=[loss])
    own_both = hbm_ledger().component_bytes(exe._exe_id)
    assert own_train["params"] > 0
    assert own_both["params"] == own_train["params"]
    assert own_both["optimizer"] == own_train["optimizer"]
    exe.close()

def test_ledger_register_retire_and_totals():
    reg = MetricsRegistry()
    led = HBMLedger(registry=reg)
    led.register("c1", "params", "params", 1000)
    led.register("c1", "peak", "peak_hbm", 9000)
    led.register("c2", "pool", "kv_cache", 500)
    snap = led.snapshot()
    # peak_hbm estimates never sum into the resident total
    assert snap["total_bytes"] == 1500
    assert snap["by_kind"] == {"params": 1000, "peak_hbm": 9000,
                               "kv_cache": 500}
    assert reg.get("memory.total_bytes").value() == 1500
    assert reg.get("memory.entries").value() == 3
    led.register("c1", "params", "params", 2000)    # upsert, no dup row
    assert led.snapshot()["total_bytes"] == 2500
    led.retire("c1")
    snap = led.snapshot()
    assert snap["by_component"] == {"c2": {"kv_cache": 500}}
    series = {tuple(sorted(lbl.items()))
              for lbl, _c in reg.get("memory.bytes").series()}
    assert series == {(("component", "c2"), ("kind", "kv_cache"))}
    with pytest.raises(ValueError):
        led.register("c1", "x", "not_a_kind", 1)


@pytest.fixture(scope="module")
def serving_params():
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, inner_size=64, max_position=64,
                        dropout=0.0)
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    params = gpt.load_params(scope, cfg)
    exe.close()
    return cfg, params


@pytest.mark.serving
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ledger_kv_pool_bytes_and_retire_on_close(serving_params, dtype):
    """Satellite: pool-bytes accounting — the ledger's kv_cache row
    equals num_blocks*H*block_size*D*itemsize summed over layers for
    BOTH k and v pools, f32 and bf16; gauges retire on close."""
    from paddle_tpu.serving.engine import GenerationServer, GPTServingModel
    cfg, params = serving_params
    srv = GenerationServer(GPTServingModel(params, cfg, dtype=dtype),
                           num_slots=2, block_size=8, max_context=32,
                           chunk=2, start=False, telemetry=False)
    itemsize = np.dtype(dtype).itemsize
    per_pool = (srv.cache.num_blocks * cfg.num_heads * 8
                * (cfg.hidden_size // cfg.num_heads) * itemsize)
    expected = cfg.num_layers * 2 * per_pool        # k AND v pools
    # ... which lie side by side in one array a layer
    assert all(set(p) == {"kv"} for p in srv.cache.pools)
    assert sum(p["kv"].size * p["kv"].dtype.itemsize
               for p in srv.cache.pools) == expected
    mem = srv.get_stats()["memory"]
    assert mem["kv_cache"] == expected
    assert mem["params"] > 0
    assert mem["peak_hbm"] >= mem["kv_cache"] + mem["params"]
    comp = srv._ledger_id
    series = [lbl for lbl, _c in
              global_registry().get("memory.bytes").series()
              if lbl.get("component") == comp]
    assert {l["kind"] for l in series} == {"kv_cache", "params",
                                           "peak_hbm"}
    srv.close()
    assert srv.get_stats()["memory"] == {}
    series = [lbl for lbl, _c in
              global_registry().get("memory.bytes").series()
              if lbl.get("component") == comp]
    assert series == []


@pytest.mark.serving
def test_ledger_retires_on_fault_stopped_close(serving_params):
    """PR 7's fault-stop path: _on_engine_fault closes without the
    normal teardown; the close()-after-fault early-return branch must
    still retire the ledger rows."""
    from paddle_tpu.serving.engine import GenerationServer, GPTServingModel
    cfg, params = serving_params
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=2,
                           block_size=8, max_context=32, chunk=2,
                           start=False, telemetry=False)
    assert hbm_ledger().component_bytes(srv._ledger_id)
    # what _on_engine_fault leaves behind: fault recorded, _closed set,
    # teardown never reached
    srv._fault = RuntimeError("poisoned pool")
    with srv._rid_lock:
        srv._closed = True
    srv.close()
    assert hbm_ledger().component_bytes(srv._ledger_id) == {}


def test_memory_endpoint_serves_ledger_snapshot():
    from paddle_tpu.observability.exporter import serve_metrics
    led = hbm_ledger()
    led.register("memtest", "unit", "other", 4321,
                 detail={"who": "test_memory_endpoint"})
    srv = serve_metrics(port=0)
    try:
        with urllib.request.urlopen(
                f"{srv.url}/memory", timeout=5) as resp:
            assert resp.status == 200
            body = json.loads(resp.read().decode())
        assert body["by_component"]["memtest"] == {"other": 4321}
        assert any(e["detail"].get("who") == "test_memory_endpoint"
                   for e in body["entries"])
        assert body["total_bytes"] >= 4321
        # 404 surface now advertises /memory
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
        assert "/memory" in ei.value.read().decode()
    finally:
        srv.close()
        led.retire("memtest")


# ---------------------------------------------------------------------------
# tool surfaces
# ---------------------------------------------------------------------------

def test_compile_report_renders_saved_report(tmp_path):
    import compile_report
    payload = {
        "explain": {"program": "program_1_v1", "flops": 7.05e8,
                    "bytes_accessed": 1.3e8, "peak_hbm_bytes": 2.8e7,
                    "source": {"flops": "static"},
                    "compile_ms": {"count": 1, "avg": 700.0},
                    "recompiles": [{"summary": "tokens: 10 -> 12"}]},
        "memory_ledger": {"total_bytes": 1000, "entries": [],
                          "by_component": {"exe0": {"params": 1000}}},
    }
    p = tmp_path / "sample.json"
    p.write_text(json.dumps(payload))
    out = io.StringIO()
    assert compile_report.run_from(str(p), file=out) == 0
    text = out.getvalue()
    assert "program_1_v1" in text and "tokens: 10 -> 12" in text
    assert "exe0: params=1.00KB" in text


