"""Observability layer: metrics registry, executor instrumentation,
Chrome-trace schema, metric-name lint, and the trace_report CLI."""

import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability.metrics import (
    METRIC_SPECS, MetricsRegistry, global_registry)
from paddle_tpu.observability.tracing import TraceRecorder, get_recorder

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(_REPO, "tools"))


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("test.hits", "help text")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("test.size")
    g.set(7)
    g.dec(2)
    assert g.value() == 5
    # same name returns the SAME metric; conflicting kind raises
    assert reg.counter("test.hits") is c
    with pytest.raises(ValueError):
        reg.gauge("test.hits")


def test_histogram_buckets_summary_and_timer():
    reg = MetricsRegistry()
    h = reg.histogram("test.lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 500.0
    assert s["sum"] == pytest.approx(555.5)
    snap = h.snapshot()["values"][0]
    # cumulative bucket counts, +Inf terminated
    assert snap["buckets"] == [[1.0, 1], [10.0, 2], [100.0, 3], ["+Inf", 4]]
    with h.time_ms():
        pass
    assert h.summary()["count"] == 5


def test_histogram_labels_are_independent_series():
    reg = MetricsRegistry()
    h = reg.histogram("test.compile_ms")
    h.labels(program="a").observe(10.0)
    h.labels(program="b").observe(20.0)
    by_label = {lbl.get("program"): s for lbl, s in h.summaries()}
    assert by_label["a"]["count"] == 1 and by_label["b"]["sum"] == 20.0


def test_registry_json_and_prometheus_export():
    reg = MetricsRegistry()
    reg.counter("test.hits", "hit count").inc(3)
    reg.histogram("test.ms", buckets=(1.0,)).observe(0.5)
    reg.gauge("test.size").labels(executor="exe0").set(2)
    dump = json.loads(reg.to_json())
    by_name = {m["name"]: m for m in dump["metrics"]}
    assert by_name["test.hits"]["values"][0]["value"] == 3
    assert by_name["test.size"]["values"][0]["labels"] == {"executor": "exe0"}
    prom = reg.to_prometheus()
    assert "# TYPE test_hits counter" in prom
    assert "test_hits 3" in prom
    assert 'test_size{executor="exe0"} 2' in prom
    assert 'test_ms_bucket{le="+Inf"} 1' in prom
    assert "test_ms_count 1" in prom


def test_prometheus_label_value_escaping():
    """Exposition-format escaping: a label value holding backslash,
    double-quote, or newline must emit the escaped sequence, never a
    raw byte that truncates the line (a label like shape="(4, 8)" with
    a stray quote inside is the classic unscrapeable case)."""
    reg = MetricsRegistry()
    reg.counter("test.hits").labels(
        shape="(4, 8)", tricky='say "hi"\\there\nnewline').inc(2)
    prom = reg.to_prometheus()
    line = next(l for l in prom.splitlines() if l.startswith("test_hits{"))
    assert line == ('test_hits{shape="(4, 8)",'
                    'tricky="say \\"hi\\"\\\\there\\nnewline"} 2')
    # every non-comment line still parses as  name{...} value
    for l in prom.splitlines():
        if l.startswith("#") or not l.strip():
            continue
        assert l.count(" ") >= 1 and "\n" not in l


def test_prometheus_help_line_escaping():
    """HELP text escapes backslash and newline per the format spec
    (quotes are legal there); histograms with escaped labels still emit
    well-formed bucket lines."""
    reg = MetricsRegistry()
    reg.counter("test.hits", help="path C:\\tmp\nsecond line").inc()
    reg.histogram("test.ms", help="h", buckets=(1.0,)).labels(
        shape="(4, 8)").observe(0.5)
    prom = reg.to_prometheus()
    assert "# HELP test_hits path C:\\\\tmp\\nsecond line" in prom
    assert 'test_ms_bucket{le="1.0",shape="(4, 8)"} 1' in prom
    assert 'test_ms_count{shape="(4, 8)"} 1' in prom
    # exactly one physical line per HELP entry
    helps = [l for l in prom.splitlines() if l.startswith("# HELP")]
    assert len(helps) == 2


def test_registry_rejects_bad_names():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("Bad Name!")


def test_registry_thread_safety_smoke():
    reg = MetricsRegistry()
    c = reg.counter("test.n")

    def spin():
        for _ in range(1000):
            c.inc()
    ts = [threading.Thread(target=spin) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == 4000


# ---------------------------------------------------------------------------
# TraceRecorder / Chrome trace schema
# ---------------------------------------------------------------------------

def test_trace_recorder_chrome_schema_roundtrip(tmp_path):
    rec = TraceRecorder()
    with rec.span("ignored_before_start"):
        pass
    assert rec.events() == []          # disabled spans record nothing
    rec.start()
    with rec.span("phase_a", cat="executor", args={"k": "v"}):
        with rec.span("inner"):
            pass
    rec.instant("marker")
    rec.stop()
    path = tmp_path / "trace.json"
    rec.save(str(path))
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"phase_a", "inner"}
    a = next(e for e in xs if e["name"] == "phase_a")
    assert a["cat"] == "executor" and a["args"] == {"k": "v"}
    assert a["dur"] >= 0 and a["ts"] >= 0
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    assert any(e["ph"] == "i" and e["name"] == "marker" for e in events)
    # thread ids are renumbered small for readable Perfetto tracks
    assert all(e["tid"] < 64 for e in xs)


# ---------------------------------------------------------------------------
# Executor instrumentation (the ISSUE acceptance scenario)
# ---------------------------------------------------------------------------

def _build_train_program():
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square_error_cost(layers.fc(x, size=8), y))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return loss


def _feed(batch=8):
    return {"x": np.ones((batch, 4), np.float32),
            "y": np.zeros((batch, 1), np.float32)}


def test_cached_three_step_loop_stats():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.reset_stats()
    for _ in range(3):
        exe.run(feed=_feed(), fetch_list=[loss])
    s = exe.get_stats()
    assert s["steps"] == 3
    assert s["compiles"] == 1
    # size 2: the startup-program entry + the train-step entry (caches
    # survive reset_stats; only counters were zeroed)
    assert s["jit_cache"] == {"hits": 2, "misses": 1, "evictions": 0,
                              "size": 2}
    assert s["meta_cache"]["hits"] == 2 and s["meta_cache"]["misses"] == 1
    # non-zero step-span histograms
    assert s["step_ms"]["count"] == 3 and s["step_ms"]["sum"] > 0
    assert s["spans"]["key_build"]["count"] == 3
    assert s["spans"]["trace"]["count"] == 1
    assert s["spans"]["compile"]["count"] == 1
    assert s["spans"]["execute"]["count"] == 2
    assert s["spans"]["fetch"]["count"] == 3
    assert all(s["spans"][k]["sum"] > 0 for k in s["spans"])
    # per-(program, shapes) compile histogram
    assert len(s["compile_ms"]) == 1
    entry = s["compile_ms"][0]
    assert entry["count"] == 1 and entry["sum"] > 0
    assert "x:8x4:float32" in entry["shapes"]


def test_shape_change_is_a_miss_and_new_compile():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.reset_stats()
    exe.run(feed=_feed(8), fetch_list=[loss])
    exe.run(feed=_feed(16), fetch_list=[loss])
    s = exe.get_stats()
    assert s["compiles"] == 2
    assert s["jit_cache"]["misses"] == 2 and s["jit_cache"]["hits"] == 0
    assert len(s["compile_ms"]) == 2


def test_close_counts_evictions_and_resets_gauges():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    assert exe.get_stats()["jit_cache"]["size"] == 2
    exe_id = exe._exe_id
    exe.close()
    s = exe.get_stats()
    assert s["jit_cache"]["size"] == 0 and s["meta_cache"]["size"] == 0
    assert s["jit_cache"]["evictions"] == 2
    assert s["meta_cache"]["evictions"] == 2
    # the process-wide gauge series for this executor is GONE, not stale
    g = global_registry().get("executor.jit_cache.size")
    assert not any(lbl.get("executor") == exe_id for lbl, _ in g.series())


def test_uncached_run_counts_bypass_not_miss():
    """run(use_program_cache=False) is a BYPASS: counted in
    executor.uncached_runs, never as a jit-cache miss — hit rates must
    stay truthful when a caller opts out of caching."""
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.reset_stats()
    exe.run(feed=_feed(), fetch_list=[loss], use_program_cache=False)
    s = exe.get_stats()
    local = exe._stats.local.get("executor.uncached_runs")
    assert local is not None and local.value() == 1
    assert s["jit_cache"]["misses"] == 0 and s["jit_cache"]["hits"] == 0
    assert s["steps"] == 1


def test_reset_stats_zeroes_counters_but_keeps_cache():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    exe.reset_stats()
    s = exe.get_stats()
    assert s["steps"] == 0 and s["compiles"] == 0
    # caches survived: the next identical run is a pure hit
    exe.run(feed=_feed(), fetch_list=[loss])
    s = exe.get_stats()
    assert s["jit_cache"]["hits"] == 1 and s["compiles"] == 0


def test_executor_spans_land_in_trace_capture():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rec = get_recorder()
    rec.start()
    try:
        exe.run(feed=_feed(), fetch_list=[loss])
        exe.run(feed=_feed(), fetch_list=[loss])
    finally:
        rec.stop()
    names = [e["name"] for e in rec.events()]
    rec.clear()
    for expected in ("executor.key_build", "executor.trace",
                     "executor.compile", "executor.execute",
                     "executor.fetch"):
        assert expected in names, names
    # per-op trace-time dispatch is captured too (ops registry spans)
    assert any(n.startswith("op:") for n in names)


# ---------------------------------------------------------------------------
# metric-name lint: the registry namespace stays declared & duplicate-free
# ---------------------------------------------------------------------------

def test_sort_keys_stay_in_sync_across_consumers():
    # observability.report is the source of truth; trace_report keeps a
    # literal copy so its --help avoids the framework import
    import trace_report as tr
    from paddle_tpu import profiler
    from paddle_tpu.observability.report import SORT_KEYS
    assert tr.SORT_KEYS == SORT_KEYS
    assert profiler._VALID_SORT_KEYS == (None,) + SORT_KEYS


def test_metric_specs_have_no_duplicates():
    names = [n for n, _k, _h in METRIC_SPECS]
    assert len(names) == len(set(names)), "duplicate metric declared"


def test_live_registry_names_are_all_declared():
    # drive every instrumented path once so the registry is populated
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    from paddle_tpu import profiler
    with profiler.record_event("lint_probe"):
        pass
    spec = {n: k for n, k, _h in METRIC_SPECS}
    reg = global_registry()
    for name in reg.names():
        assert name in spec, f"metric {name!r} not declared in METRIC_SPECS"
        assert reg.get(name).kind == spec[name], name
    # and both instance registries obey the same contract
    for name in exe._stats.local.names():
        assert name in spec, name


# ---------------------------------------------------------------------------
# trace_report CLI
# ---------------------------------------------------------------------------

def test_trace_report_on_profiler_output(tmp_path, capsys):
    import trace_report as tr
    from paddle_tpu import profiler

    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.reset_stats()
    base = tmp_path / "prof"
    with profiler.profiler(state="CPU", sorted_key="total",
                           profile_path=str(base)):
        for _ in range(3):
            exe.run(feed=_feed(), fetch_list=[loss])
    metrics_path = tmp_path / "metrics.json"
    dump = global_registry().to_dict()
    dump["executor_stats"] = exe.get_stats()
    metrics_path.write_text(json.dumps(dump))
    capsys.readouterr()

    rc = tr.main([str(base) + ".timeline.json",
                  "--metrics", str(metrics_path),
                  "--sorted-key", "total"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Trace Report" in out
    assert "executor.compile" in out
    assert "Cache Efficiency" in out
    assert "jit_cache" in out and "hit-rate" in out


def test_trace_report_parses_legacy_record_format(tmp_path, capsys):
    import trace_report as tr
    path = tmp_path / "legacy.json"
    path.write_text(json.dumps(
        [{"name": "old_style", "start_s": 0.0, "dur_s": 0.25, "tid": 1}]))
    assert tr.main([str(path)]) == 0
    assert "old_style" in capsys.readouterr().out


def test_trace_report_demo_smoke(tmp_path, capsys):
    import trace_report as tr
    rc = tr.main(["--demo", "--out-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert (tmp_path / "metrics_sample.json").exists()
    assert (tmp_path / "trace_sample.timeline.json").exists()
    # sample dump is single-line JSON
    text = (tmp_path / "metrics_sample.json").read_text()
    assert len(text.strip().splitlines()) == 1
    stats = json.loads(text)["executor_stats"]
    assert stats["compiles"] == 1 and stats["jit_cache"]["hits"] == 2
    assert "Cache Efficiency" in out


def test_retroactive_stamps_before_capture_start_are_clamped():
    # a request already in flight when the capture starts has
    # submit/admit perf_counter stamps predating the recorder's t0;
    # its retroactive spans must clamp to the capture origin instead
    # of emitting ts < 0 (Perfetto renders those off-viewport)
    import time as _time
    rec = TraceRecorder()
    rec.start()
    now = _time.perf_counter()
    rec.complete("request 1", now - 5.0, now, track="serving slot 0")
    rec.complete("queue", now - 5.0, now - 4.0, track="serving slot 0")
    rec.instant("retire", ts=now - 5.0, track="serving slot 0")
    rec.stop()
    evts = [e for e in rec.events() if e["name"] in
            ("request 1", "queue", "retire")]
    assert len(evts) == 3
    for e in evts:
        assert e["ts"] >= 0.0
        assert e.get("dur", 0.0) >= 0.0
    # the fully-pre-capture span collapses to zero width at the origin
    q = next(e for e in evts if e["name"] == "queue")
    assert q["ts"] == 0.0 and q["dur"] == 0.0


# ---------------------------------------------------------------------------
# one tracing API, two sinks, one clock
# ---------------------------------------------------------------------------

def _host_annotations(trace_dir, names):
    """{name: [(start_ns, dur_ns, stats)]} from the /host: planes of the
    jax.profiler trace under trace_dir."""
    import glob
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns, ev.duration_ns,
                                         dict(ev.stats)))
    return out


def _capture_both_sinks(trace_dir):
    """A jax.profiler session with the recorder on over a few spans:
    (recorder events, profiler annotations by name)."""
    import time as _time
    import jax
    from paddle_tpu import profiler
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # as benchmark/harness.py sets it
    options.enable_hlo_proto = False
    rec = get_recorder()
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        rec.start()
        with rec.span("both.outer", cat="t", args={"k": 3, "skip": [1]}):
            _time.sleep(0.002)
            with rec.span("both.inner", cat="t",
                          args=lambda: {"found": 7}):
                _time.sleep(0.001)
        with profiler.record_event("both.user"):
            _time.sleep(0.001)
        now = _time.perf_counter()
        rec.instant("both.retro", ts=now)
        rec.stop()
        events = rec.events()
        rec.clear()
    finally:
        rec.stop()
        jax.profiler.stop_trace()
    names = ("both.outer", "both.inner", "both.user",
             "trace.clock_anchor")
    return events, _host_annotations(trace_dir, names)


def test_span_lands_in_both_sinks_on_one_clock(tmp_path):
    # the two stamps of a span's edge lie microseconds apart unless the
    # scheduler takes the thread between them: a loaded box gets three
    # tries at a clean capture
    for attempt in range(3):
        events, ann = _capture_both_sinks(tmp_path / f"t{attempt}")
        (anchor,) = ann["trace.clock_anchor"]
        a_start, a_dur, a_stats = anchor
        # the reading was taken inside the anchor: recorder time ts maps
        # to profiler time anchor + (ts - the anchor's ts)
        offset_ns = a_start + a_dur / 2 - a_stats["recorder_ts_us"] * 1e3
        worst = a_dur / 2
        for name in ("both.outer", "both.inner", "both.user"):
            (e,) = [x for x in events if x["name"] == name]
            (p_start, p_dur, _stats) = ann[name][0]
            assert len(ann[name]) == 1
            worst = max(worst, abs(p_dur - e["dur"] * 1e3),
                        abs(p_start - (offset_ns + e["ts"] * 1e3)))
        if worst < 100e3:
            break
    assert worst < 100e3, f"sinks disagree by {worst:.0f} ns"
    # scalar args ride the annotation as stats, dict or callable; what
    # is no scalar stays with the recorder
    assert ann["both.outer"][0][2] == {"k": 3}
    assert ann["both.inner"][0][2] == {"found": 7}
    outer = next(x for x in events if x["name"] == "both.outer")
    assert outer["args"] == {"k": 3, "skip": [1]}
    assert a_stats["perf_counter_ns"] > 0
    # a retroactive event has no annotation; the anchor places it
    retro = next(x for x in events if x["name"] == "both.retro")
    user = next(x for x in events if x["name"] == "both.user")
    assert retro["ts"] >= user["ts"] + user["dur"]


def test_disabled_span_is_the_shared_noop_and_never_calls_args():
    rec = TraceRecorder()
    called = []

    def args():
        called.append(1)
        return {"x": 1}

    first = rec.span("off.a", args=args)
    assert first is rec.span("off.b") is get_recorder().span("off.c")
    with first:
        pass
    assert not called and rec.events() == []
    rec.start()
    with rec.span("on.a", args=args):
        assert not called           # called once, as the span closes
    with pytest.raises(KeyError):
        with rec.span("on.raises", args=args):
            raise KeyError("x")
    rec.stop()
    assert called == [1]            # not for the region that raised
    by_name = {e["name"]: e for e in rec.events()}
    assert by_name["on.a"]["args"] == {"x": 1}
    assert by_name["on.raises"]["args"] == {}


def test_executor_run_encloses_its_children():
    loss = _build_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rec = get_recorder()
    rec.start()
    try:
        exe.run(feed=_feed(), fetch_list=[loss])
        exe.run(feed=_feed(), fetch_list=[loss])
    finally:
        rec.stop()
    events = rec.events()
    rec.clear()
    runs = [e for e in events if e["name"] == "executor.run"]
    assert len(runs) == 2
    children = [e for e in events if e["name"] in (
        "executor.key_build", "executor.trace", "executor.compile",
        "executor.execute", "executor.fetch")]
    assert {e["name"] for e in children} >= {
        "executor.key_build", "executor.execute", "executor.fetch"}
    for c in children:
        assert any(r["tid"] == c["tid"] and r["ts"] <= c["ts"]
                   and c["ts"] + c["dur"] <= r["ts"] + r["dur"] + 0.002
                   for r in runs), c
