"""Profiler — the Fluid 1.5 profiling API over the observability layer.

Parity: python/paddle/fluid/profiler.py (profiler.start_profiler /
stop_profiler / profiler context / record_event, sorted-key report
tables). The reference profiler aggregates per-op CUDA events; here the
unit of work is a whole jitted step, so the backend is
paddle_tpu.observability instead:

- start_profiler() turns on the global Chrome-trace recorder
  (observability/tracing.py). While it is on, the Executor's step spans
  (key_build / trace / compile / execute / fetch), per-op trace-time
  dispatch, and record_event regions all land in one timeline, saved as
  `<profile_path>.timeline.json` — load it in chrome://tracing or
  https://ui.perfetto.dev. Device-side op names line up because
  ops/__init__.py wraps dispatch in jax.named_scope.
- For state "GPU"/"All" a jax.profiler device trace (TensorBoard/XProf)
  is also captured into trace_dir, the TPU equivalent of the reference's
  CUDA event timeline.
- stop_profiler() prints the fluid-style sorted-key report
  (Calls/Total/Min/Max/Ave/Ratio per event) and still writes the legacy
  host-record JSON to `profile_path` — the input format of
  paddle_tpu.utils.timeline's converter, kept for compatibility.

See docs/observability.md for the full workflow.
"""

import contextlib
import json
import threading
import time
import warnings

import jax

from .observability import tracing
from .observability.metrics import global_registry
from .observability.report import SORT_KEYS

_timings = []      # legacy records: (name, duration_s, start_epoch_s, tid)
_trace_dir = None
_jax_trace_active = False
_profiler_state = None

_VALID_STATES = ("CPU", "GPU", "All")
_VALID_SORT_KEYS = (None,) + SORT_KEYS    # one source: observability.report


def start_profiler(state="All", tracer_option="Default",
                   trace_dir="/tmp/paddle_tpu_profile"):
    """Begin profiling. `state` keeps fluid's contract: "CPU" records
    host spans only; "GPU"/"All" additionally capture a jax.profiler
    device trace into `trace_dir`."""
    global _jax_trace_active, _trace_dir, _profiler_state
    if state not in _VALID_STATES:
        raise ValueError(
            f"The state must be 'CPU' or 'GPU' or 'All', got {state!r}")
    _profiler_state = state
    _trace_dir = trace_dir
    _timings.clear()
    _jax_trace_active = False
    if state in ("GPU", "All"):
        try:
            jax.profiler.start_trace(trace_dir)
            _jax_trace_active = True
        except Exception:
            _jax_trace_active = False
    # after the device trace: the recorder's clock anchor has to land in it
    tracing.get_recorder().start()


def stop_profiler(sorted_key=None, profile_path='/tmp/profile'):
    """Stop profiling; print the sorted-key report table; write the raw
    host event records (JSON) to `profile_path` (the
    paddle_tpu.utils.timeline input format) and the full Chrome trace to
    `<profile_path>.timeline.json`."""
    global _jax_trace_active, _profiler_state
    # stop the captures BEFORE validating sorted_key: a typo'd key must
    # not leave the device trace / recorder running unbounded
    if _jax_trace_active:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _jax_trace_active = False
    recorder = tracing.get_recorder()
    recorder.stop()
    place = _profiler_state or "All"
    _profiler_state = None
    if sorted_key not in _VALID_SORT_KEYS:
        raise ValueError(
            f"The sorted_key must be None or in 'calls', 'total', "
            f"'max', 'min' and 'ave', got {sorted_key!r}")
    if _timings:
        _print_report(sorted_key, place)
        if profile_path:
            try:
                save_profiler_records(profile_path)
            except OSError:
                pass        # report already printed; path optional
    if profile_path and (recorder.events() or _timings):
        try:
            _write_chrome_trace(profile_path + ".timeline.json", recorder)
        except OSError:
            pass


def _print_report(sorted_key, place):
    from .observability.report import aggregate_events, format_event_table
    agg = aggregate_events((name, dur * 1e3)
                           for name, dur, _start, _tid in _timings)
    for line in format_event_table(
            agg, sorted_key, title="Profiling Report",
            subtitle=f"Place: {place}    "
                     f"Sorted by: {sorted_key or 'event order'}"):
        print(line)


def _write_chrome_trace(path, recorder):
    """Chrome trace_event JSON: the recorder's capture when one is
    live, else a conversion of the legacy records (record_event used
    without start_profiler)."""
    if recorder.events():
        recorder.save(path)
    else:
        from .utils.timeline import Timeline
        records = [{"name": n, "start_s": s, "dur_s": d, "tid": t}
                   for n, d, s, t in _timings]
        Timeline(records).save(path)


def save_profiler_records(path):
    """Write the recorded host events as JSON:
    [{"name", "start_s", "dur_s", "tid"}, ...]."""
    with open(path, "w") as f:
        json.dump([{"name": n, "start_s": s, "dur_s": d, "tid": t}
                   for n, d, s, t in _timings], f)


def reset_profiler():
    _timings.clear()
    tracing.get_recorder().clear()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path='/tmp/profile',
             tracer_option="Default"):
    if sorted_key not in _VALID_SORT_KEYS:       # fail before the body runs
        raise ValueError(
            f"The sorted_key must be None or in 'calls', 'total', "
            f"'max', 'min' and 'ave', got {sorted_key!r}")
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def record_event(name):
    """Host-side timing of a region: feeds the report table and, when
    capturing, the Chrome trace and the XLA device trace (the recorder's
    span writes both). The record lands even when the region raises —
    the trace recorder emits its event on exit, and the table must not
    disagree with it."""
    start = time.time()
    t0 = time.perf_counter()
    try:
        with tracing.get_recorder().span(name, cat="user"):
            yield
    finally:
        _timings.append((name, time.perf_counter() - t0, start,
                         threading.get_ident()))
        global_registry().counter("profiler.events",
                                  "profiler.record_event regions").inc()


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Parity: fluid.profiler.cuda_profiler. There is no CUDA here — the
    equivalent capture is the TPU/XLA trace path; this delegates to the
    standard profiler context for API compatibility."""
    warnings.warn(
        "cuda_profiler is deprecated on paddle_tpu: there is no CUDA "
        "device. Use profiler()/start_profiler(), which capture the "
        "TPU/XLA trace and the host timeline (docs/observability.md).",
        DeprecationWarning, stacklevel=3)
    with profiler(state="All", profile_path=output_file):
        yield


@contextlib.contextmanager
def npu_profiler(output_file=None, config=None):  # same contract
    warnings.warn(
        "npu_profiler is deprecated on paddle_tpu: there is no NPU "
        "device. Use profiler()/start_profiler(), which capture the "
        "TPU/XLA trace and the host timeline (docs/observability.md).",
        DeprecationWarning, stacklevel=3)
    with profiler(state="All", profile_path=output_file):
        yield
