"""The program's host spans in the profiler's trace, laid over the
device's idle gaps. Two parts, as in `trace.py`:

* a reader of the `/host:` planes of the `.xplane.pb`: the events with
  the names asked for, from any thread, as `trace.Event(name, start_ns,
  dur_ns, detail)` with the event's stats as `detail`. While its span
  recorder is on the program writes each span as a
  `jax.profiler.TraceAnnotation` too
  (`paddle_tpu/observability/tracing.py`), and the profiler stamps host
  and device planes on one clock;
* pure functions over such lists: the gaps between executions of one
  module, the part of a gap that named spans cover, the median over the
  gaps. `tests/test_host_spans.py` runs them on synthetic lists.

A *gap* is the interval on the first chip between the end of one
execution of the heaviest XLA module (the one `fused_step.device_ms_p50`
and `train_step.device_ms_p50` read) and the start of the next. A *part*
is a set of span names; its time in a gap is the union of those spans
cut to the gap. Only leaf spans are named in a part, so a parent
(`serving.iteration`) is never counted beside its children.

`python3 -m benchmark.host_spans <trace dir>` (from the checkout's root)
prints the gaps and parts of a trace and how many iterations' device step lies inside the host's
feed..fetch of the same iteration (the check that the clocks are one).
"""

import os
import sys

from benchmark import stats, trace

HOST_PLANE_PREFIX = "/host:"

# the serving engine's leaf spans by the part of a gap they explain. A
# `serving.fetch` span straddles the device's step: what precedes the
# step's start is launch latency, what follows its end is the transfer
SERVING_PARTS = {
    "plan": ("serving.plan",),
    "launch": ("serving.feed", "serving.dispatch", "serving.fetch:head"),
    "fetch": ("serving.fetch:tail",),
    "commit": ("serving.commit",),
    "account": ("serving.account",),
}
SERVING_SPANS = ("serving.plan", "serving.feed", "serving.dispatch",
                 "serving.fetch", "serving.commit", "serving.account")
SPLIT_SPAN = "serving.fetch"
EXECUTOR_PARTS = {"run": ("executor.run",)}
UNATTRIBUTED = "unattributed"


# ---------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------

def read_host_events(path, names):
    """{name: [Event]} for the host planes' events named in `names`,
    threads merged, in time order."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {n: [] for n in names}
    for plane in data.planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(trace.Event(
                        ev.name, int(ev.start_ns), int(ev.duration_ns),
                        dict(ev.stats)))
    for evs in out.values():
        evs.sort(key=lambda e: e.start_ns)
    return out


def traced_host_events(traced, names):
    """The host events of a `harness.TracedWindow`, read once per set of
    names and kept on it: every reader asks, and parsing takes seconds.
    {} where the run was not traced or wrote no trace."""
    if traced is None or traced.t1 is None:
        return {}
    cache = vars(traced).setdefault("_host_events", {})
    key = tuple(names)
    if key not in cache:
        try:
            cache[key] = read_host_events(trace.find_xplane(traced.dir),
                                          names)
        except FileNotFoundError:
            cache[key] = {}
    return cache[key]


# ---------------------------------------------------------------------
# pure functions over lists of events
# ---------------------------------------------------------------------

def module_gaps(module_events, needle):
    """[(start_ns, end_ns)] between the end of one execution of the
    module whose name holds `needle` and the start of the next."""
    runs = sorted((e.start_ns, e.start_ns + e.dur_ns)
                  for e in module_events if needle in e.name)
    return [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(runs, runs[1:]) if b_start >= a_end]


def _piece(span, gap):
    """The span cut to the gap as (part-name key, start, end): its own
    name, or for the span that straddles the device's step the name with
    ':head' (before the step that ends the gap starts) or ':tail' (after
    the step that opens the gap ended). None where it misses the gap."""
    g0, g1 = gap
    a, b = max(span.start_ns, g0), min(span.start_ns + span.dur_ns, g1)
    if b <= a:
        return None
    name = span.name
    if name == SPLIT_SPAN:
        name += ":head" if span.start_ns + span.dur_ns > g1 else ":tail"
    return name, a, b


def attribute(gap, spans, parts):
    """{part: ns} of one gap, plus `UNATTRIBUTED`: the gap less the union
    of every part's spans. A span that crosses the gap's edge counts
    only inside it; parts that do not overlap sum, with the unattributed
    rest, to the gap."""
    pieces = [trace.Event(n, a, b - a, None) for n, a, b
              in filter(None, (_piece(s, gap) for s in spans))]

    def covered(names):
        return trace.union_ns([e for e in pieces if e.name in names])

    out = {part: covered(names) for part, names in parts.items()}
    out[UNATTRIBUTED] = (gap[1] - gap[0]) - covered(
        {n for names in parts.values() for n in names})
    return out


def seen_gaps(gaps, spans):
    """The gaps that lie between the first span's start and the last
    span's end: the recorder starts after the profiler and stops before
    it, and a gap it never saw says nothing about the host."""
    if not spans:
        return []
    lo = min(s.start_ns for s in spans)
    hi = max(s.start_ns + s.dur_ns for s in spans)
    return [g for g in gaps if g[0] >= lo and g[1] <= hi]


def part_medians_ms(gaps, spans, parts):
    """{part: median over the gaps of its time in the gap, ms}, with
    `UNATTRIBUTED` and 'gap' (the median gap itself); {} without gaps."""
    gaps = seen_gaps(gaps, spans)
    if not gaps:
        return {}
    rows = [attribute(gap, spans, parts) for gap in gaps]
    out = {part: stats.median([r[part] for r in rows]) / 1e6
           for part in rows[0]}
    out["gap"] = stats.median([g[1] - g[0] for g in gaps]) / 1e6
    return out


def contained_share(module_events, needle, spans, first, last):
    """The share of iterations whose device step lies inside the host's
    [start of the `first` span, end of the `last` span] of the same
    iteration (the spans' `iteration` stat pairs them); None without
    iterations. Near 1 when host and device planes share a clock."""
    lo = {s.detail.get("iteration"): s.start_ns for s in spans
          if s.name == first}
    hi = {s.detail.get("iteration"): s.start_ns + s.dur_ns for s in spans
          if s.name == last}
    runs = sorted((e.start_ns, e.start_ns + e.dur_ns)
                  for e in module_events if needle in e.name)
    its = sorted(set(lo) & set(hi))
    if not its or not runs:
        return None
    inside = 0
    for it in its:
        inside += any(lo[it] <= a and b <= hi[it] for a, b in runs
                      if a < hi[it] and b > lo[it])
    return inside / len(its)


# ---------------------------------------------------------------------
# what the readers in layer_metrics/ call
# ---------------------------------------------------------------------

def _device_modules(run):
    dev = run.traced.device if run.traced is not None else None
    if dev is None:
        return None, None
    modules = next(iter(dev.planes.values())).get(trace.MODULES_LINE, [])
    return modules, trace.heaviest_module(modules)


def run_part_medians_ms(run, span_names, parts):
    """`part_medians_ms` for a `harness.Run`: {} where the run was not
    traced, the device ran no module, or the program wrote none of the
    spans (as a program older than its span does)."""
    modules, needle = _device_modules(run)
    if needle is None:
        return {}
    by_name = traced_host_events(run.traced, span_names)
    spans = sorted((e for evs in by_name.values() for e in evs),
                   key=lambda e: e.start_ns)
    if not spans:
        return {}
    return part_medians_ms(module_gaps(modules, needle), spans, parts)


def serving_idle_ms(run, part):
    """Median time of one idle gap of the device that the serving
    engine's `part` covers, ms; None where there is nothing to read."""
    return run_part_medians_ms(run, SERVING_SPANS, SERVING_PARTS).get(part)


def executor_run_ms(run):
    return run_part_medians_ms(run, EXECUTOR_PARTS["run"],
                               EXECUTOR_PARTS).get("run")


def _dump(trace_dir):
    path = trace.find_xplane(trace_dir) if os.path.isdir(trace_dir) \
        else trace_dir
    planes = trace.read_device_lines(path, 1)
    if not planes:
        raise SystemExit(f"{path} holds no {trace.DEVICE_PLANE_PREFIX} "
                         f"plane: not a chip run")
    modules = next(iter(planes.values())).get(trace.MODULES_LINE, [])
    needle = trace.heaviest_module(modules)
    gaps = module_gaps(modules, needle)
    print(f"heaviest module {needle!r}: {len(gaps) + 1} executions")
    for names, parts, first, last in (
            (SERVING_SPANS, SERVING_PARTS, "serving.feed",
             "serving.fetch"),
            (EXECUTOR_PARTS["run"], EXECUTOR_PARTS, None, None)):
        by_name = read_host_events(path, names)
        spans = sorted((e for evs in by_name.values() for e in evs),
                       key=lambda e: e.start_ns)
        print({n: len(v) for n, v in by_name.items()})
        med = part_medians_ms(gaps, spans, parts)
        if not med:
            continue
        print(f"  gaps seen by the spans: {len(seen_gaps(gaps, spans))}")
        for part, ms in med.items():
            print(f"  {part:14s} {ms:9.4f} ms")
        print(f"  parts sum to   "
              f"{sum(v for k, v in med.items() if k != 'gap'):9.4f} ms")
        if first is not None:
            print(f"  device step inside {first}..{last} of its "
                  f"iteration: "
                  f"{contained_share(modules, needle, spans, first, last)}")


if __name__ == "__main__":
    _dump(sys.argv[1])
