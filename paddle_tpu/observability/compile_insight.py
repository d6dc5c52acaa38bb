"""Compile-plane observability: cost/memory attribution, the HBM
ledger, and recompile-storm detection.

PR 7 instrumented the *request* plane (what the serving stack does per
request); this module instruments the *compile* plane — what each
compiled Program costs. Fluid 1.5 answered "where did my memory go /
why is this slow" with a memory-optimization pass and an op profiler;
paddle_tpu handed both jobs to XLA and, until now, got nothing back.
Four pieces:

- **XLA extractor** (`extract_xla_cost` / `extract_xla_memory`) — the
  compiler's own numbers for a jitted entry, from
  ``lowered.cost_analysis()`` / ``compiled.memory_analysis()``, with
  graceful ``None`` degradation on backends that don't report. The
  caller falls back to the static analyzer.
- **Static analyzer** (`analyze_jaxpr` / `analyze_program`) —
  backend-independent estimates. `analyze_jaxpr` walks the traced
  jaxpr of the *whole* step (forward + grad + optimizer included, so
  no hand-waved 3x multiplier) counting per-primitive FLOPs and
  intermediate bytes; `analyze_program` walks Block/OpDesc for the
  fluid-level attribution (per-op-type FLOPs via utils/model_stat,
  param vs optimizer-state bytes, activation bytes).
- **HBM ledger** (`HBMLedger` / `hbm_ledger()`) — one process-wide
  account of device-memory commitments: param bytes, optimizer-state
  bytes, serving `PagedKVCache` pool bytes, compiled peak-HBM
  estimates. Components register/retire entries; totals publish as
  ``memory.*`` gauges and the exporter's ``/memory`` endpoint serves
  the snapshot. Resident kinds (params/optimizer/kv_cache/other) sum
  into ``memory.total_bytes``; ``peak_hbm`` entries are derived
  *estimates* over mostly the same buffers and are reported but never
  summed.
- **Recompile-storm detector** (`RecompileTracker`) — every jit-cache
  miss past a warm threshold records a structured *key diff* (which
  feed var changed shape/dtype vs the nearest cached signature),
  emits ``executor.recompile.*`` metrics, and raises a rate-windowed
  `RecompileStormWarning` pointing at `core.bucketing.FeedBucketer`.

`Executor.explain(program, feed)` assembles the full report
(docs/observability.md "Compile & memory"); `tools/compile_report.py`
renders it as a table.
"""

import math
import os
import sys
import threading
import time
import warnings

import numpy as np

from .metrics import global_registry

__all__ = [
    "extract_xla_cost", "extract_xla_memory", "analyze_jaxpr",
    "analyze_program", "explain_entry", "array_nbytes",
    "HBMLedger", "hbm_ledger", "RESIDENT_KINDS", "LEDGER_KINDS",
    "RecompileTracker", "RecompileStormWarning",
]


def _help(name):
    from . import _help as pkg_help
    return pkg_help(name)


def array_nbytes(a):
    """Device/host array byte size (bf16-correct: jax registers
    ml_dtypes, so a.dtype.itemsize is always right)."""
    return int(a.size) * np.dtype(a.dtype).itemsize


def array_nbytes_per_device(a):
    """Bytes ONE device holds: for a mesh-sharded jax Array the shard
    shape, for replicated/host arrays the full size. The HBM ledger's
    unit — per-device HBM is what capacity questions are about."""
    sharding = getattr(a, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            shard = sharding.shard_shape(tuple(a.shape))
            return int(math.prod(shard)) * np.dtype(a.dtype).itemsize
        except Exception:
            pass
    return array_nbytes(a)


# ---------------------------------------------------------------------------
# XLA extractor — the compiler's own numbers, None when it won't say
# ---------------------------------------------------------------------------

def extract_xla_cost(lowered=None, compiled=None):
    """XLA cost model for a jitted entry: {"flops", "bytes_accessed",
    "raw"} or None when the backend doesn't report (some builds return
    nothing, raise NotImplementedError, or report flops=-1)."""
    for stage in (compiled, lowered):
        if stage is None:
            continue
        try:
            costs = stage.cost_analysis()
        except Exception:
            continue
        # older jax returns a one-element list of dicts
        if isinstance(costs, (list, tuple)):
            costs = costs[0] if costs else None
        if not costs:
            continue
        flops = float(costs.get("flops", -1.0))
        if flops < 0:
            continue
        return {"flops": flops,
                "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
                "raw": {k: float(v) for k, v in dict(costs).items()
                        if isinstance(v, (int, float))}}
    return None


def extract_xla_memory(compiled):
    """Compiled memory stats: argument/output/temp/alias bytes plus the
    derived ``peak_hbm_bytes`` (arg + out + temp - alias + code), or
    None when the backend doesn't report them."""
    if compiled is None:
        return None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    fields = ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes")
    try:
        out = {f: int(getattr(mem, f)) for f in fields}
    except AttributeError:
        return None
    out["peak_hbm_bytes"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out["alias_size_in_bytes"]
        + out["generated_code_size_in_bytes"])
    return out


# ---------------------------------------------------------------------------
# static analyzer — jaxpr walk (exact shapes, backward included)
# ---------------------------------------------------------------------------

# pure data movement / layout: zero FLOPs whatever the shapes
_ZERO_FLOP_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "convert_element_type",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "gather", "scatter", "squeeze", "expand_dims", "rev", "pad", "iota",
    "copy", "device_put", "stop_gradient", "split", "pjit_no_op",
})
# one pass over the operand, not the (reduced) output
_REDUCE_PREFIXES = ("reduce_", "cum", "arg")


def _aval_nbytes(aval):
    try:
        return int(math.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:        # key arrays / abstract tokens
        return 0


def _aval_numel(aval):
    try:
        return int(math.prod(aval.shape))
    except Exception:
        return 0


def _eqn_flops(eqn):
    """FLOPs of one jaxpr equation (sub-jaxprs handled by the caller)."""
    prim = eqn.primitive.name
    out_aval = eqn.outvars[0].aval
    if prim == "dot_general":
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for d in lhs_c:
            k *= int(lhs.shape[d])
        return 2 * _aval_numel(out_aval) * k
    if prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        dn = eqn.params["dimension_numbers"]
        cout = int(rhs.shape[dn.rhs_spec[0]])
        if cout:
            # 2 * out_elems * (kernel_spatial * cin / groups)
            return 2 * _aval_numel(out_aval) * (_aval_numel(rhs) // cout)
        return 0
    if prim in _ZERO_FLOP_PRIMS:
        return 0
    if prim.startswith(_REDUCE_PREFIXES):
        return _aval_numel(eqn.invars[0].aval)
    # elementwise / transcendental / select / compare: one op per output
    return _aval_numel(out_aval)


def _sub_jaxprs(params):
    """Every jaxpr-valued param of an eqn (pjit's 'jaxpr', scan's
    'jaxpr', custom_jvp/vjp call_jaxpr, cond's 'branches' tuple, ...)."""
    from jax._src import core as jcore
    out = []
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


def analyze_jaxpr(closed):
    """Backend-independent cost walk of a (Closed)Jaxpr: total FLOPs,
    per-primitive attribution, and intermediate/output byte totals.

    Conventions (estimates, not a compiler): scan bodies multiply
    FLOPs by `length` (their intermediates count ONCE — only one
    iteration is live at a time); `while` bodies count one trip (the
    trip count is data); both `cond` branches count (upper bound);
    elementwise/transcendental ops count 1 FLOP per output element;
    pure layout ops count 0. Donation/aliasing is the caller's story
    (see `explain_entry`)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    per = {}
    totals = {"flops": 0, "intermediate_bytes": 0, "eqns": 0}

    def walk(jx, mult):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            subs = _sub_jaxprs(eqn.params)
            if subs:
                inner = mult * int(eqn.params.get("length", 1)) \
                    if prim == "scan" else mult
                for s in subs:
                    walk(s, inner)
                # a sub-jaxpr eqn's own outvars are its inner results
                # rebound — counting them again would double activations
                continue
            f = _eqn_flops(eqn) * mult
            totals["flops"] += f
            totals["eqns"] += 1
            if f:
                per[prim] = per.get(prim, 0) + f
            # scan interiors: bytes counted once (see docstring)
            for ov in eqn.outvars:
                totals["intermediate_bytes"] += _aval_nbytes(ov.aval)

    walk(jaxpr, 1)
    out_bytes = sum(_aval_nbytes(v.aval) for v in jaxpr.outvars)
    totals["intermediate_bytes"] = max(
        0, totals["intermediate_bytes"] - out_bytes)
    return {"flops": int(totals["flops"]),
            "per_primitive": dict(sorted(per.items(),
                                         key=lambda kv: -kv[1])),
            "intermediate_bytes": int(totals["intermediate_bytes"]),
            "out_bytes": int(out_bytes),
            "eqns": totals["eqns"]}


# ---------------------------------------------------------------------------
# static analyzer — Block/OpDesc walk (fluid-level attribution)
# ---------------------------------------------------------------------------

def analyze_program(program, feeds=None, state=None, batch_size=None):
    """Fluid-level static attribution for a Program: per-op-type
    forward FLOPs (utils/model_stat's hand-count rules), parameter vs
    optimizer-state bytes, activation and feed bytes. Byte numbers
    prefer the live arrays (`state`/`feeds` — actual dtypes after bf16
    casts) and fall back to the declared var shapes with -1 batch dims
    resolved to `batch_size`."""
    from ..core.framework import dtype_itemsize
    from ..utils import model_stat

    if batch_size is None:
        batch_size = 1
        for v in (feeds or {}).values():
            shape = getattr(v, "shape", ())
            if len(shape) >= 1:
                batch_size = int(shape[0])
                break

    fwd_flops, per_op = model_stat.count_flops(program, batch_size)
    train = program.backward_marker() is not None
    param_names = {p.name for p in program.all_parameters()}

    if state:
        param_bytes = sum(array_nbytes(v) for n, v in state.items()
                          if n in param_names)
        optimizer_bytes = sum(array_nbytes(v) for n, v in state.items()
                              if n not in param_names)
    else:
        param_bytes = optimizer_bytes = 0
        for v in program.list_vars():
            if not v.persistable:
                continue
            b = v.nbytes(batch_size)
            if v.name in param_names:
                param_bytes += b
            else:
                optimizer_bytes += b

    if feeds:
        feed_bytes = sum(array_nbytes(v) for v in feeds.values())
    else:
        feed_bytes = sum(v.nbytes(batch_size)
                         for v in program.list_vars() if v.is_data)

    gb = program.global_block()
    activation_bytes = sum(
        v.nbytes(batch_size) for v in gb.vars.values()
        if not v.persistable and not v.is_data and v.shape)

    return {
        "batch_size": batch_size,
        "train": train,
        "fwd_flops": int(fwd_flops),
        # the classic hand-count convention: train step ~ 3x forward
        "flops": int(fwd_flops) * (3 if train else 1),
        "per_op_type": dict(sorted(per_op.items(), key=lambda kv: -kv[1])),
        "param_bytes": int(param_bytes),
        "optimizer_bytes": int(optimizer_bytes),
        "feed_bytes": int(feed_bytes),
        "activation_bytes": int(activation_bytes),
        "num_ops": len(gb.ops),
    }


# ---------------------------------------------------------------------------
# report assembly (Executor.explain's engine)
# ---------------------------------------------------------------------------

def explain_entry(step_fn, args, program=None, state=None, feeds=None,
                  labels=None, backend=None):
    """Full compile-plane report for one jitted entry.

    `backend=None` (auto) asks XLA first and falls back to the static
    analyzer per field; `backend=False` forces the static path (the
    deterministic answer on any backend); `backend=True` demands XLA's
    numbers and raises if the backend doesn't report them. The static
    analysis always runs — it is the cross-check column.

    Headline fields and their fallback chain:
      flops          xla cost_analysis -> jaxpr walk
      bytes_accessed xla cost_analysis -> arg + out + 2x intermediates
      peak_hbm_bytes xla memory_analysis -> arg + (out - donated) +
                     intermediates (donated state aliases in-place)
    """
    import jax

    xla_cost = xla_mem = None
    if backend is not False:
        try:
            lowered = step_fn.lower(*args)
            compiled = lowered.compile()
            xla_cost = extract_xla_cost(lowered=lowered, compiled=compiled)
            xla_mem = extract_xla_memory(compiled)
        except Exception:
            xla_cost = xla_mem = None
        if backend is True and (xla_cost is None or xla_mem is None):
            raise RuntimeError(
                f"backend={jax.default_backend()!r} reports no "
                f"{'cost' if xla_cost is None else 'memory'} analysis "
                f"for this entry; use backend=None for the static "
                f"fallback")

    jaxpr_rep = analyze_jaxpr(jax.make_jaxpr(step_fn)(*args))
    prog_rep = analyze_program(program, feeds=feeds, state=state) \
        if program is not None else None

    arg_bytes = sum(array_nbytes(a) for part in args
                    for a in jax.tree_util.tree_leaves(part))
    state_bytes = sum(array_nbytes(a) for a in
                      jax.tree_util.tree_leaves(state or {}))
    donated = state_bytes if (
        program is not None and program.backward_marker() is not None
        and state) else 0
    out_bytes = jaxpr_rep["out_bytes"]
    static_peak = arg_bytes + max(0, out_bytes - donated) \
        + jaxpr_rep["intermediate_bytes"]
    static_bytes = arg_bytes + out_bytes \
        + 2 * jaxpr_rep["intermediate_bytes"]

    flops = xla_cost["flops"] if xla_cost else jaxpr_rep["flops"]
    bytes_accessed = xla_cost["bytes_accessed"] if xla_cost \
        else static_bytes
    peak = xla_mem["peak_hbm_bytes"] if xla_mem else static_peak

    report = {
        "backend": jax.default_backend(),
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "peak_hbm_bytes": peak,
        "source": {"flops": "xla" if xla_cost else "static",
                   "bytes": "xla" if xla_cost else "static",
                   "peak_hbm": "xla" if xla_mem else "static"},
        "xla": {"cost": xla_cost, "memory": xla_mem},
        "static": {"jaxpr": jaxpr_rep, "program": prog_rep,
                   "arg_bytes": int(arg_bytes),
                   "out_bytes": int(out_bytes),
                   "donated_bytes": int(donated),
                   "bytes_accessed_est": int(static_bytes),
                   "peak_hbm_bytes_est": int(static_peak)},
        "memory": {
            "param_bytes": prog_rep["param_bytes"] if prog_rep else None,
            "optimizer_bytes": prog_rep["optimizer_bytes"]
            if prog_rep else None,
            "feed_bytes": prog_rep["feed_bytes"] if prog_rep else None,
            "activation_bytes": prog_rep["activation_bytes"]
            if prog_rep else None,
            "peak_hbm_bytes": peak,
        },
    }
    if labels:
        report.update(labels)
    return report


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

RESIDENT_KINDS = ("params", "optimizer", "kv_cache", "other")
# "host_ram" rows account host-memory commitments (the serving KV
# spill tier): real bytes a fleet sizes against, but deliberately NOT
# a resident kind — memory.total_bytes stays per-device HBM truth.
LEDGER_KINDS = RESIDENT_KINDS + ("peak_hbm", "host_ram")


def _agg(kind, acc, nbytes):
    """Rollup rule per kind: resident entries are disjoint buffers and
    sum; ``peak_hbm`` entries are per-(program, shapes) estimates over
    mostly the SAME buffers — the rollup is the worst case, not a sum."""
    return max(acc, nbytes) if kind == "peak_hbm" else acc + nbytes


class HBMLedger:
    """Process-wide account of device-memory commitments.

    Entries are keyed (component, name); `register` is an upsert so a
    component refreshing a number never duplicates a row; `retire`
    drops a component's rows (and its emptied ``memory.bytes`` gauge
    series) — a closed server or a cleared jit cache must never keep
    reporting freed bytes as live. Resident kinds sum into
    ``memory.total_bytes``; ``peak_hbm`` entries are estimates over
    mostly the same buffers and never sum — per component (one entry
    per compiled (program, shapes)) they aggregate as the MAX: the
    worst-case compiled step is the component's peak.
    """

    def __init__(self, registry=None):
        self._reg = registry if registry is not None else global_registry()
        self._lock = threading.Lock()
        self._entries = {}      # (component, name) -> entry dict

    def _gauges(self):
        return (self._reg.gauge("memory.bytes", _help("memory.bytes")),
                self._reg.gauge("memory.total_bytes",
                                _help("memory.total_bytes")),
                self._reg.gauge("memory.entries", _help("memory.entries")))

    def register(self, component, name, kind, nbytes, detail=None):
        """Upsert one entry; returns it. `kind` must be a LEDGER_KINDS
        member; `detail` is a small JSON-able dict for the /memory
        endpoint (dtype, shapes, counts)."""
        if kind not in LEDGER_KINDS:
            raise ValueError(
                f"unknown ledger kind {kind!r}; expected one of "
                f"{LEDGER_KINDS}")
        entry = {"component": str(component), "name": str(name),
                 "kind": kind, "bytes": int(nbytes),
                 "detail": dict(detail or {})}
        with self._lock:
            self._entries[(entry["component"], entry["name"])] = entry
            self._publish(entry["component"])
        return entry

    def retire(self, component, name=None):
        """Drop one entry (or, with name=None, every entry) of a
        component; removes gauge series that emptied. Idempotent."""
        component = str(component)
        with self._lock:
            if name is not None:
                self._entries.pop((component, str(name)), None)
            else:
                for key in [k for k in self._entries if k[0] == component]:
                    del self._entries[key]
            self._publish(component)

    def _publish(self, component):
        """Refresh the gauges for one component + the process totals.
        Caller holds the lock."""
        by_kind_g, total_g, entries_g = self._gauges()
        live = {}
        resident_total = 0
        for e in self._entries.values():
            if e["kind"] in RESIDENT_KINDS:
                resident_total += e["bytes"]
            if e["component"] == component:
                live[e["kind"]] = _agg(e["kind"], live.get(e["kind"], 0),
                                       e["bytes"])
        for kind in LEDGER_KINDS:
            if kind in live:
                by_kind_g.labels(component=component, kind=kind).set(
                    live[kind])
            else:
                by_kind_g.remove(component=component, kind=kind)
        total_g.set(resident_total)
        entries_g.set(len(self._entries))

    def component_bytes(self, component):
        """{kind: bytes} for one component's live entries."""
        component = str(component)
        with self._lock:
            out = {}
            for e in self._entries.values():
                if e["component"] == component:
                    out[e["kind"]] = _agg(e["kind"], out.get(e["kind"], 0),
                                          e["bytes"])
            return out

    def snapshot(self):
        """JSON-able view: process totals, per-kind and per-component
        rollups, and the raw entry list (the /memory endpoint body and
        ``Executor.get_stats()["memory"]["ledger"]``)."""
        with self._lock:
            entries = [dict(e, detail=dict(e["detail"]))
                       for e in self._entries.values()]
        by_kind, by_component = {}, {}
        resident = 0
        for e in entries:
            by_kind[e["kind"]] = _agg(e["kind"], by_kind.get(e["kind"], 0),
                                      e["bytes"])
            comp = by_component.setdefault(e["component"], {})
            comp[e["kind"]] = _agg(e["kind"], comp.get(e["kind"], 0),
                                   e["bytes"])
            if e["kind"] in RESIDENT_KINDS:
                resident += e["bytes"]
        return {"total_bytes": resident,
                "by_kind": by_kind,
                "by_component": by_component,
                "entries": sorted(entries, key=lambda e: (e["component"],
                                                          e["name"]))}

    def reset(self):
        """Drop everything (tests only)."""
        with self._lock:
            components = {e["component"] for e in self._entries.values()}
            self._entries.clear()
            for c in components:
                self._publish(c)


_LEDGER = HBMLedger()


def hbm_ledger():
    return _LEDGER


# ---------------------------------------------------------------------------
# recompile-storm detector
# ---------------------------------------------------------------------------

class RecompileStormWarning(UserWarning):
    """Raised (as a warning) when an already-warm Program keeps
    compiling fresh feed signatures at storm rate — almost always
    unbucketed dynamic shapes. See docs/performance.md."""


_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _user_stacklevel():
    """stacklevel that attributes a warning to the first frame OUTSIDE
    paddle_tpu — the user's run()/run_async() call site, whatever entry
    path led here (run() is wrapped by compiler._run_maybe_compiled,
    run_async() is not, so no constant is right for both)."""
    # _getframe(1) is our caller (where warnings.warn runs) = level 1
    frame, level = sys._getframe(1), 1
    while frame is not None and frame.f_code.co_filename.startswith(
            _PKG_DIR):
        frame = frame.f_back
        level += 1
    return level


def _sig_str(shape, dtype):
    return f"{'x'.join(map(str, shape)) or 'scalar'}:{dtype}"


class RecompileTracker:
    """Per-executor jit-cache-miss historian.

    Every miss records its feed signature per program; past the warm
    threshold (`PADDLE_TPU_RECOMPILE_WARM` distinct signatures, default
    2) each further miss is a *recompile event* carrying a structured
    key diff against the nearest cached signature — which feed var
    changed shape/dtype, or whether the fetch/state signature moved
    instead. Events inside the rate window
    (`PADDLE_TPU_RECOMPILE_WINDOW_S`, default 60s) reaching the storm
    threshold (`PADDLE_TPU_RECOMPILE_STORM`, default 3) raise ONE
    `RecompileStormWarning` per burst (latched until the window
    drains). `PADDLE_TPU_RECOMPILE_DETECT=0` disables the tracker.

    Hot-path cost: zero on cache hits (never called); one small
    signature comparison per miss — next to the XLA compile a miss
    pays anyway, this is noise.
    """

    MAX_EVENTS = 64         # bounded postmortem ring per executor
    MAX_SIGNATURES = 64     # bounded per-program history: the nearest-
    #                         signature scan is O(history), and a
    #                         pathological unbucketed stream must not
    #                         grow it (or the diff cost) without limit

    def __init__(self, stats=None, warm=None, storm=None, window_s=None,
                 enabled=None, clock=None):
        import os
        env = os.environ.get
        self.enabled = (env("PADDLE_TPU_RECOMPILE_DETECT", "1") != "0"
                        if enabled is None else bool(enabled))
        self.warm = int(warm if warm is not None
                        else env("PADDLE_TPU_RECOMPILE_WARM", 2))
        self.storm = int(storm if storm is not None
                         else env("PADDLE_TPU_RECOMPILE_STORM", 3))
        self.window_s = float(
            window_s if window_s is not None
            else env("PADDLE_TPU_RECOMPILE_WINDOW_S", 60.0))
        self._stats = stats
        self._clock = clock if clock is not None else time.monotonic
        self._history = {}      # uid -> [(feed_sig, fetch, state, extra)]
        self._events = []       # bounded, newest last
        self._total_events = 0  # cumulative (the ring truncates at 64)
        self._window = []       # event timestamps inside the rate window
        self._storms = 0
        self._latched = False

    # -- bookkeeping --------------------------------------------------------
    def observe_miss(self, program_uid, program_label, feed_sig,
                     fetch_names, state_sig, step_id, extra_sig=()):
        """Record one jit-cache miss. Returns the event dict when this
        miss is a post-warm recompile (the caller threads its summary
        into the compile span's trace args), else None. `extra_sig` is
        the labeled tail of the caller's cache key ((name, value)
        pairs, e.g. program version and mesh) so a miss whose feeds
        never moved is attributed to what actually changed."""
        if not self.enabled:
            return None
        hist = self._history.setdefault(program_uid, [])
        event = None
        if hist and len(hist) >= self.warm:     # a diff needs a neighbor
            event = self._diff_event(program_uid, program_label, feed_sig,
                                     fetch_names, state_sig, step_id,
                                     extra_sig)
            self._record_event(event)
        hist.append((feed_sig, fetch_names, state_sig, extra_sig))
        del hist[:-self.MAX_SIGNATURES]
        return event

    def _diff_event(self, program_uid, program_label, feed_sig,
                    fetch_names, state_sig, step_id, extra_sig):
        now = dict((k, (s, d)) for k, s, d in feed_sig)
        best = None         # (n_changed, -recency, diff, nearest_sig)
        hist = self._history[program_uid]
        for age, (sig, fetch, state, extra) in enumerate(reversed(hist)):
            cached = dict((k, (s, d)) for k, s, d in sig)
            changed, added, removed = [], [], []
            for k, (shape, dtype) in now.items():
                if k not in cached:
                    added.append(k)
                elif cached[k] != (shape, dtype):
                    cs, cd = cached[k]
                    changed.append({
                        "var": k,
                        "from": _sig_str(cs, cd), "to": _sig_str(shape,
                                                                 dtype),
                        "kind": "dtype" if cs == shape else "shape"})
            removed = [k for k in cached if k not in now]
            n = len(changed) + len(added) + len(removed)
            key = (n, age)
            if best is None or key < best[0]:
                best = (key, changed, added, removed,
                        (sig, fetch, state, extra))
                if n == 0:      # identical feeds: no closer match exists
                    break
        _key, changed, added, removed, (near_sig, near_fetch, near_state,
                                        near_extra) = best
        if changed or added or removed:
            parts = [f"{c['var']}: {c['from']} -> {c['to']}"
                     for c in changed]
            parts += [f"+{k}" for k in added] + [f"-{k}" for k in removed]
            summary = "; ".join(parts)
        else:
            # identical feeds: name what in the rest of the cache key
            # actually moved (fetch list, persistable-state set, or the
            # caller's extra components — program version, mesh, ...)
            parts = []
            if tuple(near_fetch) != tuple(fetch_names):
                parts.append("fetch_list changed")
            if near_state != state_sig:
                parts.append("persistable state set changed")
            near_ex = dict(near_extra)
            for name, val in extra_sig:
                if name in near_ex and near_ex[name] != val:
                    parts.append(f"{name} changed "
                                 f"({near_ex[name]} -> {val})")
            summary = ("; ".join(parts)
                       or "cache key changed (cause not visible)")
        return {"step": int(step_id), "program": program_label,
                "changed": changed, "added": added, "removed": removed,
                "nearest": ";".join(_sig_str(s, d)
                                    for _k, s, d in near_sig) or "nofeeds",
                "summary": summary, "ts": self._clock()}

    def _record_event(self, event):
        self._events.append(event)
        del self._events[:-self.MAX_EVENTS]
        self._total_events += 1
        if self._stats is not None:
            self._stats.count("executor.recompile.events")
        now = event["ts"]
        self._window = [t for t in self._window
                        if now - t <= self.window_s]
        self._window.append(now)
        if self._stats is not None:
            self._stats.set_gauge("executor.recompile.window_events",
                                  len(self._window))
        if len(self._window) >= self.storm:
            if not self._latched:
                self._latched = True
                self._storms += 1
                if self._stats is not None:
                    self._stats.count("executor.recompile.storms")
                warnings.warn(RecompileStormWarning(
                    f"recompile storm: {len(self._window)} fresh XLA "
                    f"compiles of already-warm program(s) within "
                    f"{self.window_s:.0f}s (latest: {event['program']}, "
                    f"key diff vs nearest cached signature: "
                    f"{event['summary']}). Every distinct feed "
                    f"shape/dtype is a full recompile — bucket feeds "
                    f"with core.bucketing.FeedBucketer "
                    f"(run_async(bucketer=...)/run_pipelined) or pad "
                    f"host-side; see docs/performance.md and "
                    f"docs/observability.md 'Compile & memory'."),
                    stacklevel=_user_stacklevel())
        else:
            self._latched = False

    # -- surfaces -----------------------------------------------------------
    def events(self, program=None):
        """Recorded recompile events, newest last; `program` filters by
        the event's program label."""
        evs = self._events if program is None else \
            [e for e in self._events if e["program"] == program]
        return [dict(e) for e in evs]

    def snapshot(self):
        now = self._clock()
        window = [t for t in self._window if now - t <= self.window_s]
        return {"enabled": self.enabled,
                # cumulative, NOT len(self._events): the postmortem ring
                # truncates at MAX_EVENTS but the count must keep pace
                # with the executor.recompile.events counter
                "events": self._total_events,
                "storms": self._storms,
                "window_events": len(window),
                "warm_threshold": self.warm,
                "storm_threshold": self.storm,
                "window_s": self.window_s,
                "signatures": {str(uid): len(sigs)
                               for uid, sigs in self._history.items()},
                "last_events": [dict(e) for e in self._events[-5:]]}

    def reset(self):
        """Forget everything (clear_caches: freed entries make the next
        compiles cold again, not recompiles)."""
        self._history.clear()
        self._events.clear()
        self._total_events = 0
        self._window.clear()
        self._latched = False
        if self._stats is not None:
            self._stats.set_gauge("executor.recompile.window_events", 0)
