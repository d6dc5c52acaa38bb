"""Executor & Scope.

Parity: python/paddle/fluid/executor.py + paddle/fluid/framework/executor.cc.

The reference Executor walks the ProgramDesc op-by-op, dispatching a C++/CUDA
kernel per op on a device stream. The TPU-native Executor instead *traces*
the whole Program (forward + jax.grad backward + optimizer updates) into a
single jitted step function per (program version, feed signature):

    step(state, feeds, rng) -> (new_state, fetches)

- `state` is the Scope's persistable variables (params, optimizer moments,
  batch-norm running stats, LR counters) as one pytree; it is donated to XLA
  so parameter updates are in-place in HBM, like fluid's in-place ops.
- feeds/fetches keep the fluid API: exe.run(program, feed={...},
  fetch_list=[...]).
- RNG: `rng` is a (2,) uint32 host array (program.random_seed, step counter);
  the step derives the PRNGKey IN-GRAPH (fold_in(PRNGKey(rng[0]), rng[1])) —
  the eager key construction cost ~0.5ms host dispatch per cached step.
  Each random op then folds in its own static op_seed (ops/random_ops.py).
"""

import collections
import contextlib
import itertools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import framework
from .framework import (Program, Variable, grad_var_name, BACKWARD_MARKER,
                        default_main_program)
from .. import ops as ops_registry
from ..observability import ComponentStats
from ..observability.tracing import get_recorder


def _canon_host(name, a):
    """Host half of the int64 policy (MIGRATION.md "Integer dtypes"):
    device integers are int32. int64 values — fluid's contract for
    ids/labels — are VALIDATED to fit and converted explicitly; a value
    past 2^31 raises instead of silently truncating (the jax default
    would wrap). float64 narrows to float32 (x64 off). numpy in/out —
    device placement is the caller's job."""
    if a.dtype == np.int64 or a.dtype == np.uint64:
        lo, hi = (np.iinfo(np.int32).min, np.iinfo(np.int32).max) \
            if a.dtype == np.int64 else (0, np.iinfo(np.uint32).max)
        if a.size:
            # ONE combined validation pass: min+max computed once and
            # reused in the error message (the old path re-scanned the
            # whole array inside the f-string on failure)
            mn, mx = int(a.min()), int(a.max())
            if mx > hi or mn < lo:
                raise OverflowError(
                    f"feed '{name}' carries {a.dtype} values outside the "
                    f"32-bit device integer range [{lo}, {hi}] (seen: "
                    f"[{mn}, {mx}]). Device integers are int32 by policy "
                    f"— re-index ids below 2**31 or split the vocab. See "
                    f"MIGRATION.md 'Integer dtypes'.")
        a = a.astype(np.int32 if a.dtype == np.int64 else np.uint32)
    elif a.dtype == np.float64:
        a = a.astype(np.float32)
    return a


def _canon_feed(name, value):
    """Single-value canonicalization (dp path)."""
    if isinstance(value, jax.Array):
        # already on device (e.g. the compiled path device_put the feed
        # with its mesh sharding) — converting via numpy would pull it
        # to host and DESTROY the placement; 64-bit dtypes can't exist
        # on device with x64 off, so there is nothing to canonicalize
        return value
    return jnp.asarray(_canon_host(name, np.asarray(value)))


def _canon_feeds(feed, device=None):
    """Canonicalize a whole feed dict; host values land on `device`.

    Two hot-path properties the per-value loop didn't have:
    - per-step identity cache: the same host array fed under several
      names (tied inputs, shared masks) pays its O(n) int64 validation
      scan and upload ONCE; strong refs live only for this call, so
      id() can't be recycled under the cache;
    - ONE batched jax.device_put for every host value: per-feed
      jnp.asarray paid jax's full dispatch overhead per array (~half
      the cached-step host cost for small models).
    """
    out = {}
    host = {}      # name -> canonical numpy, one batched upload below
    seen = {}      # id -> (obj, first name)
    dups = []
    for k, v in feed.items():
        if isinstance(v, jax.Array):
            out[k] = v        # placed already (prefetch/mesh path)
            continue
        hit = seen.get(id(v))
        if hit is not None and hit[0] is v:
            dups.append((k, hit[1]))
            continue
        seen[id(v)] = (v, k)
        host[k] = _canon_host(k, np.asarray(v))
    if host:
        out.update(jax.device_put(host, device))
    for k, first in dups:
        out[k] = out[first]
    return out


class Scope:
    """Name -> device array store for persistable variables.

    Parity: paddle/fluid/framework/scope.h. Flat (no kid scopes): the jit
    owns all temporary storage, so only persistables live here.
    """

    def __init__(self):
        self._vars = {}

    def find_var(self, name):
        return self._vars.get(name)

    def var(self, name):
        return self._vars.setdefault(name, None)

    def set(self, name, value):
        self._vars[name] = value

    def get(self, name, default=None):
        return self._vars.get(name, default)

    def __contains__(self, name):
        return name in self._vars

    def names(self):
        return list(self._vars)

    def drop(self, name):
        self._vars.pop(name, None)


_global_scope = Scope()


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old
    return guard()


def _as_fetch_name(f):
    if isinstance(f, Variable):
        return f.name
    return str(f)


# ops kept for their host-visible side effects even when nothing consumes
# their outputs (fluid's Print/assert family)
SIDE_EFFECT_OPS = {"print"}


def _slice_ops(block, fetch_names):
    """Backward slice of a block's op list: ops needed for fetches, ops
    that write persistable vars (stat/counter updates keep running), and
    side-effect roots (print)."""
    needed = set(fetch_names)
    keep = []
    for op in reversed(block.ops):
        out_names = set(op.output_names)
        writes_persistable = any(
            (n in block.vars and block.vars[n].persistable)
            for n in out_names)
        if writes_persistable or (out_names & needed) \
                or op.type in SIDE_EFFECT_OPS:
            keep.append(op)
            needed |= set(op.input_names)
    return list(reversed(keep))


def _lower_block(block, env, program, is_test):
    """Trace every op of a block into env (jit-traceable)."""
    for op in block.ops:
        if op.type == BACKWARD_MARKER:
            raise RuntimeError("backward marker must be handled by caller")
        ops_registry.run_op(op, env, program, is_test)


_EXECUTOR_SEQ = itertools.count()


def _program_label(program):
    """Stable-within-process label for compile-time histograms (uid is
    never recycled, unlike id())."""
    return f"program_{program.uid}_v{program.version}"


def _shapes_label(feed_sig):
    """Compact feed-signature label: 'x:32x4:float32;y:32x1:float32'.
    Only built on the compile (cache-miss) path — feed_sig carries raw
    np.dtype objects so the per-step key build never pays str()."""
    parts = [f"{k}:{'x'.join(map(str, shape)) or 'scalar'}:{dt}"
             for k, shape, dt in feed_sig]
    return ";".join(parts)[:160] or "nofeeds"


class FetchHandle:
    """Future for one in-flight `Executor.run_async` step.

    The XLA call was already dispatched when the handle was created; the
    device arrays inside materialize on XLA's schedule while the host
    keeps running. `result()` blocks until this step's fetches are ready
    and returns them (numpy by default, matching `exe.run`); `wait()`
    blocks without converting. An exception — raised at dispatch (bad
    feed, unknown fetch) or surfaced by the device when the step ran —
    re-raises HERE, at resolution, not inside the dispatching
    `run_async` call. Handles resolve independently and in any order;
    each carries exactly the fetches of its own step.
    """

    __slots__ = ("_exe", "_fetches", "_error", "_finished", "step",
                 "_guard")

    def __init__(self, exe, step, fetches=None, error=None, guard=None):
        self._exe = exe
        self.step = step            # executor-wide async sequence number
        self._fetches = fetches
        self._error = error
        self._finished = error is not None
        self._guard = guard         # (vec, names, step_id) sentinel ride

    def done(self):
        """True once every fetch materialized (never blocks);
        best-effort True when the backend can't answer."""
        if self._finished:
            return True
        try:
            return all(f.is_ready() for f in self._fetches
                       if hasattr(f, "is_ready"))
        except Exception:
            return True

    def wait(self):
        """Block until the step completed; re-raise its error if it
        failed. Retires the handle from the executor's in-flight
        window. Idempotent — a failed handle re-raises every time."""
        if not self._finished:
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(self._fetches)
            except Exception as e:      # device-side failure surfaces here
                self._error = e
                self._exe._stats.count("executor.async.errors")
            else:
                if self._guard is not None:
                    # NaN/Inf sentinel: the guard vec materialized with
                    # the fetches; the host check re-raises HERE (and at
                    # result()/drain()), never inside dispatch
                    g, self._guard = self._guard, None
                    try:
                        self._exe._check_guard(g)
                    except Exception as e:
                        self._error = e
            self._finished = True
            self._exe._stats.observe("executor.async.host_sync_wait_ms",
                                     (time.perf_counter() - t0) * 1e3)
            self._exe._retire(self)
        if self._error is not None:
            raise self._error
        return self

    def result(self, return_numpy=True):
        """Blocking resolution to the step's fetch list (exe.run's
        return shape): numpy copies by default, live device arrays with
        return_numpy=False."""
        self.wait()
        with self._exe._stats.span("executor.fetch",
                                   "executor.span.fetch_ms"):
            if return_numpy:
                return [np.asarray(f) for f in self._fetches]
            return list(self._fetches)


class Executor:
    """Parity: fluid.Executor. `place` selects the device — feeds are
    put there and the step runs there (a mesh-placed CompiledProgram
    names its own devices instead); XLA owns streams.

    Two dispatch surfaces share one compiled-step cache:
      run()       — synchronous fluid semantics (numpy fetches in hand
                    when the call returns);
      run_async() — non-blocking: returns a FetchHandle immediately and
                    keeps up to `async_window` donated step executables
                    in flight, so the device never waits for the host's
                    feed preparation (docs/performance.md).

    `guard=True` (or PADDLE_TPU_GUARD=1, or a robustness.GuardConfig)
    folds a NaN/Inf sentinel into every compiled step: one fused
    isfinite reduction over the loss, the param grads, and the float
    fetches, checked host-side where results are observed — run()
    raises robustness.NonFiniteError directly, async steps re-raise it
    at FetchHandle.result()/wait()/drain() (docs/robustness.md). The
    guard is fixed for the executor's lifetime (it is baked into the
    compiled step functions).
    """

    def __init__(self, place=None, async_window=None, guard=None):
        from .place import TPUPlace
        self.place = place if place is not None else TPUPlace(0)
        # resolved at first dispatch, so constructing an Executor never
        # initializes the backend
        self._device = None
        self._cache = {}
        self._meta_cache = {}   # static per-(program, feeds, fetches) work
        self._step_counter = 0
        self._last_call = None
        # async pipeline: bounded window of dispatched-but-unresolved
        # steps (depth 2 overlaps host prep with device compute without
        # piling up feed buffers in HBM)
        self.async_window = int(
            async_window if async_window is not None
            else os.environ.get("PADDLE_TPU_ASYNC_WINDOW", 2))
        self._inflight = collections.deque()
        self._async_seq = 0
        # NaN/Inf sentinel (robustness/guard.py): resolved once, then
        # immutable — the sentinel reduction is baked into every step
        # function this executor compiles
        from ..robustness.guard import GuardConfig
        self._guard = GuardConfig.resolve(
            guard if guard is not None
            else os.environ.get("PADDLE_TPU_GUARD"))
        # observability: per-instance counters/histograms mirrored into
        # the process-wide registry; gauges labeled per-executor there
        self._exe_id = f"exe{next(_EXECUTOR_SEQ)}"
        self._stats = ComponentStats(gauge_labels={"executor": self._exe_id})
        self._telemetry_server = None   # serve_metrics() mount
        # compile-plane observability (observability/compile_insight.py):
        # the recompile-storm detector rides the jit-cache miss path;
        # _entry_meta remembers each cached entry's (program, shapes)
        # labels so clear_caches can retire exactly its series
        from ..observability.compile_insight import RecompileTracker
        self._recompile = RecompileTracker(stats=self._stats)
        self._entry_meta = {}           # cache key -> compile_ms labels
        self._mem_vars = {}             # var name -> (nbytes, is_param)

    # ------------------------------------------------------------------
    def clear_caches(self):
        """Drop the step-fn and metadata caches (counted as evictions),
        zero the cache-size gauges, and retire the freed entries'
        observability: their per-(program, shapes) compile-time
        histogram series, this executor's HBM-ledger rows, and the
        recompile tracker's signature history — a freed entry must
        never keep reporting as live, and the next compile of the same
        shape is cold, not a recompile."""
        if self._cache:
            self._stats.count("executor.jit_cache.evictions",
                              len(self._cache))
        if self._meta_cache:
            self._stats.count("executor.meta_cache.evictions",
                              len(self._meta_cache))
        hist = self._stats.local.get("executor.compile_ms")
        if hist is not None:
            for labels in self._entry_meta.values():
                hist.remove(**labels)
        self._entry_meta.clear()
        self._mem_vars.clear()
        from ..observability.compile_insight import hbm_ledger
        hbm_ledger().retire(self._exe_id)
        self._recompile.reset()
        self._cache.clear()
        self._meta_cache.clear()
        self._update_cache_gauges()

    def close(self):
        # drain first: in-flight steps still own donated state buffers
        # and their owners may still resolve handles after close()
        self.drain(raise_errors=False)
        self.clear_caches()
        # a closed executor must not keep reporting cache sizes from the
        # process-wide registry (stale gauges in long-lived processes)
        self._stats.drop_gauges("executor.jit_cache.size",
                                "executor.meta_cache.size",
                                "executor.async.inflight",
                                "executor.recompile.window_events")
        if self._telemetry_server is not None:
            self._telemetry_server.close()
            self._telemetry_server = None
        self._last_call = None
        self._compiled_pair = None

    # -- async pipeline -------------------------------------------------
    def _update_inflight_gauge(self):
        self._stats.set_gauge("executor.async.inflight",
                              len(self._inflight))

    def _retire(self, handle):
        """Drop a finished handle from the in-flight window (called by
        FetchHandle.wait; resolution order is the caller's choice)."""
        try:
            self._inflight.remove(handle)
        except ValueError:
            return                      # already retired (drain raced)
        self._update_inflight_gauge()

    def _wait_oldest(self):
        """Window admission: block on the OLDEST in-flight step. An
        error it captured stays in ITS handle (re-raised at that
        handle's result()), never in the step being admitted."""
        h = self._inflight[0]
        try:
            h.wait()
        except Exception:
            pass
        if self._inflight and self._inflight[0] is h:
            # wait() normally retires; belt-and-braces against a handle
            # whose fetches can't be blocked on
            self._inflight.popleft()
            self._update_inflight_gauge()

    def drain(self, raise_errors=True):
        """Block until every in-flight async step has completed (FIFO).
        The first captured error re-raises AFTER the pipeline is empty
        (raise_errors=False keeps it in its handle instead — close()'s
        mode)."""
        first_err = None
        while self._inflight:
            h = self._inflight[0]
            try:
                h.wait()
            except Exception as e:
                if first_err is None:
                    first_err = e
            if self._inflight and self._inflight[0] is h:
                self._inflight.popleft()
                self._update_inflight_gauge()
        if first_err is not None and raise_errors:
            raise first_err

    def _update_cache_gauges(self):
        self._stats.set_gauge("executor.jit_cache.size", len(self._cache))
        self._stats.set_gauge("executor.meta_cache.size",
                              len(self._meta_cache))

    # -- NaN/Inf sentinel ----------------------------------------------
    def _check_guard(self, guard):
        """Host half of the sentinel: `guard` is (vec, names, step_id)
        from a guarded step — vec[i] is the in-graph all-isfinite of
        names[i]. The np.asarray is a tiny sync that rides the fetch
        the caller was about to pay anyway."""
        if guard is None:
            return
        vec, names, step_id = guard
        self._stats.count("executor.fault.guard_steps")
        flags = np.asarray(vec)
        if flags.size and not flags.all():
            bad = [names[i] for i in np.nonzero(~flags)[0]]
            self._stats.count("executor.fault.nonfinite")
            from ..robustness.guard import NonFiniteError
            raise NonFiniteError(bad[0], step_id, bad)

    # -- observability --------------------------------------------------
    def serve_metrics(self, port=0, host=None):
        """Mount the stdlib telemetry endpoint (/metrics Prometheus
        exposition of the process-wide registry, /healthz with this
        executor's vitals) — the training-side twin of
        GenerationServer.serve_metrics. Binds loopback by default
        (docs/observability.md security note); idempotent while a mount
        is live, but an explicit port/host that differs from the live
        mount raises instead of silently returning the old endpoint;
        closed with the executor."""
        from ..observability.exporter import (check_remount,
                                              serve_metrics as _serve)
        if self._telemetry_server is not None and \
                not self._telemetry_server.closed:
            check_remount(self._telemetry_server, port, host)
            return self._telemetry_server    # live mount: idempotent

        def _health():
            s = self.get_stats()
            return {"executor": s["executor"], "steps": s["steps"],
                    "compiles": s["compiles"],
                    "inflight": s["async"]["inflight"],
                    "guarded": s["fault"]["guarded"]}

        self._telemetry_server = _serve(port=port,
                                        host=host or "127.0.0.1",
                                        health_fn=_health)
        return self._telemetry_server

    def get_stats(self):
        """Structured snapshot of this executor's counters and span
        histograms (docs/observability.md). Cheap; safe to call every
        step."""
        local = self._stats.local

        def c(name):
            m = local.get(name)
            return int(m.value()) if m is not None else 0

        def h(name):
            m = local.get(name)
            return m.summary() if m is not None else \
                {"count": 0, "sum": 0.0, "min": None, "max": None,
                 "avg": 0.0}

        compile_hist = local.get("executor.compile_ms")
        per_key = []
        if compile_hist is not None:
            for labels, summ in compile_hist.summaries():
                if summ["count"]:   # reset_stats keeps zeroed label series
                    per_key.append(dict(labels, **summ))
        return {
            "executor": self._exe_id,
            "steps": c("executor.steps"),
            "compiles": c("executor.compiles"),
            "jit_cache": {"hits": c("executor.jit_cache.hits"),
                          "misses": c("executor.jit_cache.misses"),
                          "evictions": c("executor.jit_cache.evictions"),
                          "size": len(self._cache)},
            "meta_cache": {"hits": c("executor.meta_cache.hits"),
                           "misses": c("executor.meta_cache.misses"),
                           "evictions": c("executor.meta_cache.evictions"),
                           "size": len(self._meta_cache)},
            "step_ms": h("executor.step_ms"),
            "spans": {k: h(f"executor.span.{k}_ms")
                      for k in ("key_build", "trace", "compile",
                                "execute", "fetch")},
            "fault": {"guard_steps": c("executor.fault.guard_steps"),
                      "nonfinite": c("executor.fault.nonfinite"),
                      "guarded": self._guard is not None},
            "async": {"dispatches": c("executor.async.dispatches"),
                      "errors": c("executor.async.errors"),
                      "window_waits": c("executor.async.window_waits"),
                      "inflight": len(self._inflight),
                      "window": self.async_window,
                      "dispatch_ms": h("executor.async.dispatch_ms"),
                      "host_sync_wait_ms":
                          h("executor.async.host_sync_wait_ms")},
            "compile_ms": per_key,
            "recompile": self._recompile.snapshot(),
            "memory": self._memory_stats(),
        }

    def _memory_stats(self):
        """The HBM-ledger view get_stats()['memory'] exposes: this
        executor's own rows plus the unified process-wide snapshot
        (params + optimizer state + serving PagedKVCache pools +
        compiled peak-HBM estimates)."""
        from ..observability.compile_insight import hbm_ledger
        led = hbm_ledger()
        return {"component": self._exe_id,
                "own": led.component_bytes(self._exe_id),
                "ledger": led.snapshot()}

    def reset_stats(self):
        """Zero this executor's local counters/histograms (the process-
        wide registry keeps its cumulative totals)."""
        self._stats.reset()
        self._update_cache_gauges()

    def _last_compiled(self):
        """AOT-compiled object for the most recent step, memoized for
        the CURRENT step_fn only — lower().compile() would otherwise
        re-pay the full XLA compile (~20-40s for the big models) on
        every introspection call, and keeping more than one executable
        leaks them across programs. Identity-compared against the live
        step_fn (an id() key could alias a recycled address)."""
        step_fn = self._last_step()[0]
        pair = getattr(self, "_compiled_pair", None)
        if pair is None or pair[0] is not step_fn:
            self._compiled_pair = (step_fn, self._lower_last().compile())
        return self._compiled_pair[1]

    def _last_step(self):
        """(step_fn, args, mesh context) of the most recent step. The
        mesh it ran under comes along because ops that consult the
        active mesh at trace time (the flash shard_map wrap, ring
        attention) must see the same one when the step is re-traced
        for introspection, or the program inspected is not the one
        that ran."""
        if self._last_call is None:
            raise RuntimeError("no program has been run yet")
        step_fn, args, mesh = self._last_call
        return step_fn, args, (mesh if mesh is not None
                               else contextlib.nullcontext())

    def _lower_last(self):
        step_fn, args, mesh_ctx = self._last_step()
        with mesh_ctx:
            return step_fn.lower(*args)

    def last_compiled_text(self):
        """Optimized HLO of the most recent step executable (post-XLA-opt;
        what actually ran). Used by the HLO audits and kernel tests."""
        return self._last_compiled().as_text()

    def last_lowered_text(self):
        """StableHLO of the most recent step BEFORE backend optimization.
        Backend-independent: bf16 dot operand types and remat's duplicated
        computation are still visible here, where the CPU backend's
        legalization (bf16->f32 upcast) and CSE would erase them from the
        optimized text. Used by tests/perf/ HLO audits."""
        return self._lower_last().as_text()

    def explain(self, program=None, feed=None, fetch_list=None,
                scope=None, backend=None):
        """Full compile-plane report for (program, feed): FLOPs, bytes
        accessed, peak HBM, per-primitive/per-op-type attribution,
        param vs optimizer-state bytes, this entry's compile-time
        history and the program's recorded recompile causes
        (docs/observability.md "Compile & memory";
        tools/compile_report.py renders the table).

        On-demand and read-free: no step runs, the step counter does
        not advance, and cache/recompile metrics are untouched — but a
        fresh entry IS built and cached when none matches, pre-warming
        the next run() (which then counts a hit whose miss was never
        recorded). `backend=None` tries XLA's cost/memory analysis and
        falls back to the static analyzer per field; `backend=False`
        forces the static path; `backend=True` raises if the backend
        reports nothing. The report's peak-HBM estimate is upserted
        into the process-wide HBM ledger (kind ``peak_hbm``) so the
        /memory endpoint carries it; clear_caches()/close() retire it.
        """
        from ..observability import compile_insight as _ci
        program = program if program is not None else default_main_program()
        if getattr(program, "_data_parallel", False):
            raise NotImplementedError(
                "explain() takes a plain Program — the data-parallel "
                "CompiledProgram path places state per-mesh at run time")
        program = getattr(program, "program", program)  # CompiledProgram
        scope = scope if scope is not None else global_scope()
        fetch_names = tuple(_as_fetch_name(f) for f in (fetch_list or []))
        entry, state, feeds, feed_sig, _fresh, _diff = self._resolve_entry(
            program, feed or {}, fetch_names, scope, record=False)
        step_fn, _guard_cell = entry
        seed = program.random_seed or framework.default_seed()
        rng = np.asarray([seed & 0xFFFFFFFF,
                          self._step_counter & 0xFFFFFFFF], np.uint32)
        labels = {"program": _program_label(program),
                  "shapes": _shapes_label(feed_sig)}
        report = _ci.explain_entry(step_fn, (state, feeds, rng),
                                   program=program, state=state,
                                   feeds=feeds, labels=labels,
                                   backend=backend)
        report["executor"] = self._exe_id
        report["fetches"] = list(fetch_names)
        # compile history for exactly this (program, shapes) series
        report["compile_ms"] = None
        hist = self._stats.local.get("executor.compile_ms")
        if hist is not None:
            for lbl, summ in hist.summaries():
                if lbl == labels and summ["count"]:
                    report["compile_ms"] = summ
        report["recompiles"] = self._recompile.events(labels["program"])
        _ci.hbm_ledger().register(
            self._exe_id, f"{labels['program']}/{labels['shapes']}/peak",
            "peak_hbm", report["peak_hbm_bytes"],
            detail={"source": report["source"]["peak_hbm"]})
        return report

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Consume every sample in `dataset`, one optimizer step per
        batch. Parity: fluid.Executor.train_from_dataset
        (executor.py:894). The reference spawns `thread` HogwildWorkers
        each interpreting the op list against a feed queue; here the
        whole step is one donated XLA executable, so threads go to the
        native file PARSER (csrc/dataset_feed.cc) and the host loop just
        hands static-shape batches to the device."""
        return self._run_from_dataset(program, dataset, scope, thread,
                                      debug, fetch_list, fetch_info,
                                      print_period, is_infer=False)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Parity: fluid.Executor.infer_from_dataset (executor.py:817).
        Same loop as train_from_dataset; the program decides whether
        anything trains (pass a clone(for_test=True) / optimizer-free
        program, as the reference's examples do — the reference's
        `_set_infer` flag only gates pserver gradient push, which is
        design-deleted on TPU)."""
        return self._run_from_dataset(program, dataset, scope, thread,
                                      debug, fetch_list, fetch_info,
                                      print_period, is_infer=True)

    def _run_from_dataset(self, program, dataset, scope, thread, debug,
                          fetch_list, fetch_info, print_period, is_infer):
        import time as _time
        if dataset is None:
            raise RuntimeError("dataset is need and should be initialized")
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        dataset._prepare_to_run()
        # reference executor.py _prepare_trainer: an explicit thread > 0
        # overrides dataset.thread_num (the docstring's min() is stale)
        nthread = thread if thread > 0 else dataset.thread_num
        names = [f if isinstance(f, str) else f.name
                 for f in (fetch_list or [])]
        infos = list(fetch_info) if fetch_info else names
        step = 0
        t0 = _time.perf_counter()
        base_prog = getattr(program, "program", program)  # CompiledProgram
        gb = base_prog.global_block()
        drop = None        # loop-invariant: batch key sets are identical

        def batches():
            nonlocal drop
            for feed in dataset._iter_batches(nthread):
                # drop feed entries the program doesn't declare (e.g. the
                # auto-emitted <name>_seq_len when the program skips it)
                if drop is None:
                    drop = {k for k in feed if not gb.has_var(k)}
                if drop:
                    feed = {k: v for k, v in feed.items()
                            if k not in drop}
                yield feed

        it = batches()
        # overlap host->device transfer with device compute; on the
        # data-parallel path each batch is placed straight into its
        # sharded mesh layout (specs memoized per batch-shape set: one
        # entry, plus possibly the tail batch). Gate on _data_parallel,
        # NOT the mesh property — reading CompiledProgram.mesh lazily
        # CREATES a dp mesh, which would shard inputs for a program
        # that run() then executes single-device.
        from ..reader.dataloader import device_prefetch
        if getattr(program, "_data_parallel", False):
            from .compiler import _shard_feeds_spec
            mesh = program.mesh
            spec_memo = {}

            def sharding_for(feed):
                key = tuple(sorted((k, getattr(v, "shape", ()))
                                   for k, v in feed.items()))
                if key not in spec_memo:
                    # _shard_feeds_spec reads only .shape/.ndim — numpy
                    # arrays go in directly, no device round-trip
                    spec_memo[key] = _shard_feeds_spec(feed, mesh)
                return spec_memo[key]

            it = device_prefetch(it, depth=2, sharding_fn=sharding_for)
        else:
            it = device_prefetch(it, depth=2)
        for feed in it:
            out = self.run(program, feed=feed, fetch_list=fetch_list,
                           scope=scope)
            step += 1
            if names and step % print_period == 0:
                msgs = [f"{info}: {np.asarray(v).ravel()[:8]}"
                        for info, v in zip(infos, out)]
                print(f"step {step}: " + ", ".join(msgs))
            if debug:
                dt = (_time.perf_counter() - t0) / step
                print(f"step {step}: avg {dt * 1e3:.2f} ms/batch")
        dataset._finish_to_run()
        return None

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            feed_var_name="feed", fetch_var_name="fetch", return_numpy=True,
            use_program_cache=True):
        t_step0 = time.perf_counter()
        # the parent of key_build / trace / compile / execute / fetch:
        # one step's host time as one duration
        with get_recorder().span("executor.run", cat="executor"):
            fetches, guard = self._dispatch(program, feed, fetch_list,
                                            scope, use_program_cache)
            # sentinel check BEFORE conversion: sync semantics put the
            # NonFiniteError in the caller's hands, not in the fetch
            # copies
            self._check_guard(guard)
            with self._stats.span("executor.fetch",
                                  "executor.span.fetch_ms"):
                if return_numpy:
                    out = [np.asarray(f) for f in fetches]
                else:
                    out = list(fetches)
            self._stats.observe("executor.step_ms",
                                (time.perf_counter() - t_step0) * 1e3)
        return out

    def run_async(self, program=None, feed=None, fetch_list=None,
                  scope=None, window=None, use_program_cache=True,
                  bucketer=None):
        """Non-blocking run(): dispatch the step and return a
        FetchHandle immediately.

        At most `window` (default: self.async_window) steps stay in
        flight; when the window is full this call first blocks on the
        OLDEST outstanding step — the bounded pipeline that overlaps
        host-side feed preparation with device compute without letting
        feed buffers pile up in HBM. Errors (a bad feed, an unknown
        fetch, a device-side failure) are captured into the returned
        handle and re-raised at its result()/wait(), keeping dispatch
        order == feed order even through a failed step. `bucketer` (a
        core.bucketing.FeedBucketer) pads the feed before dispatch so a
        dynamic-batch loop stays within O(log n) jit-cache entries.

        State semantics match run(): the scope's persistables are
        updated at dispatch time with the (asynchronously materializing)
        output arrays, so back-to-back dispatches chain on-device.
        """
        win = max(1, int(self.async_window if window is None else window))
        if getattr(program, "_data_parallel", False):
            raise NotImplementedError(
                "run_async does not take a data-parallel CompiledProgram "
                "— the dp path places feeds/state synchronously; use "
                "run(), whose XLA dispatch is already async under the "
                "hood")
        program = getattr(program, "program", program)   # CompiledProgram
        while len(self._inflight) >= win:
            self._stats.count("executor.async.window_waits")
            self._wait_oldest()
        t0 = time.perf_counter()
        step = self._async_seq
        self._async_seq += 1
        try:
            if bucketer is not None:
                feed = bucketer.bucket(feed or {})
            fetches, guard = self._dispatch(program, feed, fetch_list,
                                            scope, use_program_cache)
        except Exception as e:
            # dispatch never ran on device: deliver the error through
            # the handle (async contract — the CALLER of result() owns
            # failure handling, not whatever loop happened to dispatch)
            self._stats.count("executor.async.errors")
            return FetchHandle(self, step, error=e)
        handle = FetchHandle(self, step, fetches, guard=guard)
        self._inflight.append(handle)
        self._update_inflight_gauge()
        self._stats.count("executor.async.dispatches")
        self._stats.observe("executor.async.dispatch_ms",
                            (time.perf_counter() - t0) * 1e3)
        return handle

    def run_pipelined(self, program=None, feed_iter=None, fetch_list=None,
                      scope=None, window=None, prefetch_depth=2,
                      bucketer=None, return_numpy=True):
        """Drive a whole feed stream through the async pipeline,
        yielding one resolved fetch list per feed, in feed order.

        Three overlapped stages, the same machinery train_from_dataset
        uses but for a plain python feed iterable:
          host:   optional FeedBucketer padding (power-of-2 shapes),
          copy:   reader.dataloader.device_prefetch — the NEXT batches
                  are device_put while the current step computes,
          device: run_async's bounded in-flight window.
        Results lag dispatch by `window` steps; the generator drains the
        window at stream end. A step's error raises at ITS yield point.
        """
        from ..reader.dataloader import device_prefetch
        win = max(1, int(self.async_window if window is None else window))

        def canon(feed):
            # the int64 policy must hold on THIS path too: a raw
            # device_put would silently wrap out-of-range int64 ids
            # where run()/run_async raise (MIGRATION.md "Integer
            # dtypes") — canonicalize host-side, before upload
            return {k: v if isinstance(v, jax.Array)
                    else _canon_host(k, np.asarray(v))
                    for k, v in feed.items()}

        if bucketer is not None:
            def transform(feed, _b=bucketer.bucket):
                return canon(_b(feed))
        else:
            transform = canon
        pending = collections.deque()
        for feed in device_prefetch(feed_iter, depth=prefetch_depth,
                                    transform=transform):
            pending.append(self.run_async(
                program, feed=feed, fetch_list=fetch_list, scope=scope,
                window=win))
            if len(pending) > win:
                yield pending.popleft().result(return_numpy=return_numpy)
        while pending:
            yield pending.popleft().result(return_numpy=return_numpy)

    def _resolve_entry(self, program, feed, fetch_names, scope,
                       use_program_cache=True, record=True):
        """Canonicalize feeds, validate the (program, feed, fetch)
        triple, assemble the persistable state, and build-or-fetch the
        cached step fn. Returns (entry, state, feeds, feed_sig, fresh,
        diff): `diff` is the recompile key diff when this miss happened
        on an already-warm program (None otherwise). `record=False`
        (explain()'s mode) builds/caches exactly the same entry but
        skips the hit/miss counters and the recompile tracker — an
        on-demand introspection call must not fire a storm warning or
        skew cache-efficiency metrics."""
        with self._stats.span("executor.key_build",
                              "executor.span.key_build_ms"):
            if self._device is None:
                self._device = self.place.jax_device()
            feeds = _canon_feeds(feed, self._device)
            # np.dtype objects hash/compare fine and cost nothing; the
            # human-readable str(dtype) is built only in _shapes_label
            # on the compile path (str() per feed per step was ~10% of
            # the cached-step key build)
            feed_sig = tuple(sorted((k, v.shape, v.dtype)
                                    for k, v in feeds.items()))

            # validation + persistable enumeration are static per (program
            # version, feed keys, fetches) — walking every op each run()
            # cost ~0.5ms/step on cached small-model steps
            meta_key = (program.uid, program.version,
                        tuple(sorted(feed)), fetch_names)
            persist_names = (self._meta_cache.get(meta_key)
                             if use_program_cache else None)
            if persist_names is None:
                # a bypassed cache (use_program_cache=False) is not a
                # miss — counting it would fake a churn problem
                if use_program_cache and record:
                    self._stats.count("executor.meta_cache.misses")
                # early, friendly validation (parity: fluid's
                # check_feed_shape_type)
                gb = program.global_block()
                for f in fetch_names:
                    base = f[:-5] if f.endswith("@GRAD") else f
                    if not gb.has_var(base):
                        raise ValueError(
                            f"fetch target '{f}' is not a variable of this "
                            f"program")
                live_ops = gb.ops if program.backward_marker() is not None \
                    else _slice_ops(gb, fetch_names)
                for v in program.list_vars():
                    if v.is_data and v.name not in feeds and not v.persistable:
                        if any(v.name in op.input_names for op in live_ops):
                            raise ValueError(
                                f"feed variable '{v.name}' is required by "
                                f"the program but missing from feed={{...}}")
                persist_names = tuple(sorted(
                    v.name for v in program.list_vars() if v.persistable))
                if use_program_cache:
                    self._meta_cache[meta_key] = persist_names
            elif record:
                self._stats.count("executor.meta_cache.hits")
            state = {n: scope.get(n) for n in persist_names
                     if scope.get(n) is not None}
            state_sig = tuple(sorted(state))

            mesh = getattr(self, "_active_mesh", None)
            mesh_key = None if mesh is None \
                else (id(mesh), tuple(mesh.axis_names))
            key = (program.uid, program.version, feed_sig, fetch_names,
                   state_sig, mesh_key)
        entry = self._cache.get(key) if use_program_cache else None
        fresh = entry is None
        diff = None
        if fresh:  # entry = (step_fn, guard_cell)
            if record:
                if use_program_cache:
                    self._stats.count("executor.jit_cache.misses")
                    # recompile-storm detector: a miss on an already-warm
                    # program records a key diff vs the nearest cached
                    # signature (and may warn, rate-windowed)
                    diff = self._recompile.observe_miss(
                        program.uid, _program_label(program), feed_sig,
                        fetch_names, state_sig, self._step_counter,
                        extra_sig=(("program version", program.version),
                                   ("mesh", mesh_key)))
                else:
                    self._stats.count("executor.uncached_runs")
            # "trace" span: program -> step-closure construction; the
            # jaxpr trace + XLA compile happen lazily inside the first
            # invocation (the "compile" span below)
            with self._stats.span("executor.trace",
                                  "executor.span.trace_ms"):
                entry = self._build(program, fetch_names, persist_names,
                                    state_sig)
            if use_program_cache:
                self._cache[key] = entry
                self._entry_meta[key] = {
                    "program": _program_label(program),
                    "shapes": _shapes_label(feed_sig)}
            # sizes only change on an insert (or clear_caches); a pure
            # hit must not pay two gauge writes
            self._update_cache_gauges()
            # HBM ledger: param vs optimizer-state bytes of the state
            # this entry closes over (miss-path-only bookkeeping;
            # upserts, so re-compiles just refresh the numbers)
            self._register_state_memory(program, state)
        elif record:
            self._stats.count("executor.jit_cache.hits")
        return entry, state, feeds, feed_sig, fresh, diff

    def _register_state_memory(self, program, state):
        """Register resident state in the process-wide HBM ledger,
        split param vs optimizer-state (moments, LR counters,
        batch-norm stats): the ledger's training-side rows.

        The accounting unit is the VAR NAME, merged across programs
        into two rows per executor: a train program and its
        clone(for_test=True) eval program run over the SAME scope
        arrays, so per-program rows would double-count every shared
        parameter (the trade-off: distinct scopes feeding one executor
        under-count, which is the rarer shape)."""
        if not state:
            return
        from ..observability.compile_insight import (
            array_nbytes_per_device, hbm_ledger)
        pset = {p.name for p in program.all_parameters()}
        for n, v in state.items():
            # per-DEVICE bytes: under a dp/tp mesh a dist_attr-sharded
            # var costs each chip only its shard
            self._mem_vars[n] = (array_nbytes_per_device(v), n in pset)
        param_b = opt_b = 0
        n_params = n_opt = 0
        for b, is_param in self._mem_vars.values():
            if is_param:
                param_b += b
                n_params += 1
            else:
                opt_b += b
                n_opt += 1
        led = hbm_ledger()
        led.register(self._exe_id, "state/params", "params", param_b,
                     detail={"vars": n_params})
        led.register(self._exe_id, "state/optimizer", "optimizer",
                     opt_b, detail={"vars": n_opt})

    def _dispatch(self, program, feed, fetch_list, scope,
                  use_program_cache):
        """Shared front half of run()/run_async(): canonicalize feeds,
        build or fetch the cached step fn, invoke it (XLA dispatch is
        asynchronous), write the new state into the scope. Returns
        (fetches, guard): the step's fetch tuple as device arrays, and
        the sentinel ride-along for _check_guard (None unguarded) —
        synchronization, numpy conversion and the guard check belong to
        the caller."""
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_names = tuple(_as_fetch_name(f) for f in (fetch_list or []))

        entry, state, feeds, feed_sig, fresh, diff = self._resolve_entry(
            program, feed, fetch_names, scope, use_program_cache)
        step_fn, guard_cell = entry

        seed = program.random_seed or framework.default_seed()
        # (seed, step) ride in as a tiny host array; the key derivation
        # happens INSIDE the compiled step — the eager
        # PRNGKey+fold_in pair cost ~0.5ms of host dispatch per step
        # (half the cached-step overhead)
        # mask to uint32: PRNGKey accepted negative/wide seeds and numpy 2
        # would raise where jax silently wrapped
        step_id = self._step_counter     # what the RNG folds in; what a
        #                                  NonFiniteError reports
        rng = np.asarray([seed & 0xFFFFFFFF,
                          step_id & 0xFFFFFFFF], np.uint32)
        self._step_counter += 1

        self._last_call = (step_fn, (state, feeds, rng),
                           getattr(self, "_active_mesh", None))
        if fresh:
            labels = {"program": _program_label(program),
                      "shapes": _shapes_label(feed_sig)}
            # a post-warm recompile rides its key diff into the trace
            # span args (NOT the metric labels — unbounded cardinality),
            # so Perfetto shows WHY this compile happened, not just that
            span_args = labels if diff is None else dict(
                labels, key_diff=diff["summary"],
                nearest_signature=diff["nearest"])
            t_c0 = time.perf_counter()
            with self._stats.span("executor.compile",
                                  "executor.span.compile_ms",
                                  trace_args=span_args):
                new_state, fetches = self._run_step(step_fn, state,
                                                    feeds, rng)
            self._stats.count("executor.compiles")
            self._stats.observe("executor.compile_ms",
                                (time.perf_counter() - t_c0) * 1e3,
                                labels=labels)
        else:
            with self._stats.span("executor.execute",
                                  "executor.span.execute_ms"):
                new_state, fetches = self._run_step(step_fn, state,
                                                    feeds, rng)
        for n, v in new_state.items():
            scope.set(n, v)
        self._stats.count("executor.steps")
        guard = None
        if self._guard is not None:
            # the step appended its sentinel vector as an extra fetch;
            # guard_cell was filled (with the monitored-name order) at
            # trace time, so it is populated by now even on a fresh entry
            gvec, fetches = fetches[-1], fetches[:-1]
            if guard_cell:
                guard = (gvec, tuple(guard_cell), step_id)
        return fetches, guard

    def _run_step(self, step_fn, state, feeds, rng):
        """Invoke a compiled step on the device `place` names: whatever
        arrives uncommitted (the rng, a startup program's whole output)
        lands there. Under an active mesh the shardings already name
        the devices."""
        if getattr(self, "_active_mesh", None) is not None:
            return step_fn(state, feeds, rng)
        with jax.default_device(self._device):
            return step_fn(state, feeds, rng)

    # ------------------------------------------------------------------
    def _build(self, program, fetch_names, persist_names, state_sig):
        gb = program.global_block()
        marker_idx = None
        for i, op in enumerate(gb.ops):
            if op.type == BACKWARD_MARKER:
                marker_idx = i
                break
        is_test = program._is_test
        state_keys = set(state_sig)
        guard_cfg = self._guard
        # filled at trace time with the monitored-name order (one trace
        # per cache entry, so the cell and its step fn stay consistent)
        guard_cell = []

        # Pipeline parallelism: when PipelineOptimizer attached a config and
        # the active mesh has a pp axis, lower the forward section to the
        # SPMD scan schedule (parallel/pipeline.py) instead of the plain
        # op-by-op trace.
        pipelined_fwd = None
        pcfg = getattr(program, "_pipeline", None)
        mesh = getattr(self, "_active_mesh", None)
        if pcfg is not None and marker_idx is not None and mesh is not None \
                and "pp" in mesh.axis_names and mesh.shape["pp"] > 1:
            from ..parallel.pipeline import build_pipelined_forward
            ploss = gb.ops[marker_idx].attr("loss")
            # Forward intermediates live per-microbatch inside the scan;
            # only the loss, persistables, feeds, and grads are fetchable.
            data_names = {v.name for v in program.list_vars()
                          if getattr(v, "is_data", False)}
            bad_fetch = [f for f in fetch_names
                         if f != ploss and not f.endswith("@GRAD")
                         and f not in persist_names and f not in data_names]
            if bad_fetch:
                raise ValueError(
                    f"cannot fetch forward intermediates {bad_fetch} from a "
                    f"pipelined program — they exist only per-microbatch "
                    f"inside the pipeline scan; fetch the loss, params or "
                    f"gradients instead")
            pipelined_fwd = build_pipelined_forward(
                program, marker_idx, pcfg, mesh, ploss, is_test=is_test)

        if marker_idx is None:
            # dead-code-eliminate to the fetch set (+ persistable writers):
            # an inference/test run must not demand feeds its fetches don't
            # need (parity: fluid Executor prunes feed/fetch targets).
            run_ops = _slice_ops(gb, fetch_names)
        else:
            run_ops = gb.ops

        def step(state, feeds, rng):
            env = {}
            env.update(state)
            env.update(feeds)
            # rng arrives as (seed, step); derive the key in-graph
            env["@RNG@"] = jax.random.fold_in(
                jax.random.PRNGKey(rng[0]), rng[1])
            if marker_idx is None:
                for op in run_ops:
                    ops_registry.run_op(op, env, program, is_test)
            else:
                marker = gb.ops[marker_idx]
                loss_name = marker.attr("loss")
                param_names = [n for n in marker.attr("params") if n in env]
                base_env = {k: v for k, v in env.items() if k not in param_names}

                # Forward results that stay live past the backward: what
                # the optimizer section reads, what run() fetches, and the
                # persistables (e.g. batch-norm running stats written in
                # the forward). Everything else is returned nowhere, so a
                # remat policy is free to discard it — without this
                # pruning the aux dict would pin every intermediate as a
                # checkpoint output and jax.checkpoint could save nothing.
                post_reads = set()
                for op in gb.ops[marker_idx + 1:]:
                    post_reads.update(op.input_names)
                # "@RNG@" is an implicit read (OpContext.rng()), never in
                # input_names — optimizer-section ops like dpsgd need it
                keep_names = (set(fetch_names) | set(persist_names)
                              | set(post_reads) | {loss_name, "@RNG@"})

                if pipelined_fwd is not None:
                    feed_keys = set(feeds)

                    def fwd(params):
                        genv = {k: v for k, v in base_env.items()
                                if k not in feed_keys and k != "@RNG@"}
                        genv.update(params)
                        fd = {k: env[k] for k in feed_keys}
                        loss = pipelined_fwd(genv, fd, env["@RNG@"])
                        env2 = dict(base_env)
                        env2.update(params)
                        env2[loss_name] = loss
                        return loss, {k: v for k, v in env2.items()
                                      if k in keep_names}
                else:
                    def fwd(params):
                        env2 = dict(base_env)
                        env2.update(params)
                        for op in gb.ops[:marker_idx]:
                            ops_registry.run_op(op, env2, program, is_test)
                        loss = jnp.sum(env2[loss_name])
                        return loss, {k: v for k, v in env2.items()
                                      if k in keep_names}

                rcfg = getattr(program, "_recompute", None)
                if rcfg is not None:
                    # Remat: backward rebuilds the forward under the XLA
                    # policy instead of saving every intermediate
                    # (optimizer/recompute.py; HBM-for-FLOPs trade).
                    from ..optimizer.recompute import resolve_policy
                    fwd = jax.checkpoint(
                        fwd, policy=resolve_policy(rcfg["policy"]))

                params = {n: env[n] for n in param_names}
                (loss_val, env), grads = jax.value_and_grad(
                    fwd, has_aux=True)(params)
                del loss_val
                env = dict(env)
                for n in param_names:
                    env[grad_var_name(n)] = grads[n]
                for op in gb.ops[marker_idx + 1:]:
                    ops_registry.run_op(op, env, program, is_test)

            new_state = {n: env[n] for n in persist_names if n in env}
            fetches = tuple(env[f] for f in fetch_names)
            if guard_cfg is not None:
                # NaN/Inf sentinel folded INTO the step: one fused
                # isfinite reduction per monitored var (loss, grads,
                # float fetches), returned as a (n,)-bool extra fetch —
                # a device-side check, not a host scan of the arrays
                if marker_idx is not None:
                    marker = gb.ops[marker_idx]
                    g_loss = marker.attr("loss")
                    g_grads = [grad_var_name(n)
                               for n in marker.attr("params")]
                else:
                    g_loss, g_grads = None, []
                names, flags = [], []
                for n in guard_cfg.candidates(g_loss, g_grads,
                                              fetch_names):
                    v = env.get(n)
                    if v is None:
                        continue
                    v = jnp.asarray(v)
                    if not jnp.issubdtype(v.dtype, jnp.floating):
                        continue
                    names.append(n)
                    flags.append(jnp.all(jnp.isfinite(v)))
                guard_cell[:] = names
                gvec = jnp.stack(flags) if flags \
                    else jnp.zeros((0,), jnp.bool_)
                fetches = fetches + (gvec,)
            return new_state, fetches

        # Donate the state pytree: param/opt-state updates reuse HBM buffers,
        # matching fluid's in-place update semantics with zero copies.
        donate = (0,) if marker_idx is not None and state_keys else ()
        return jax.jit(step, donate_argnums=donate), guard_cell


# Convenience mirroring fluid.executor._run helpers -------------------------

def run_startup(startup_program=None, scope=None, place=None):
    from .framework import default_startup_program
    exe = Executor(place)
    exe.run(startup_program or default_startup_program(), scope=scope)
    return exe
