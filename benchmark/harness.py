"""What every runner shares: the device check, the compile-cache rule,
the guard against compilation inside the window, the traced sub-window,
and the `Run` record that per-layer readers read from."""

import importlib
import json
import os
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg):
    print(f"benchmark: {msg}", flush=True)


def load_traffic(name, rehearsal=False):
    """The parameters of the traffic mix `benchmark/traffic/<name>.json`;
    a rehearsal takes the file's `rehearsal` overrides (tiny lengths for
    the CPU) on top."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        params = json.load(f)
    over = params.pop("rehearsal", {})
    if rehearsal:
        params.update(over)
    return params


def by_name(kind, name):
    """The module `benchmark/<kind>/<name>.py`: a runner, a generator, a
    model family or a plain reference, found by the name a data file
    gives."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def claim_devices(chips, rehearsal, t_start):
    """Fail unless JAX holds `chips` TPU chips (or, in a rehearsal, the
    CPU). Returns (devices, compile cache directory)."""
    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    backend = jax.default_backend()
    devs = jax.devices()
    log(f"jax is up ({time.perf_counter() - t_start:.1f}s)")
    want = "cpu" if rehearsal else "tpu"
    if backend != want:
        raise NoAccelerator(
            f"jax.default_backend() is {backend!r}, this run needs "
            f"{want!r}")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chip(s), JAX has {len(devs)}")
    cache_dir = enable_compile_cache()
    log(f"platform: {devs[0].platform}  device_kind: "
        f"{devs[0].device_kind}  count: {len(devs)}  compile cache: "
        f"{cache_dir}")
    return devs[:chips], cache_dir


class CompileCounter:
    """Counts what XLA compiles (or loads from the persistent cache) in
    this process, with the host time of each, through JAX's own
    monitoring events. `inside(t0, t1)` is the guard: it must be 0 for
    the measured window. `cache_misses` counts the programs the
    persistent cache did not hold: above 0, this run compiled (its
    cache was cold)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.stamps = []
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == self.MISS:
            with self._lock:
                self.cache_misses += 1

    def _on(self, event, duration_secs, **_kw):
        if event == self.EVENT:
            with self._lock:
                self.stamps.append(time.perf_counter())

    def total(self):
        with self._lock:
            return len(self.stamps)

    def inside(self, t0, t1):
        with self._lock:
            return sum(1 for t in self.stamps if t0 <= t <= t1)


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the backend reports it
    (0 where it reports nothing, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class TracedWindow:
    """The profiler and the program's span recorder, on for the first
    `seconds` of the measured window of a `--trace 1` run. The trace is
    written under `<checkout>/chiprun_out/traces/` (git-ignored), never
    into the tree proper."""

    def __init__(self, cell, seed, chips):
        self.dir = os.path.join(ROOT, "chiprun_out", "traces",
                                f"{cell}.seed{seed}")
        self.chips = chips
        self.t0 = self.t1 = None
        self.spans = []
        self._device = self._parsed = None

    def start(self):
        import jax
        from paddle_tpu.observability.tracing import get_recorder
        os.makedirs(self.dir, exist_ok=True)
        # no Python-function tracing: it hooks every call of the serving
        # loop, slows the host it is there to observe, and bloats the file
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        get_recorder().start()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        from paddle_tpu.observability.tracing import get_recorder
        self.t1 = time.perf_counter()
        rec = get_recorder()
        rec.stop()
        self.spans = rec.events()
        rec.clear()
        jax.profiler.stop_trace()

    @property
    def device(self):
        """The device side of the trace, parsed on first use (after the
        window: parsing holds the interpreter for seconds)."""
        if not self._parsed and self.t1 is not None:
            from benchmark import trace
            planes = trace.read_device_lines(
                trace.find_xplane(self.dir), self.chips)
            self._device = trace.DeviceTrace(planes) if planes else None
            self._parsed = True
        return self._device


class Run:
    """What a runner hands back: the end-to-end values, the verdicts,
    and everything a per-layer reader may read. A reader takes what it
    needs and returns None where that is missing."""

    def __init__(self, ctx):
        self.ctx = ctx                  # cell, config, traffic, seed...
        self.e2e = {}                   # name -> value
        self.attempted = 0
        self.failed = 0
        self.correct = False
        self.t0 = self.t1 = None        # the measured window, host clock
        self.traced = None              # TracedWindow of a --trace 1 run
        self.samples = {}               # name -> [values] the runner took
        self.requests = []              # serving: the runner's request log
        self.facts = {}                 # shapes and counts of the run
        self.compared = {}              # name -> {"value", "limit"}
        self.memory_peak_bytes = 0

    def check(self, ok, msg):
        """Log one condition of `correct`; all must hold."""
        log(("ok   " if ok else "FAIL ") + msg)
        return bool(ok)

    def within(self, name, value, limit, what):
        """One NUMBER of `correct` against its limit (holds where value
        <= limit); `run.py` prints every such pair last, on standard
        error and in the result line, so that a run that is not correct
        says by how much."""
        self.compared[name] = {"value": float(value),
                               "limit": float(limit)}
        return self.check(value <= limit,
                          f"{what}: {value:.6g} (limit {limit})")
