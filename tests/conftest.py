"""Test config: force a virtual 8-device CPU mesh before jax initializes.

Mirrors SURVEY.md §4 — parallel tests run on
xla_force_host_platform_device_count=8 CPU devices; TPU perf is
benchmark/run.py's job, correctness is this suite's job.
"""

import os

# The suite's job is correctness on the virtual 8-device CPU mesh; the
# chip belongs to chip_smoke.py and tests_tpu/.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np
import pytest

import jax

# Numeric tests compare against fp64/numpy goldens; force fp32 matmuls
# (production path uses bf16 on the MXU — tests_tpu/ and the benchmark's
# `correct` hold that path to its tolerance on the chip).
jax.config.update("jax_default_matmul_precision", "highest")


# ---------------------------------------------------------------------------
# Suite tiering (VERDICT r4 #6): tests whose RECORDED duration exceeds
# the threshold are auto-marked `slow`, so the inner loop runs
# `pytest tests/ -m "not slow"` in minutes while plain `pytest tests/`
# (CI/judging) still runs everything. The record is committed at
# tests/.durations.json; regenerate after big suite changes with
#   PT_WRITE_DURATIONS=1 python -m pytest tests/ -q
# Unrecorded (new) tests default to the fast tier.
# ---------------------------------------------------------------------------

_DURATIONS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               ".durations.json")
_SLOW_THRESHOLD_S = float(os.environ.get("PT_SLOW_THRESHOLD_S", 3.0))
_observed_durations = {}


def pytest_collection_modifyitems(config, items):
    import json
    try:
        with open(_DURATIONS_PATH) as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        return
    slow = pytest.mark.slow
    for item in items:
        if recorded.get(item.nodeid, 0.0) >= _SLOW_THRESHOLD_S:
            item.add_marker(slow)


def pytest_runtest_logreport(report):
    # sum setup+call+teardown: module fixtures (training/compile setup)
    # charge their cost to setup, and a test is only "fast" if its
    # WHOLE cost is small
    if os.environ.get("PT_WRITE_DURATIONS"):
        total = _observed_durations.get(report.nodeid, 0.0)
        _observed_durations[report.nodeid] = round(
            total + report.duration, 3)


def pytest_sessionfinish(session, exitstatus):
    if not (os.environ.get("PT_WRITE_DURATIONS") and _observed_durations):
        return
    import json
    # deselected runs (-k/-m/path args) would drop every other test's
    # record; merge instead of overwrite
    try:
        with open(_DURATIONS_PATH) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        merged = {}
    merged.update(_observed_durations)
    with open(_DURATIONS_PATH, "w") as f:
        json.dump(dict(sorted(merged.items())), f, indent=0)


@pytest.fixture
def tp_subprocess():
    """Run a python snippet in a FRESH process pinned to an N-device
    CPU topology (`XLA_FLAGS=--xla_force_host_platform_device_count=N`,
    `JAX_PLATFORMS=cpu`) — the documented multi-device serving recipe
    (docs/serving.md "Serving on a mesh"). The in-session suite already
    runs on the 8-device mesh this conftest forces above; this fixture
    exists so `tp`-marked tests can prove the standalone recipe works
    WITHOUT re-initializing (and so poisoning) the current session's
    jax backend. Returns run(code, devices=2, timeout=300) ->
    CompletedProcess."""
    import subprocess
    import sys

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))

    def run(code, devices=2, timeout=300):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # appended, not overwritten: the session's other XLA flags
        # survive, and XLA's last-occurrence-wins parsing still pins
        # OUR device count (the fixture's whole point)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count="
                            f"{int(devices)}").strip()
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        return subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=repo_root)

    return run


@pytest.fixture
def proc_fleet():
    """Bounded-lifetime guard for `proc`-marked tests (subprocess
    replica backend): on teardown, every worker process spawned
    through serving/remote.py that is STILL alive is SIGKILLed and
    reaped. A test that closes its fleet cleanly leaves nothing for
    the sweep; a test that failed mid-storm cannot leak engines into
    the rest of the suite (each worker holds a full jitted
    GenerationServer — a leak is ~a core and ~a GiB, and a stuck one
    would hang the session at exit). Yields remote.live_workers for
    assertions."""
    import signal
    import time as _time
    from paddle_tpu.serving import remote

    yield remote.live_workers
    leaked = remote.live_workers()
    for p in leaked:
        try:
            p.send_signal(signal.SIGKILL)
        except OSError:
            pass
    deadline = _time.monotonic() + 10.0
    for p in leaked:
        while p.poll() is None and _time.monotonic() < deadline:
            _time.sleep(0.05)


@pytest.fixture
def bert_classifier_export(tmp_path):
    """(model_dir, infer_feed, ref_probs): ONE copy of the shared
    save_inference_model + reference-forward recipe (tiny BERT
    classifier, dropout-off reference) used by the tp-predictor and
    batching-server serving tests."""
    import numpy as _np
    import paddle_tpu as fluid
    from paddle_tpu.core import framework as _fw
    from paddle_tpu.models import bert as _bert

    cfg = _bert.bert_tiny()
    main, startup = _fw.Program(), _fw.Program()
    with _fw.program_guard(main, startup):
        _feeds, _loss, _acc, probs = _bert.build_classifier_net(
            cfg, seq_len=32, num_labels=3)
    exe = fluid.Executor()
    scope = fluid.Scope()
    full = _bert.make_pretrain_feed(cfg, 32, 4)
    # the inference inputs: what the classifier FORWARD reads (label
    # only feeds the loss/acc heads, pruned at save time)
    infer_names = ["input_mask", "sent_ids", "src_ids"]
    infer_feed = {k: full[k] for k in infer_names}
    ref_feed = dict(infer_feed, label=_np.zeros((4, 1), _np.int64))
    test_prog = main.clone(for_test=True)   # dropout off, like serving
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(
            str(tmp_path / "m"), infer_names, [probs], exe,
            main_program=main)
        ref_out = _np.asarray(exe.run(test_prog, feed=ref_feed,
                                      fetch_list=[probs])[0])
    return str(tmp_path / "m"), infer_feed, ref_out


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope (fluid tests reset
    similarly via new Program/Scope per unit test)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework, executor, unique_name
    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_scope = executor._global_scope
    executor._global_scope = executor.Scope()
    unique_name.switch()
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    executor._global_scope = old_scope
