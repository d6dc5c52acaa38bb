"""CPU smoke of tools/tune_flash.py — the FULL tuner code path.

The tuner once failed on hardware in a path no test had run
end-to-end and left an empty artifact. This test runs the tuner main()
as a subprocess — argparse, device init (cpu-pinned), the fwd AND
--backward sweep, winner selection, and the persist gate — on
interpreter-sized shapes so the path cannot crash only on hardware.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TUNER = os.path.join(REPO, "tools", "tune_flash.py")


def _run_tuner(tmp_path, *extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # persist gate check: even if the gate broke, the write must land in
    # tmp, never in the repo's perf/flash_tuned.json
    env["PADDLE_TPU_FLASH_TUNED_FILE"] = str(tmp_path / "tuned.json")
    return subprocess.run(
        [sys.executable, TUNER, "--seq", "64", "--batch", "1",
         "--heads", "2", "--dim", "16", "--blocks", "32", "--steps", "1",
         "--dtype", "float32", *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)


def test_tuner_backward_full_path(tmp_path):
    """The full configuration (--backward) end-to-end on cpu."""
    r = _run_tuner(tmp_path, "--backward")
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "best: " in r.stdout, (r.stdout, r.stderr)
    assert "ms/step" in r.stdout
    # cpu runs must NOT persist tuned blocks (they'd steer TPU defaults)
    assert not os.path.exists(tmp_path / "tuned.json"), \
        "cpu tuner run persisted block sizes"


def test_tuner_failure_writes_structured_record(tmp_path):
    """When no config can run, stdout carries a parseable failure record
    — never a 0-byte artifact."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_FLASH_TUNED_FILE"] = str(tmp_path / "tuned.json")
    # every swept block exceeds seq -> the sweep is empty
    r = subprocess.run(
        [sys.executable, TUNER, "--seq", "32", "--blocks", "64",
         "--dtype", "float32"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert r.returncode == 1
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["failed"] is True and "error" in rec, rec
