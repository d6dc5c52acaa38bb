"""chip_smoke.py from the outside: the rehearsal flag drives every
phase on CPU (tiny model, kernels interpreted) and says so; the default
invocation refuses to run without a TPU and prints no result line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)      # the suite's 8-device mesh flag
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          cwd=str(tmp_path), capture_output=True,
                          text=True, timeout=600)


def test_rehearsal_runs_every_phase_on_cpu_and_says_so(tmp_path):
    r = _run(tmp_path, "--rehearse-on-cpu")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "REHEARSAL on cpu" in r.stdout
    assert "platform: cpu" in r.stdout
    for phase in ("train:", "serve:", "check:"):
        assert f"chip_smoke: {phase}" in r.stdout, r.stdout
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 1}}
    # run from elsewhere, cache placed from outside: nothing is left
    # behind in the working directory but the cache we named
    assert sorted(os.listdir(tmp_path)) == ["cache"]


def test_default_invocation_fails_without_a_tpu(tmp_path):
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
