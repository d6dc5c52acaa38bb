"""`correct` comes out false when the timed path is broken underneath.

Not pure: each case drives a whole run of `gpt2-xl.closed-16` at the
files' rehearsal sizes on the CPU (the harness's look for a chip
skipped, the rest of a run as it is), in a process of its own, some
20 s a case. The fault is planted in the PROGRAM, where a token is
produced: `GenerationServer._fetch_outputs` hands lane 0 another token
than the step chose, so the engine streams it, feeds it back and
returns it. The benchmark's own comparison with the plain reference has
to see it.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DRIVER = """
import sys
import numpy as np
sys.path.insert(0, {root!r})
from paddle_tpu.serving import engine
if {broken!r}:
    whole = engine.GenerationServer._fetch_outputs
    def altered(self, out, plan):
        nxt, logps, fed, rows = whole(self, out, plan)
        nxt = np.array(nxt)
        nxt[0] = (nxt[0] + 1) % self._vocab     # lane 0: another token
        return nxt, logps, fed, rows
    engine.GenerationServer._fetch_outputs = altered
from benchmark import run
sys.exit(run.main(["--workload", "gpt2-xl.closed-16", "--seed",
                   "3300700001", "--seconds", "2", "--trace", "0",
                   "--rehearse-on-cpu"]))
"""


def _rehearse(broken):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gpt2-xl.json")) as f:
        jax_env = json.load(f).get("jax_env", {})
    env = {**os.environ, **jax_env, "JAX_PLATFORMS": "cpu",
           "PADDLE_TPU_FORCE_FLASH": "1"}
    done = subprocess.run(
        [sys.executable, "-c", DRIVER.format(root=ROOT, broken=broken)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("broken", [False, True],
                         ids=["as_it_is", "a_token_altered"])
def test_correct_sees_an_altered_token(broken):
    result, stderr = _rehearse(broken)
    assert result["rehearsal"] is True and result["attempted"] > 0
    compared = result["compared"]
    assert list(result)[-1] == "compared"
    # the same pairs are the last lines of standard error
    tail = stderr.strip().splitlines()[-len(compared):]
    assert [line.split()[1] for line in tail] == list(compared)
    over = [k for k, v in compared.items() if v["value"] > v["limit"]]
    if broken:
        assert result["correct"] is False
        assert "token_regret_gap_nats" in over, compared
    else:
        assert result["correct"] is True and not over, compared
