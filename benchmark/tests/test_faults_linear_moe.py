"""`correct` of `solar-open2-250b-ep16.long8k-closed-16` comes out false
when the new family's path is broken underneath.

Not pure, like `test_faults.py`: each case drives a whole run of the
cell at the files' rehearsal sizes on the CPU, in a process of its own
(some 20 s a case), and reads `correct` off the result line, as the
driver does. Three faults are planted in the PROGRAM, in what PR 36
added: the step size drops its factor 2 (`kda_allow_neg_eigval`), the
short convolution forgets the rows it carries over a chunk boundary,
or a lane keeps a retired request's state (the reset inside the step
does not happen). The fourth is the limits' own control, planted in
the REFERENCE: it reads every matrix rounded to float8_e4m3fn, the
nearest precision below the configuration's bfloat16 (at full width on
the chip: `PERF.md` section 6). The rehearsal runs in float32 over
weights at 0.15 (the configuration's `rehearsal` section says why), so
a sound run reads 0.0 against the limits that were set at full width
and each fault crosses one.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "solar-open2-250b-ep16.long8k-closed-16"

DRIVER = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from paddle_tpu.serving import blocks
fault = {fault!r}
if fault == "beta_without_its_factor_2":
    whole = blocks.kda_chunk
    def altered(q, k, v, g, beta, *a, **kw):
        return whole(q, k, v, g, beta * 0.5, *a, **kw)
    blocks.kda_chunk = altered
elif fault == "carried_rows_skipped":
    whole = blocks.short_conv
    def altered(z, carried, taps, counts):
        return whole(z, carried * 0, taps, counts)
    blocks.short_conv = altered
elif fault == "retired_state_kept":
    whole = blocks.kda_chunk
    def altered(q, k, v, g, beta, state, counts, reset):
        return whole(q, k, v, g, beta, state, counts,
                     jnp.zeros_like(reset))
    blocks.kda_chunk = altered
elif fault == "reference_in_float8":
    from benchmark.reference import solar_open2 as ref
    class Rounded(dict):
        # a parameter tree whose matrices are rounded to float8 as the
        # reference reads them, an entry (a layer) at a time
        def __getitem__(self, name):
            return jax.tree_util.tree_map(
                lambda a: a if a.ndim < 2 else a.astype(
                    jnp.float8_e4m3fn).astype(a.dtype),
                dict.__getitem__(self, name))
    plain = ref.forward_logprobs
    ref.forward_logprobs = lambda params, *a, **kw: plain(
        Rounded(params), *a, **kw)
from benchmark import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", "3600700001",
                   "--seconds", "2", "--trace", "0",
                   "--rehearse-on-cpu"]))
"""


def _rehearse(fault):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b-ep16.json")) as f:
        jax_env = json.load(f).get("jax_env", {})
    env = {**os.environ, **jax_env, "JAX_PLATFORMS": "cpu",
           "PADDLE_TPU_FORCE_FLASH": "1"}
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(root=ROOT, fault=fault, cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


GAPS = ("token_logp_gap_nats", "mean_logp_gap_nats_per_token",
        "token_regret_gap_nats")


def _over(result):
    return [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]


def test_a_sound_run_is_correct_and_reads_zero():
    result = _rehearse(None)
    assert result["rehearsal"] is True and result["attempted"] > 0
    assert result["correct"] is True and not _over(result), \
        result["compared"]
    for gap in GAPS:
        assert result["compared"][gap]["value"] < 1e-4, result["compared"]


@pytest.mark.parametrize("fault", ["beta_without_its_factor_2",
                                   "carried_rows_skipped",
                                   "retired_state_kept",
                                   "reference_in_float8"])
def test_a_fault_comes_out_not_correct(fault):
    result = _rehearse(fault)
    assert result["attempted"] > 0 and result["failed"] == 0
    # by the comparison with the reference, not by a request that broke
    assert result["correct"] is False, result["compared"]
    assert set(_over(result)) & set(GAPS), result["compared"]
