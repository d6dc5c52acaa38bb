"""`correct` of `brumby-14b-pp5.longgen-closed-16` comes out false when
the family's path is broken underneath.

Not pure, like `test_faults.py`: each case drives a whole run of the
cell at the files' rehearsal sizes on the CPU, in a process of its own
(some 20 s a case), and reads `correct` off the result line, as a
check of the cell does. Six faults are planted in the PROGRAM, one for
each fact of the layer that the family added: the gate taken off (no
decay), the power 1 in place of 2 (plain linear attention over the
same state), the normaliser dropped, the state not reset where a lane
starts a request, the rotary positions taken off, and q and k not
normed. Two are the limits' controls, planted in the REFERENCE: every
matrix rounded to float8_e4m3fn (the nearest precision below the
configuration's bfloat16), and each query's history cut to its last 8
tokens (the chip's control cuts it to 1,024). The rehearsal runs in
float32 over weights at 0.15 under contexts to 128 (the
configuration's `rehearsal` section), so a sound run reads about 0
against the limits set at full width and each fault crosses one.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "brumby-14b-pp5.longgen-closed-16"

SCRIPT = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from paddle_tpu.serving import blocks
from paddle_tpu.ops.pallas import retention
fault = {fault!r}


def variant(degree, normalised):
    # the rule token by token with another power or no normaliser: the
    # expansion of degree 1 is x itself, padded to the state's rows
    def expand(x, side):
        if degree == 2:
            return retention.expand(x, side)
        rows = retention.state_rows(x.shape[-1])
        pad = [(0, 0)] * (x.ndim - 1) + [(0, rows - x.shape[-1])]
        return jnp.pad(x.astype(jnp.float32), pad)

    def chunk(q, k, v, a, state, norm, counts, reset):
        s, c, h, d = q.shape
        kv = k.shape[2]
        st = jnp.where(reset[:, None, None, None], 0.0, state)
        z = jnp.where(reset[:, None, None], 0.0, norm)
        outs = []
        for t in range(c):
            live = (t < counts)
            pk = expand(k[:, t].astype(jnp.float32), "key")
            dec = jnp.exp(a[:, t])
            new_s = dec[..., None, None] * st + jnp.einsum(
                "skr,skv->skrv", pk, v[:, t].astype(jnp.float32))
            new_z = dec[..., None] * z + pk
            st = jnp.where(live[:, None, None, None], new_s, st)
            z = jnp.where(live[:, None, None], new_z, z)
            pq = expand(q[:, t].astype(jnp.float32).reshape(
                s, kv, h // kv, d), "query")
            num = jnp.einsum("skgr,skrv->skgv", pq, st)
            den = jnp.einsum("skgr,skr->skg", pq, z)[..., None]
            o = num / (den + retention.EPS) if normalised else num
            outs.append(o.reshape(s, h, d))
        busy = counts > 0
        return (jnp.stack(outs, 1),
                jnp.where(busy[:, None, None, None], st, state),
                jnp.where(busy[:, None, None], z, norm))
    return chunk


if fault == "gate_off":
    whole = blocks.power_retention_chunk
    blocks.power_retention_chunk = lambda q, k, v, a, *rest: whole(
        q, k, v, jnp.zeros_like(a), *rest)
elif fault == "power_one":
    blocks.power_retention_chunk = variant(1, True)
elif fault == "normaliser_dropped":
    blocks.power_retention_chunk = variant(2, False)
elif fault == "state_not_reset":
    layer = blocks.ATTENTIONS["power"]
    blocks.ATTENTIONS["power"] = lambda ctx, *a: layer(
        ctx._replace(starts=jnp.zeros_like(ctx.starts)), *a)
elif fault == "rotary_off":
    blocks.rotate_half = lambda x, cos, sin: x
elif fault == "qk_norm_off":
    blocks.qk_rms_norm = lambda q, k, lp, eps: (q.astype(jnp.float32),
                                                k.astype(jnp.float32))
elif fault.startswith("reference_"):
    from benchmark.reference import brumby as ref
    plain = ref.forward_logprobs
    if fault == "reference_in_float8":
        class Rounded(dict):
            # a parameter tree whose matrices are rounded to float8 as
            # the reference reads them, an entry (a layer) at a time
            def __getitem__(self, name):
                return jax.tree_util.tree_map(
                    lambda a: a if a.ndim < 2 else a.astype(
                        jnp.float8_e4m3fn).astype(a.dtype),
                    dict.__getitem__(self, name))
        ref.forward_logprobs = lambda params, *a, **kw: plain(
            Rounded(params), *a, **kw)
    else:
        ref.forward_logprobs = lambda *a, **kw: plain(*a, history=8, **kw)
from benchmark import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", "4200700001",
                   "--seconds", "2", "--trace", "0",
                   "--rehearse-on-cpu"]))
"""


def _rehearse(fault):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-pp5.json")) as f:
        jax_env = json.load(f).get("jax_env", {})
    env = {**os.environ, **jax_env, "JAX_PLATFORMS": "cpu",
           "PADDLE_TPU_FORCE_FLASH": "1"}
    done = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(root=ROOT, fault=fault, cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


GAPS = ("token_logp_gap_nats", "mean_logp_gap_nats_per_token",
        "token_regret_gap_nats")


def _over(result):
    return [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]


def test_a_sound_run_is_correct_and_reads_zero():
    result = _rehearse("none")
    assert result["rehearsal"] is True and result["attempted"] > 0
    assert result["correct"] is True and not _over(result), \
        result["compared"]
    for gap in GAPS:
        assert result["compared"][gap]["value"] < 1e-4, result["compared"]


@pytest.mark.parametrize("fault", [
    "gate_off", "power_one", "normaliser_dropped", "state_not_reset",
    "rotary_off", "qk_norm_off", "reference_in_float8",
    "reference_cut_history"])
def test_a_fault_comes_out_not_correct(fault):
    result = _rehearse(fault)
    assert result["attempted"] > 0 and result["failed"] == 0
    # by the comparison with the reference, not by a request that broke
    assert result["correct"] is False, result["compared"]
    assert set(_over(result)) & set(GAPS), result["compared"]
