"""`correct` of `smallthinker-21b-a3b.mixed16k-closed-16` comes out
false when the family's path is broken underneath.

Not pure, like `test_faults.py`: each case drives a whole run of the
cell at the files' rehearsal sizes on the CPU, in a process of its own
(some 20 s a case), and reads `correct` off the result line, as a
check of the cell does. Six faults are planted in the PROGRAM, one for each fact
of the layer that the family added: the window layers' walk drops its
window, the full layers turn q and k as the window layers do, the
window layers turn them in the interleaved convention, the router
scores by sigmoid, the experts gate by SiLU, and the router reads the
post-attention norm. Two are the limits' controls, planted in the
REFERENCE: every matrix rounded to float8_e4m3fn (the nearest
precision below the configuration's bfloat16), and the window taken
off. The rehearsal runs in float32 over weights at 0.15 with a window
of 32 under contexts to 128 (the configuration's `rehearsal` section),
so a sound run reads about 0 against the limits set at full width and
each fault crosses one.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "smallthinker-21b-a3b.mixed16k-closed-16"

SCRIPT = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from paddle_tpu.serving import blocks, moe
from paddle_tpu.serving.smallthinker import SmallThinkerServingModel
from paddle_tpu.ops.pallas import moe as moe_kernel
fault = {fault!r}


def with_dims(**dims):
    spec = SmallThinkerServingModel.step_spec
    SmallThinkerServingModel.step_spec = lambda self: spec(self)._replace(
        dims={{**spec(self).dims, **dims}})


if fault == "window_dropped":
    walk = blocks.paged_attention
    blocks.paged_attention = lambda *a, window=None, **kw: walk(
        *a, window=None if window is None else 1 << 30, **kw)
elif fault == "rotary_on_the_nope_layers":
    full = blocks.ATTENTIONS["gqa"]

    def altered(ctx, hn, lp, pools):
        cos, sin = (a[:, :, None] for a in ctx.angles)
        fuse, walk = blocks.fuse_kv, blocks.paged_attention
        blocks.fuse_kv = lambda k, v: fuse(blocks.rotate_half(k, cos, sin),
                                           v)
        blocks.paged_attention = lambda q, *a, **kw: walk(
            blocks.rotate_half(q.transpose(0, 2, 1, 3), cos, sin
                               ).transpose(0, 2, 1, 3), *a, **kw)
        try:
            return full(ctx, hn, lp, pools)
        finally:
            blocks.fuse_kv, blocks.paged_attention = fuse, walk
    blocks.ATTENTIONS["gqa"] = altered
elif fault == "interleaved_rotary":
    blocks.rotate_half = blocks.rotate_interleaved
elif fault == "sigmoid_routing":
    whole = moe.route
    moe.route = lambda h, w, b, k, scaling, normalize=True, scoring=None: \\
        whole(h, w, jnp.zeros((w.shape[1],)), k, scaling, normalize,
              "sigmoid")
elif fault == "silu_experts":
    moe_kernel.ACTIVATIONS["relu"] = jax.nn.silu
elif fault == "router_after_attention":
    with_dims(router_input="mlp")
elif fault.startswith("reference_"):
    from benchmark.reference import smallthinker as ref
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
        import copy

        def unwindowed(params, cfg, *a, **kw):
            cfg = copy.copy(cfg)
            cfg.sliding_window = 1 << 30
            return plain(params, cfg, *a, **kw)
        ref.forward_logprobs = unwindowed
from benchmark import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", "4000700001",
                   "--seconds", "2", "--trace", "0",
                   "--rehearse-on-cpu"]))
"""


def _rehearse(fault):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
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
    "window_dropped", "rotary_on_the_nope_layers", "interleaved_rotary",
    "sigmoid_routing", "silu_experts", "router_after_attention",
    "reference_in_float8", "reference_without_window"])
def test_a_fault_comes_out_not_correct(fault):
    result = _rehearse(fault)
    assert result["attempted"] > 0 and result["failed"] == 0
    # by the comparison with the reference, not by a request that broke
    assert result["correct"] is False, result["compared"]
    assert set(_over(result)) & set(GAPS), result["compared"]
