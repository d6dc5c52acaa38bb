"""The fused step's guarded sampled tail on REAL TPU hardware, at the
`gpt2-xl` cell's sizes (16 lanes, 1600 wide, 50,257 ids, bf16 operands):
the branch under `lax.cond(do_sample.any())` gives, on the chip, bit for
bit what the unguarded arithmetic gives, for all-greedy, all-sampled and
mixed lanes, over the last column (16 columns a lane) and per column
(the speculative servers' tail, 4 columns). No benchmark cell sends
sampled traffic, so this is where the chip runs the sampled branch. The
reference and the comparison are the CPU tier's
(`tests/api/test_sampled_tail_guard.py`), loaded from its file.
"""

import importlib.util
import os

import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def cpu_tier():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests", "api",
        "test_sampled_tail_guard.py")
    spec = importlib.util.spec_from_file_location("sampled_tail_guard",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("per_column,columns", [(False, 16), (True, 4)],
                         ids=["last_column", "per_column"])
def test_guarded_tail_bitwise_at_the_cells_sizes(cpu_tier, per_column,
                                                 columns):
    import jax.numpy as jnp
    tails = cpu_tier.both_tails(per_column)     # two compiles a tail
    for lanes in ("greedy", "sampled", "mixed"):
        operands, ctl = cpu_tier._tail_inputs(
            lanes, True, True, per_column,
            dims=(16, columns, 1600, 50257), dtype=jnp.bfloat16)
        cpu_tier.check_guarded_tail_bitwise(per_column, lanes, operands,
                                            ctl, tails=tails)
