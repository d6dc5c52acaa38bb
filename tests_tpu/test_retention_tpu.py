"""The power-retention kernel on REAL TPU hardware, compiled by Mosaic
at `brumby-14b-pp5`'s geometry (16 lanes x 16 columns, 40 query heads
on 8 KV heads of 128, a float32 state of 8,320 rows a KV head) and
compared, on the same chip, with the kernel's `jax.numpy` form and with
the rule token by token; lanes with no valid column keep their state
bitwise, whether a busy lane lies before them, only after them, or
nowhere in the call. The CPU tier (`tests/api/test_brumby_serving.py`)
pins the same kernel under the interpreter at a tiny size.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _case(dtype, seed, s=16, c=16, h=40, kv=8, d=128):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import retention
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def inputs(i):
        # q at 1 / sqrt(d) of a normed head, k normed: q . k ~ N(0, 1);
        # log-gates from a head that barely forgets to one that halves
        # in a token
        q = unit(jax.random.normal(ks[i], (s, c, h, d)))
        k = unit(jax.random.normal(ks[i + 1], (s, c, kv, d))) * d ** 0.5
        v = jax.random.normal(ks[i + 2], (s, c, kv, d))
        a = -jnp.exp(jax.random.uniform(ks[i + 3], (s, c, kv),
                                        minval=np.log(4e-5),
                                        maxval=np.log(0.7)))
        return q.astype(dtype), k.astype(dtype), v.astype(dtype), a

    rows = retention.state_rows(d)
    zero = (jnp.zeros((s, kv, rows, d), jnp.float32),
            jnp.zeros((s, kv, rows), jnp.float32))
    # a carried state: one full chunk from zero
    _o, state, norm = jax.jit(retention.power_retention_chunk_reference)(
        *inputs(0), *zero, jnp.full((s,), c, jnp.int32),
        jnp.ones((s,), bool))
    return inputs(4), state, norm


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 1e-2)])
@pytest.mark.parametrize("counts", [
    # prefixes 0, 1, C-1, C; idle lanes first, between and last
    [0, 0, 16, 15, 1, 0, 7, 16, 1, 1, 0, 3, 16, 1, 0, 0],
    # one busy lane, at the end: every idle lane precedes it
    [0] * 15 + [5],
    # no busy lane at all
    [0] * 16], ids=["mixed", "last_only", "all_idle"])
def test_power_retention_matches_its_jnp_form_and_the_rule_on_the_chip(
        dtype, tol, counts):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import retention
    (q, k, v, a), state, norm = _case(getattr(jnp, dtype), 0)
    counts = jnp.asarray(counts, jnp.int32)
    reset = jnp.asarray([0, 1, 0, 0, 1, 0, 1, 0] * 2, bool) & (counts > 0)
    args = (q, k, v, a, state, norm, counts, reset)
    got = jax.jit(retention.power_retention_chunk)(*args)
    live = (np.arange(16)[None] < np.asarray(counts)[:, None])[
        ..., None, None]
    assert np.isfinite(np.asarray(got[0])).all()
    jnp_form = jax.jit(retention.power_retention_chunk_reference)(*args)
    rule = jax.jit(retention.power_retention_recurrence)(*args)

    def close(g, w, where=True):
        # errors against each output's scale: a state element is a sum
        # of terms of either sign, each rounded once in bf16
        g, w = np.asarray(g), np.asarray(w)
        err = np.where(where, np.abs(g - w), 0.0)
        return err.max() <= tol * np.abs(w).max()

    # the kernel against its jnp form: every output
    assert close(np.where(live, got[0], 0), np.where(live, jnp_form[0], 0))
    for i in (1, 2):
        assert close(got[i], jnp_form[i])
    # against the rule: the state and the normaliser everywhere; an
    # output row where the two float32 forms agree within half the
    # tolerance (at least 99% of the live rows). A row whose weights
    # sum to about EPS (a query almost orthogonal to the one key a
    # reset lane has seen) is a ratio of two tiny numbers, and the
    # rule's sum over the expansion's 8,256 products rounds its
    # denominator by more than its size; such rows are few
    for i in (1, 2):
        assert close(got[i], rule[i])
    o_j, o_r = np.asarray(jnp_form[0]), np.asarray(rule[0])
    agree = (np.abs(o_j - o_r).max(-1, keepdims=True)
             <= tol / 2 * np.abs(o_r).max()) & live
    assert agree.sum() >= 0.99 * np.broadcast_to(live, agree.shape).sum()
    assert close(got[0], o_r, agree)
    idle = np.asarray(counts) == 0
    for i, before in ((1, state), (2, norm)):
        assert np.array_equal(np.asarray(got[i])[idle],
                              np.asarray(before)[idle])
