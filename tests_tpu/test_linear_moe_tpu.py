"""The chunked delta rule and the held experts' kernel in its sliced
form on REAL TPU hardware, compiled by Mosaic at
`solar-open2-250b-ep16`'s geometry (16 lanes x 16 columns, 64 heads of
128 x 128, float32 state; 20 experts of 4096 x 1280 over the 256
columns of a step) and compared, on the same chip, with the kernel's
`jax.numpy` form, with the rule token by token, and with the experts
computed one by one. The CPU tier
(`tests/api/test_linear_moe_serving.py`) pins the same kernels under
the interpreter at a tiny size.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _kda_case(dtype, seed, s=16, c=16, h=64, d=128):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (s, c, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (s, c, h, d)))
    v = jax.random.normal(ks[2], (s, c, h, d))
    # decays from a channel that barely forgets to one that forgets at
    # once, as the configuration's initialisers give them
    g = -jnp.exp(jax.random.uniform(ks[3], (s, c, h, d),
                                    minval=np.log(1e-3),
                                    maxval=np.log(2.4)))
    beta = jax.random.uniform(ks[4], (s, c, h), minval=0.0, maxval=2.0)
    state = jax.random.normal(ks[5], (s, h, d, d)) * 0.1
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-3),
                                       ("bfloat16", 0.03)])
def test_kda_chunk_matches_its_jnp_form_and_the_rule_on_the_chip(dtype,
                                                                 tol):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import linear
    args = _kda_case(getattr(jnp, dtype), 0)
    # prefixes 0, 1, C-1, C; decode lanes; lanes that start a request
    counts = jnp.asarray([16, 15, 1, 0, 7, 16, 1, 1, 16, 3, 0, 16, 16,
                          1, 9, 16], jnp.int32)
    reset = jnp.asarray([0, 1, 0, 0, 1, 0, 1, 0] * 2, bool)
    o, new = jax.jit(linear.kda_chunk)(*args, counts, reset)
    o_ref, new_ref = jax.jit(linear.kda_chunk_reference)(*args, counts,
                                                         reset)
    o_tok, new_tok = jax.jit(linear.kda_recurrence)(*args, counts, reset)
    live = (np.arange(16)[None] < np.asarray(counts)[:, None])[
        ..., None, None]
    assert np.isfinite(np.asarray(o)).all()
    for want_o, want_s in ((o_ref, new_ref), (o_tok, new_tok)):
        np.testing.assert_allclose(np.where(live, o, 0),
                                   np.where(live, want_o, 0), atol=tol)
        np.testing.assert_allclose(new, want_s, atol=tol)
    idle = np.asarray(counts) == 0
    assert np.array_equal(np.asarray(new)[idle],
                          np.asarray(args[5])[idle])


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-3),
                                       ("bfloat16", 0.08)])
def test_sliced_held_experts_match_one_by_one_on_the_chip(dtype, tol):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import moe
    dt = getattr(jnp, dtype)
    t, hid, inner, e = 256, 4096, 1280, 20
    # float32 weights are twice the bytes: both types take slices
    assert moe._inner_blocks(hid, inner, dt) >= 2
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (t, hid)).astype(dt)
    gu = (jax.random.normal(ks[1], (e, hid, 2 * inner)) * 0.02).astype(dt)
    down = (jax.random.normal(ks[2], (e, inner, hid)) * 0.02).astype(dt)
    comb = jax.random.uniform(ks[3], (t, e), minval=0.1, maxval=1.0)
    sel = jax.random.uniform(ks[4], (t, e)) < 0.03
    got = np.asarray(moe.moe_experts(x, sel, comb, gu, down))
    want = jnp.zeros((t, hid), jnp.float32)
    x32 = x.astype(jnp.float32)
    for i in range(e):
        g = jnp.dot(x32, gu[i].astype(jnp.float32), precision="highest")
        y = jnp.dot(jax.nn.silu(g[:, :inner]) * g[:, inner:],
                    down[i].astype(jnp.float32), precision="highest")
        want = want + jnp.where(sel[:, i:i + 1], comb[:, i:i + 1], 0) * y
    np.testing.assert_allclose(got, np.asarray(want), atol=tol)
