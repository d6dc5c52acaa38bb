"""The latent walk and the held experts' kernel on REAL TPU hardware,
compiled by Mosaic at `joyai-llm-flash-ep16`'s geometry (32 heads over
a 640-wide row, 16-token blocks, a 256-block table; 16 experts of
2048 x 768 over the 256 columns of a step) and compared, on the same
chip, with `serving.kv_cache.paged_latent_attention_reference` and
with the experts computed one by one in plain jax.numpy. The CPU tier
(`tests/api/test_latent_moe_serving.py`) pins the same kernels under
the interpreter at a tiny size.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _latent_case(contexts, c, dtype, seed=0, h=32, lora=512, rope=64,
                 bs=16, m=256):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    w, b = 640, len(contexts)
    used = sum(-(-ctx // bs) for ctx in contexts)
    n = 2 + used
    pool = (rng.standard_normal((n, 1, bs, w)) * 0.5).astype(np.float32)
    pool[..., lora + rope:] = 0.0
    pool[0] = np.nan                    # NULL: never read
    table = np.zeros((b, m), np.int32)
    pos = np.zeros((b, c), np.int32)
    nxt = 1
    for i, ctx in enumerate(contexts):
        nb = -(-ctx // bs)
        table[i, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
        q_n = min(c, ctx)
        pos[i, :q_n] = np.arange(ctx - q_n, ctx)
    q = (rng.standard_normal((b, c, h, w)) * 0.2).astype(np.float32)
    q[..., lora + rope:] = 0.0
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(pos))


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-4),
                                       ("bfloat16", 0.03)])
@pytest.mark.parametrize("contexts,c", [
    ((4095, 0, 1, 2048, 300, 17), 16),      # chunks, idle, context of 1
    ((4095, 77, 1), 1)])                    # decode
def test_latent_walk_matches_reference_on_the_chip(dtype, tol, contexts,
                                                   c):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged
    from paddle_tpu.serving import kv_cache as kvc
    q, pool, table, pos = _latent_case(contexts, c, getattr(jnp, dtype))
    kw = dict(value_width=512, scale=192 ** -0.5)
    got = np.asarray(paged.paged_latent_attention(
        q, pool, table, pos, **kw), np.float32)
    assert np.isfinite(got).all()
    clean = jnp.where(jnp.isnan(pool), 0, pool).astype(jnp.float32)
    want = np.asarray(kvc.paged_latent_attention_reference(
        q.astype(jnp.float32), clean, table, pos, **kw))
    for i, ctx in enumerate(contexts):
        live = min(c, ctx)
        if ctx == 0:
            assert not got[i].any()
        else:
            np.testing.assert_allclose(got[i, :live], want[i, :live],
                                       atol=tol)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-3),
                                       ("bfloat16", 0.05)])
@pytest.mark.parametrize("routing", ["spread", "one_expert", "nobody"])
def test_held_experts_match_one_by_one_on_the_chip(dtype, tol, routing):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.moe import moe_experts
    dt = getattr(jnp, dtype)
    t, hid, inner, e = 256, 2048, 768, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (t, hid)).astype(dt)
    gu = (jax.random.normal(ks[1], (e, hid, 2 * inner)) * 0.02).astype(dt)
    down = (jax.random.normal(ks[2], (e, inner, hid)) * 0.02).astype(dt)
    comb = jax.random.uniform(ks[3], (t, e), minval=0.1, maxval=1.0)
    if routing == "spread":
        sel = jax.random.uniform(ks[4], (t, e)) < 0.03
    elif routing == "one_expert":       # every column on expert 5
        sel = jnp.zeros((t, e), bool).at[:, 5].set(True)
    else:
        sel = jnp.zeros((t, e), bool)
    got = np.asarray(moe_experts(x, sel, comb, gu, down))
    want = jnp.zeros((t, hid), jnp.float32)
    x32 = x.astype(jnp.float32)
    for i in range(e):
        g = x32 @ gu[i].astype(jnp.float32)
        y = (jax.nn.silu(g[:, :inner]) * g[:, inner:]) \
            @ down[i].astype(jnp.float32)
        want = want + jnp.where(sel[:, i:i + 1], comb[:, i:i + 1], 0) * y
    np.testing.assert_allclose(got, np.asarray(want), atol=tol)
