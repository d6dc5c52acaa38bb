"""decoding.py edge cases the serving engine relies on.

- `update_kv_cache` dtype-wins contract: a bf16 serving cache accepts
  f32 K/V without caller casts, on BOTH the dense and the paged path;
- beam search finished-lane masking holds through the final scan step
  (a lane that finished early keeps emitting EOS at zero cost all the
  way to t == max_len, so its score is frozen);
- paged-vs-dense decode equivalence on identical prompts: bitwise for
  greedy argmax token ids, allclose (and in practice bitwise) scores —
  the acceptance bar for serving/kv_cache.py's adapter.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.inference import decoding as dec
from paddle_tpu.models import gpt

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# update_kv_cache dtype contract
# ---------------------------------------------------------------------------

def test_update_kv_cache_bf16_cache_wins_over_f32_kv():
    cache = {"k": jnp.zeros((2, 2, 8, 4), jnp.bfloat16),
             "v": jnp.zeros((2, 2, 8, 4), jnp.bfloat16)}
    k_t = jnp.full((2, 2, 1, 4), 1.0078125, jnp.float32)  # exact in bf16
    v_t = jnp.full((2, 2, 1, 4), 2.5, jnp.float32)
    out = dec.update_kv_cache(cache, k_t, v_t, 3)
    assert out["k"].dtype == jnp.bfloat16
    assert out["v"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["k"][:, :, 3, :], np.float32), 1.0078125)
    np.testing.assert_array_equal(
        np.asarray(out["v"][:, :, 3, :], np.float32), 2.5)
    # untouched rows stay zero
    assert not np.asarray(out["k"][:, :, 4, :], np.float32).any()


def test_update_kv_cache_bf16_rounds_like_astype():
    """The cast is bf16 rounding, not truncation: the stored value must
    equal jnp.asarray(x, bf16) for a value NOT representable in bf16."""
    cache = {"k": jnp.zeros((1, 1, 4, 2), jnp.bfloat16),
             "v": jnp.zeros((1, 1, 4, 2), jnp.bfloat16)}
    x = 1.0001     # rounds in bf16
    out = dec.update_kv_cache(cache, jnp.full((1, 1, 1, 2), x),
                              jnp.full((1, 1, 1, 2), x), 0)
    expect = jnp.asarray(x, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out["k"][0, 0, 0], np.float32),
                                  np.float32(expect))


def test_paged_update_kv_cache_dtype_wins_too():
    pool = serving.PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                                num_blocks=5, block_size=4,
                                dtype=jnp.bfloat16)
    layers, _tables, blocks = serving.build_paged_decode_cache(
        pool, batch=2, max_len=8)
    k_t = jnp.full((2, 2, 1, 4), 1.0078125, jnp.float32)
    out = dec.update_kv_cache(layers[0], k_t, 2 * k_t, 5)
    assert isinstance(out, serving.PagedDecodeLayer)
    # the dense views are the K lanes and the V lanes of the one pool
    for key, want in (("k", 1.0078125), ("v", 2.015625)):
        dense_view = out[key]
        assert dense_view.dtype == jnp.bfloat16
        assert dense_view.shape == (2, 2, 8, 4)
        np.testing.assert_array_equal(
            np.asarray(dense_view[:, :, 5, :], np.float32), want)
    pool.free(blocks)


# ---------------------------------------------------------------------------
# beam search finished-lane masking at the scan boundary
# ---------------------------------------------------------------------------

def test_beam_finished_lane_frozen_through_final_step():
    """Vocab 4, eos=3. The step emits a fixed distribution: eos wins at
    every step. The best lane finishes at t=0; every later step
    (including the LAST, t == max_len-1) may only append eos at zero
    cost, so the final score is exactly the single eos logprob (the
    GNMT length penalty divides by 1.0 for a length-1 sequence)."""
    logp = np.log(np.array([0.05, 0.2, 0.05, 0.7], np.float32))

    def step(ids_t, cache, t):
        return jnp.tile(jnp.asarray(logp)[None, :],
                        (ids_t.shape[0], 1)), cache

    max_len = 4
    ids, scores = dec.beam_decode(step, {"z": jnp.zeros((2,))},
                                  jnp.zeros((1,), jnp.int32),
                                  max_len=max_len, beam_size=2, eos_id=3)
    ids, scores = np.asarray(ids), np.asarray(scores)
    # best lane: eos at step 0, padded with eos to the end of the scan
    np.testing.assert_array_equal(ids[0, 0], [3, 3, 3, 3])
    np.testing.assert_allclose(scores[0, 0], logp[3], rtol=1e-6)
    # runner-up: token 1 then eos; its score is logp[1] + logp[3],
    # length 2 -> penalty ((5+2)/6)**0.6
    np.testing.assert_array_equal(ids[0, 1], [1, 3, 3, 3])
    lp = ((5.0 + 2.0) / 6.0) ** 0.6
    np.testing.assert_allclose(scores[0, 1], (logp[1] + logp[3]) / lp,
                               rtol=1e-5)


def test_beam_lane_finishing_on_last_step_counts_its_eos():
    """A lane that emits eos exactly AT the final step t == max_len-1:
    the eos must land in the ids and its logprob in the score — the
    boundary the finished-lane mask must not clip."""
    # eos only becomes the argmax at the last step
    def step(ids_t, cache, t):
        base = jnp.log(jnp.asarray([0.05, 0.85, 0.05, 0.05]))
        late = jnp.log(jnp.asarray([0.05, 0.05, 0.05, 0.85]))
        row = jax.lax.select(t >= 2, late, base)
        return jnp.tile(row[None, :], (ids_t.shape[0], 1)), cache

    ids, scores = dec.beam_decode(step, {"z": jnp.zeros((1,))},
                                  jnp.zeros((1,), jnp.int32),
                                  max_len=3, beam_size=1, eos_id=3)
    np.testing.assert_array_equal(np.asarray(ids)[0, 0], [1, 1, 3])
    expect = 2 * np.log(0.85) + np.log(0.85)
    lp = ((5.0 + 3.0) / 6.0) ** 0.6
    np.testing.assert_allclose(np.asarray(scores)[0, 0], expect / lp,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# paged-vs-dense decode equivalence (the serving acceptance bar)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt_params():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 23
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, gpt.load_params(scope, cfg)


def test_paged_vs_dense_greedy_bitwise(tiny_gpt_params):
    cfg, params = tiny_gpt_params
    d = cfg.hidden_size // cfg.num_heads
    max_len, gen = 32, 16
    step = gpt.build_kv_step(params, cfg, max_len)
    bos = jnp.asarray([5, 9, 200], jnp.int32)
    dense = dec.init_kv_cache(3, cfg.num_layers, cfg.num_heads, max_len, d)
    ids_d, sc_d = dec.greedy_decode(step, dense, bos, max_len=gen)
    pool = serving.PagedKVCache(cfg.num_layers, cfg.num_heads, d,
                                num_blocks=16, block_size=8)
    paged, _tables, blocks = serving.build_paged_decode_cache(
        pool, batch=3, max_len=max_len)
    ids_p, sc_p = dec.greedy_decode(step, paged, bos, max_len=gen)
    pool.free(blocks)
    # bitwise token ids; scores allclose (and bitwise in practice —
    # the gathered view runs the identical contraction)
    np.testing.assert_array_equal(np.asarray(ids_d), np.asarray(ids_p))
    np.testing.assert_allclose(np.asarray(sc_d), np.asarray(sc_p),
                               rtol=1e-6, atol=1e-7)


def test_paged_vs_dense_sampling_same_rng_bitwise(tiny_gpt_params):
    """sample_decode with the same rng key must pick the same tokens
    against either cache — the filtered distributions agree."""
    cfg, params = tiny_gpt_params
    d = cfg.hidden_size // cfg.num_heads
    max_len, gen = 16, 8
    step = gpt.build_kv_step(params, cfg, max_len)
    bos = jnp.asarray([5, 9], jnp.int32)
    key = jax.random.PRNGKey(3)
    dense = dec.init_kv_cache(2, cfg.num_layers, cfg.num_heads, max_len, d)
    ids_d, _ = dec.sample_decode(step, dense, bos, gen, key,
                                 temperature=1.0, top_k=16)
    pool = serving.PagedKVCache(cfg.num_layers, cfg.num_heads, d,
                                num_blocks=8, block_size=8)
    paged, _t, blocks = serving.build_paged_decode_cache(pool, 2, max_len)
    ids_p, _ = dec.sample_decode(step, paged, bos, gen, key,
                                 temperature=1.0, top_k=16)
    pool.free(blocks)
    np.testing.assert_array_equal(np.asarray(ids_d), np.asarray(ids_p))
