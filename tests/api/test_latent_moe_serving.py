"""The latent-attention / mixture-of-experts family through the serving
stack (ISSUE 34), at a tiny size on the CPU with the kernels
interpreted: the program against the plain reference in LOGITS, the
latent walk against its reference, the router's semantics, the expert
layer's share semantics, the cache's per-layer geometry, and the spec
that `gpt2-xl`'s block became against the block as it was inlined.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import joyai_llm_flash as ref
from paddle_tpu.models import gpt
from paddle_tpu.models.latent_moe import (LatentMoEConfig, init_params,
                                          latent_moe_tiny)
from paddle_tpu.ops.pallas import paged as pk
from paddle_tpu.ops.pallas.moe import moe_experts
from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                LatentMoEServingModel)
from paddle_tpu.serving import blocks, kv_cache as kvc, moe
from paddle_tpu.serving.kv_cache import NULL_BLOCK, PagedKVCache


def _perturbed(cfg, seed, dtype=jnp.float32):
    """Seeded parameters with norm scales moved off one, so that a
    program that dropped a scale would not pass."""
    params = init_params(cfg, seed, dtype)
    key = jax.random.PRNGKey(seed + 100)

    def bump(path, a):
        name = path[-1].key
        if not name.endswith("_s"):
            return a
        k = jax.random.fold_in(key, hash(jax.tree_util.keystr(path))
                               & 0x7FFFFFFF)
        return (a.astype(jnp.float32)
                + 0.2 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(bump, params)


def _drive(model, prompt, n_new, chunk=4, block_size=8, max_context=64):
    """Prefill `prompt` in chunks, then decode `n_new` greedy tokens,
    through a PagedKVCache and the model's own fused step (the sampling
    variant, whose last output is the full log-prob row of each lane's
    last column). Returns ({position: row}, generated ids)."""
    cfg = model.cfg
    s = 2                                   # lane 1 stays idle
    cache = PagedKVCache(model.num_layers, model.num_heads,
                         model.head_dim, 2 * (max_context // block_size)
                         + 1, block_size=block_size, dtype=model.kv_dtype,
                         geometry=model.kv_geometry)
    m = max_context // block_size
    blocks_ = cache.allocate(m)
    tables = np.stack([cache.make_table(blocks_, m),
                       cache.make_table([], m)])
    fused = jax.jit(model.build_fused_step(block_size, sampling=True))
    v = cfg.vocab_size
    extra = (jnp.zeros((s, v), jnp.float32),
             jnp.zeros((s, 2), jnp.uint32), jnp.ones((s,), jnp.float32),
             jnp.zeros((s,), bool), jnp.zeros((s,), jnp.int32),
             jnp.full((s,), 2.0, jnp.float32))
    pools, rows, out_ids = cache.pools, {}, []
    fed = list(prompt)
    pos = 0
    while len(out_ids) < n_new:
        n = min(chunk, len(fed) - pos)
        tokens = np.zeros((s, chunk), np.int32)
        positions = np.zeros((s, chunk), np.int32)
        valid = np.zeros((s, chunk), bool)
        tokens[0, :n] = fed[pos:pos + n]
        positions[0, :n] = np.arange(pos, pos + n)
        valid[0, :n] = True
        res = fused(pools, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(valid), jnp.asarray(tables), *extra)
        pools, nxt, logp = res[0], res[1], res[3]
        pos += n
        rows[pos - 1] = np.asarray(logp[0])
        if pos == len(fed):
            fed.append(int(nxt[0]))
            out_ids.append(int(nxt[0]))
    return rows, out_ids


# ---------------------------------------------------------------------
# the program against the plain reference, in logits
# ---------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [
    ("float32", 1e-4),
    # bf16 program against float32 over the same bf16-rounded weights:
    # worst gap measured here 0.043 nats (seeds 5, 11) at this size
    ("bfloat16", 0.15),
])
def test_chunked_prefill_then_paged_decode_matches_reference_logits(
        dtype, tol):
    cfg = latent_moe_tiny(n_routed_experts_held=8, expert_offset=4)
    for seed in (5, 11):
        params = _perturbed(cfg, seed, getattr(jnp, dtype))
        model = LatentMoEServingModel(params, cfg)
        prompt = np.random.RandomState(seed).randint(1, 256, size=19)
        rows, out_ids = _drive(model, prompt, n_new=6)
        ids = np.concatenate([prompt, out_ids])
        want = ref.forward_logprobs(params, cfg, ids, pad_to=64)
        assert sorted(rows) == [3, 7, 11, 15, 18, 19, 20, 21, 22, 23]
        for t, row in rows.items():
            gap = np.abs(row - want[t]).max()
            assert gap <= tol, (seed, t, gap)
        if dtype == "float32":
            # the greedy choice is the reference's argmax
            assert out_ids == [int(want[t].argmax())
                               for t in range(18, 24)]


def test_server_scores_match_reference_and_count_routing():
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.observability.tracing import get_recorder
    cfg = latent_moe_tiny(n_routed_experts_held=4, expert_offset=8)
    model = LatentMoEServingModel(_perturbed(cfg, 2), cfg)
    reg = global_registry()
    rec = get_recorder()
    rec.start()
    srv = GenerationServer(model, num_slots=3, chunk=4, block_size=8,
                           max_context=64, start=False)
    prompts = [np.arange(1, 14), np.arange(40, 43), np.arange(7, 30)]
    futs = [srv.submit(p, max_new_tokens=5) for p in prompts]
    srv.run_until_idle()
    rec.stop()
    spans = [e for e in rec.events()
             if e.get("name") == "serving.iteration"]
    rec.clear()
    st = srv.get_stats()
    srv.close()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"] == {
        "mode": "auto", "engaged": True, "version": "v2",
        "name": "paged_latent_attention", "kernel_dispatches": cfg.num_layers, "fallback_dispatches": 0,
        "pool_block_shape": [1, 8, 128]}
    for p, f in zip(prompts, futs):
        r = f.result()
        ids = np.concatenate([p, r.token_ids])
        rows = ref.forward_logprobs(model.params, cfg, ids, pad_to=64,
                                    first_row=len(p) - 1, n_rows=5)
        chosen = rows[np.arange(5), np.asarray(r.token_ids)]
        assert abs(chosen.sum() - r.score) < 5e-4
        assert (rows.max(-1) - chosen).max() < 1e-5
    # every iteration says what its routers did: 3 expert layers, 4 a
    # token; the held share is part of the whole
    assert spans
    for e in spans:
        a = e["args"]
        assert a["moe_assignments"] == a["valid_columns"] * 4 * 3
        assert 0 <= a["moe_assignments_held"] <= a["moe_assignments"]
        assert a["moe_expert_tokens_max"] <= a["valid_columns"]
        assert a["moe_experts_touched"] <= 4 * 3
    total = sum(e["args"]["moe_assignments"] for e in spans)
    assert reg.get("serving.moe.assignments").value() >= total
    assert reg.get("serving.moe.assignments_held").value() >= sum(
        e["args"]["moe_assignments_held"] for e in spans)


def test_absorbed_attention_equals_the_expanded_form():
    """The program's latent attention (absorbed: W_uk folded into the
    query, c_kv summed and expanded through W_uv, read from the paged
    pool by the kernel) against the reference's expanded one (k_nope
    and v materialised a head), one layer, one chunk."""
    cfg = latent_moe_tiny()
    params = _perturbed(cfg, 9)
    model = LatentMoEServingModel(params, cfg)
    spec = model.step_spec()
    t, bs = 16, 8
    h = jax.random.normal(jax.random.PRNGKey(0), (1, t, cfg.hidden_size))
    pos = jnp.arange(t)[None]
    tables = jnp.asarray([[1, 2]], jnp.int32)
    pool = jnp.zeros((3, 1, bs, 128), jnp.float32)
    ctx = blocks.StepContext(
        spec, 1, t, h.dtype, pos, tables[0][pos // bs], pos % bs,
        jnp.ones((1, t), bool), tables, lambda z: z, False, None,
        blocks.rotary_angles(pos, cfg.qk_rope_head_dim, cfg.rope_theta))
    got, new = blocks.ATTENTIONS["latent"](ctx, h, params["l1"],
                                           {"kv": pool})
    dims = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank, float(cfg.rope_theta),
            float(cfg.rms_norm_eps))
    with jax.default_matmul_precision("highest"):
        want = ref._attention(h[0], params["l1"], dims)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    # the cache row of a token: [c_kv | k_rope | zeros]
    row = np.asarray(new["kv"][1, 0, 3])
    assert np.abs(row[:40]).min() > 0 and not row[40:].any()


# ---------------------------------------------------------------------
# the latent walk against its reference
# ---------------------------------------------------------------------

def _latent_case(contexts, c, heads=4, lora=32, rope=8, bs=8, m=12,
                 dtype=jnp.float32, seed=0):
    """Lanes at the given contexts (0: idle), the last `c` (or fewer)
    positions of each as its queries."""
    rng = np.random.RandomState(seed)
    w = pk.latent_row_width(lora, rope)
    b = len(contexts)
    n = 1 + b * m
    pool = rng.randn(n, 1, bs, w).astype(np.float32)
    pool[..., lora + rope:] = 0.0
    pool[NULL_BLOCK] = np.nan           # never read
    table = np.full((b, m), NULL_BLOCK, np.int32)
    pos = np.zeros((b, c), np.int32)
    for i, ctx in enumerate(contexts):
        nb = -(-ctx // bs)
        table[i, :nb] = 1 + i * m + np.arange(nb)
        # a stale entry past the live blocks, which no walk may read
        if 0 < nb < m:
            table[i, nb] = 1 + i * m + nb
            pool[table[i, nb]] = np.nan
        q_n = min(c, ctx)
        pos[i, :q_n] = np.arange(ctx - q_n, ctx)
        pos[i, q_n:] = max(ctx - 1, 0)
    q = rng.randn(b, c, heads, w).astype(np.float32)
    q[..., lora + rope:] = 0.0
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(pos))


@pytest.mark.parametrize("contexts,c", [
    ((37, 0, 1, 96), 4),        # ragged, an idle lane, a context of one
    ((5, 64, 17), 1),           # decode
    ((96, 96), 16),             # a full table, a whole chunk
])
def test_latent_kernel_matches_reference_attention(contexts, c):
    q, pool, table, pos = _latent_case(contexts, c)
    kw = dict(value_width=32, scale=1.0 / np.sqrt(24))
    got = np.asarray(pk.paged_latent_attention(q, pool, table, pos, **kw))
    assert np.isfinite(got).all()           # NULL / stale never read
    # the reference reads whatever the table names: give it zeros there
    clean = jnp.where(jnp.isnan(pool), 0.0, pool)
    want = np.asarray(kvc.paged_latent_attention_reference(
        q, clean, table, pos, **kw))
    for i, ctx in enumerate(contexts):
        if ctx == 0:
            assert not got[i].any()         # an idle lane: exact zeros
            continue
        live = min(c, ctx)
        np.testing.assert_allclose(got[i, :live], want[i, :live],
                                   atol=2e-5)
        if live == 1:
            # a lane that feeds one token: its padded columns' rows
            # are not computed
            assert not got[i, 1:].any()


def test_latent_kernel_bf16_pool_accumulates_in_float32():
    q, pool, table, pos = _latent_case((50, 9), 4, dtype=jnp.bfloat16)
    kw = dict(value_width=32, scale=1.0 / np.sqrt(24))
    got = pk.paged_latent_attention(q, pool, table, pos, **kw)
    assert got.dtype == jnp.bfloat16
    clean = jnp.where(jnp.isnan(pool), 0, pool).astype(jnp.float32)
    want = kvc.paged_latent_attention_reference(
        q.astype(jnp.float32), clean, table, pos, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=0.06)
    # a decode lane among prefill lanes takes the one-column path
    q1, pool1, table1, pos1 = _latent_case((50, 9), 1, dtype=jnp.bfloat16)
    pos4 = jnp.concatenate([pos1, jnp.zeros((2, 3), jnp.int32)], axis=1)
    q4 = jnp.concatenate([q1, q[:, 1:]], axis=1)
    one = pk.paged_latent_attention(q4, pool1, table1, pos4, **kw)
    want1 = pk.paged_latent_attention(q1, pool1, table1, pos1, **kw)
    np.testing.assert_array_equal(np.asarray(one[:, :1], np.float32),
                                  np.asarray(want1, np.float32))
    assert not np.asarray(one[:, 1:], np.float32).any()


def test_latent_dispatcher_counts_and_pins(monkeypatch):
    q, pool, table, pos = _latent_case((20,), 2)
    pool = jnp.where(jnp.isnan(pool), 0.0, pool)
    kw = dict(value_width=32, scale=0.2)
    before = kvc.kernel_dispatch_stats()
    a = kvc.paged_latent_attention(q, pool, table, pos, **kw)
    mid = kvc.kernel_dispatch_stats()
    assert mid["kernel_dispatches"] == before["kernel_dispatches"] + 1
    assert mid["kernel_versions"]["v2"] == \
        before["kernel_versions"].get("v2", 0) + 1
    assert mid["kernel_names"]["paged_latent_attention"] == \
        before["kernel_names"].get("paged_latent_attention", 0) + 1
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    b = kvc.paged_latent_attention(q, pool, table, pos, **kw)
    after = kvc.kernel_dispatch_stats()
    assert after["fallback_dispatches"] == mid["fallback_dispatches"] + 1
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert kvc.paged_kernel_supported(q, pool, latent=True)
    assert not kvc.paged_kernel_supported(q, pool)      # not K beside V
    assert not kvc.paged_kernel_supported(q[..., :64], pool, latent=True)
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    with pytest.raises(ValueError, match="latent walk"):
        kvc.paged_latent_attention(q[..., :64], pool, table, pos, **kw)


# ---------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------

def test_router_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(6, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 12) * 0.5, jnp.float32)
    zero = jnp.zeros((12,))
    ids0, w0 = moe.route(h, w, zero, 3, 2.5)
    # a bias that lifts expert 11 over everyone: chosen by all, first
    bias = zero.at[11].set(10.0)
    ids1, w1 = moe.route(h, w, bias, 3, 2.5)
    assert (np.asarray(ids1)[:, 0] == 11).all()
    assert not (np.asarray(ids0) == 11).all(axis=None)
    s = np.asarray(jax.nn.sigmoid(h @ w))
    # its weight is its sigmoid score over the chosen scores' sum: the
    # bias is in no weight
    picked = np.take_along_axis(s, np.asarray(ids1), 1)
    np.testing.assert_allclose(
        np.asarray(w1), 2.5 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w0).sum(1), 2.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(1), 2.5, rtol=1e-5)
    # without normalisation the weights are the scaled scores
    _, w2 = moe.route(h, w, bias, 3, 2.5, normalize=False)
    np.testing.assert_allclose(np.asarray(w2), 2.5 * picked, rtol=1e-5)


def test_router_breaks_ties_toward_the_lower_index_like_the_reference():
    h = jnp.ones((2, 4), jnp.float32)
    w = jnp.zeros((4, 8), jnp.float32)      # every score 0.5
    ids, wts = moe.route(h, w, jnp.zeros((8,)), 3, 2.5)
    assert np.asarray(ids).tolist() == [[0, 1, 2]] * 2
    np.testing.assert_allclose(np.asarray(wts), 2.5 / 3, rtol=1e-6)
    # a bias tie is broken the same way
    ids, _ = moe.route(h, w, jnp.asarray([0, 1, 0, 1, 1, 0, 1, 0.]), 2,
                       1.0)
    assert np.asarray(ids).tolist() == [[1, 3]] * 2


# ---------------------------------------------------------------------
# the expert layer's share
# ---------------------------------------------------------------------

def _layer_case(cfg, seed=4, t=24):
    params = init_params(cfg, seed)
    h = jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.hidden_size))
    return params["l2"], h


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(shares):
    whole = latent_moe_tiny()
    lp, h = _layer_case(whole)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lp, 4, 2.5, True, 0)     # uncut reference
        shared = ref._gated(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    held = 16 // shares
    live = jnp.ones((h.shape[0],), bool)
    total = jnp.zeros_like(h)
    counts = np.zeros(4, np.int64)
    for k in range(shares):
        cfg = latent_moe_tiny(n_routed_experts_held=held,
                              expert_offset=k * held)
        lp_k, _ = _layer_case(cfg)
        # a share holds the same tensors the uncut layer holds there
        np.testing.assert_array_equal(
            np.asarray(lp_k["exp_gu"]),
            np.asarray(lp["exp_gu"][k * held:(k + 1) * held]))
        part, stats = moe.expert_share(
            h, lp_k, live, k=4, scaling=2.5, normalize=True,
            offset=k * held)
        total = total + (part - shared)     # the shared expert once
        counts += np.asarray(stats)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), atol=2e-5)
    # every assignment was held by exactly one share
    assert counts[1] == h.shape[0] * 4
    assert counts[0] == shares * h.shape[0] * 4


def test_no_token_is_dropped_when_every_column_picks_the_same_expert():
    cfg = latent_moe_tiny(n_routed_experts_held=4, expert_offset=4)
    lp, h = _layer_case(cfg, t=80)
    # every token's first choice is expert 5; the other three elsewhere
    lp = dict(lp, router_b=jnp.zeros((16,)).at[5].set(10.0))
    live = jnp.ones((80,), bool).at[70:].set(False)
    got, stats = moe.expert_share(h, lp, live, k=4, scaling=2.5,
                                  normalize=True, offset=4)
    assert int(stats[2]) == 70 and int(stats[0]) == 70 * 4
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lp, 4, 2.5, True, 4)
        shared = ref._gated(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    np.testing.assert_allclose(np.asarray(got[:70]),
                               np.asarray(want[:70]), atol=2e-5)
    # a padded column is routed nowhere: the shared expert alone
    np.testing.assert_allclose(np.asarray(got[70:]),
                               np.asarray(shared[70:]), atol=2e-5)


def test_moe_kernel_with_nobody_routed_here_writes_zeros():
    x = jnp.ones((16, 32))
    out = moe_experts(x, jnp.zeros((16, 2), bool), jnp.ones((16, 2)),
                      jnp.ones((2, 32, 16)), jnp.ones((2, 8, 32)))
    assert out.shape == (16, 32) and not np.asarray(out).any()


# ---------------------------------------------------------------------
# the cache's per-layer geometry
# ---------------------------------------------------------------------

def test_cache_takes_a_per_layer_geometry():
    cache = PagedKVCache(3, 4, 24, 9, block_size=8, dtype=jnp.bfloat16,
                         geometry=[(1, 128), (4, 48), (1, 128)])
    assert [p["kv"].shape for p in cache.pools] == [
        (9, 1, 8, 128), (9, 4, 8, 48), (9, 1, 8, 128)]
    assert cache.latent
    assert cache.pool_bytes() == 9 * 8 * (128 + 4 * 48 + 128) * 2
    assert cache.dense_pool_bytes() == cache.pool_bytes()
    assert cache.wire_geometry()["block_shapes"] == [
        [1, 128], [4, 48], [1, 128]]
    # blocks are addressed by id whatever lies inside: COW, the wire
    # and the host tier are the same code
    a, b = cache.allocate(2)
    cache.pools[0]["kv"] = cache.pools[0]["kv"].at[a].set(1.5)
    cache.cow_copy(a, b)
    assert float(cache.pools[0]["kv"][b].min()) == 1.5
    meta, arrays = cache.serialize_block(a)
    other = PagedKVCache(3, 4, 24, 5, block_size=8, dtype=jnp.bfloat16,
                         geometry=[(1, 128), (4, 48), (1, 128)])
    other.deserialize_block(2, meta, arrays)
    assert float(other.pools[0]["kv"][2].max()) == 1.5
    plain = PagedKVCache(3, 4, 24, 5, block_size=8, dtype=jnp.bfloat16)
    assert not plain.latent and "block_shapes" not in plain.wire_geometry()
    with pytest.raises(ValueError, match="matching pool geometry"):
        plain.deserialize_block(2, meta, arrays)
    with pytest.raises(ValueError, match="matching pool geometry"):
        plain.adopt_block_from(cache, a, 1)
    cache.enable_host_tier(2)
    hb = cache.spill_block(a)
    cache.swap_in_block(hb, b)
    assert cache.host.pool_bytes() == 2 * 8 * (128 + 4 * 48 + 128) * 2
    with pytest.raises(ValueError, match="names 2 layers"):
        PagedKVCache(3, 4, 24, 9, geometry=[(1, 128)] * 2)
    with pytest.raises(NotImplementedError, match="served dense"):
        PagedKVCache(3, 4, 24, 9, kv_dtype="int8",
                     geometry=[(1, 128)] * 3)


def test_latent_row_width_pads_to_whole_lane_tiles():
    assert pk.latent_row_width(512, 64) == 640
    assert pk.latent_row_width(32, 8) == 128
    assert pk.latent_row_width(96, 32) == 128


def test_config_refuses_unknown_fields_and_bad_shares():
    with pytest.raises(TypeError, match="no field"):
        LatentMoEConfig(hidden=3)
    with pytest.raises(ValueError, match="not among"):
        LatentMoEConfig(n_routed_experts_held=16, expert_offset=250)


# ---------------------------------------------------------------------
# gpt2-xl's block as a spec against the block as it was inlined
# ---------------------------------------------------------------------

def _inlined_gpt_layers(params, cfg, block_size, pools, tokens, positions,
                        valid, tables):
    """`_fused_step_body`'s layer loop as it stood before the spec
    (PR 33), kept here as the oracle: learned positions, LayerNorm with
    bias, biased projections, erf GELU."""
    from paddle_tpu.models.gpt import _ln
    from paddle_tpu.serving.kv_cache import (fuse_kv, paged_attention,
                                             write_block_kv)
    s, c = tokens.shape
    h_count = cfg.num_heads
    d = cfg.hidden_size // h_count
    pos = jnp.where(valid, positions, 0)
    x = params["word_emb"][tokens] + params["pos_emb"][pos]
    bidx = jnp.take_along_axis(tables, pos // block_size, axis=1)
    bidx = jnp.where(valid, bidx, NULL_BLOCK)
    off = jnp.where(valid, pos % block_size, 0)
    new_pools = []
    for i in range(cfg.num_layers):
        lp = params[f"l{i}"]
        kvp = pools[i]["kv"]
        hn = _ln(x, lp["ln1_s"], lp["ln1_b"])
        q = (hn @ lp["wq"] + lp["bq"]).reshape(s, c, h_count, d)
        k = (hn @ lp["wk"] + lp["bk"]).reshape(s, c, h_count, d)
        v = (hn @ lp["wv"] + lp["bv"]).reshape(s, c, h_count, d)
        kvp = write_block_kv(kvp, fuse_kv(k, v), bidx, off)
        o = paged_attention(q.transpose(0, 2, 1, 3), kvp, tables, pos)
        o = o.transpose(0, 2, 1, 3).reshape(s, c, h_count * d)
        x = x + (o @ lp["wo"] + lp["bo"]).astype(x.dtype)
        hn = _ln(x, lp["ln2_s"], lp["ln2_b"])
        f = jax.nn.gelu(hn @ lp["f0w"] + lp["f0b"], approximate=False)
        x = x + (f @ lp["f1w"] + lp["f1b"]).astype(x.dtype)
        new_pools.append({"kv": kvp})
    x = _ln(x, params["lnf_s"], params["lnf_b"])
    last = jnp.clip(valid.sum(1) - 1, 0, c - 1)
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logp = jax.nn.log_softmax(
        (xl @ params["word_emb"].T).astype(jnp.float32))
    nxt = jnp.argmax(logp, axis=-1)
    return new_pools, nxt.astype(jnp.int32), \
        jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gpt_spec_is_bitwise_the_inlined_block(dtype):
    cfg = gpt.gpt_tiny()
    rng = np.random.RandomState(1)
    h, inner, v = cfg.hidden_size, cfg.inner_size, cfg.vocab_size

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)

    params = {"word_emb": arr(v, h), "pos_emb": arr(cfg.max_position, h),
              "lnf_s": 1 + arr(h), "lnf_b": arr(h)}
    for i in range(cfg.num_layers):
        params[f"l{i}"] = {
            "ln1_s": 1 + arr(h), "ln1_b": arr(h), "ln2_s": 1 + arr(h),
            "ln2_b": arr(h), "wq": arr(h, h), "wk": arr(h, h),
            "wv": arr(h, h), "wo": arr(h, h), "bq": arr(h), "bk": arr(h),
            "bv": arr(h), "bo": arr(h), "f0w": arr(h, inner),
            "f0b": arr(inner), "f1w": arr(inner, h), "f1b": arr(h)}
    model = GPTServingModel(params, cfg, dtype=getattr(jnp, dtype))
    bs, s, c, m = 8, 3, 4, 4
    spec = model.step_spec()
    assert spec.layers == (blocks.LayerSpec("layer_norm", "mha",
                                            "gelu"),) * 4
    assert spec.positions == "learned" and spec.tied_head

    def fresh():
        return PagedKVCache(cfg.num_layers, cfg.num_heads, 32, 16,
                            block_size=bs, dtype=model.kv_dtype).pools

    tables = jnp.asarray(np.arange(1, 1 + s * m).reshape(s, m), jnp.int32)
    new = jax.jit(model.build_fused_step(bs))
    old = jax.jit(lambda *a: _inlined_gpt_layers(model.params, cfg, bs,
                                                 *a))
    pools_new, pools_old = fresh(), fresh()
    for it in range(3):
        tokens = jnp.asarray(rng.randint(0, v, (s, c)), jnp.int32)
        positions = jnp.asarray(np.arange(c)[None] + it * c
                                + np.zeros((s, 1), int), jnp.int32)
        valid = jnp.asarray([[True] * c, [True, True, False, False],
                             [False] * c])
        pools_new, n_ids, n_lp = new(pools_new, tokens, positions, valid,
                                     tables)
        pools_old, o_ids, o_lp = old(pools_old, tokens, positions, valid,
                                     tables)
        np.testing.assert_array_equal(np.asarray(n_ids), np.asarray(o_ids))
        np.testing.assert_array_equal(np.asarray(n_lp), np.asarray(o_lp))
        for a, b in zip(pools_new, pools_old):
            np.testing.assert_array_equal(
                np.asarray(a["kv"], np.float32),
                np.asarray(b["kv"], np.float32))


def test_every_block_kind_a_spec_may_name_is_in_the_tables():
    assert set(blocks.NORMS) == {"layer_norm", "rms_norm"}
    assert set(blocks.ATTENTIONS) >= {"mha", "latent"}
    assert set(blocks.MLPS) == {"gelu", "gated", "experts"}
    spec = LatentMoEServingModel(init_params(latent_moe_tiny(), 0),
                                 latent_moe_tiny()).step_spec()
    assert [l.mlp for l in spec.layers] == ["gated"] + ["experts"] * 3
    assert {l.attention for l in spec.layers} == {"latent"}
    assert spec.positions == "rotary" and not spec.tied_head
