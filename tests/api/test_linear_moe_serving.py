"""The linear-attention / mixture-of-experts family through the serving
stack (ISSUE 36), at a tiny size on the CPU with the kernels
interpreted: the program against the plain reference in LOGITS
(prefill in chunks, then decoding, through BOTH kinds of cache), the
chunked delta rule and its `jax.numpy` form against the rule token by
token, the state's reset and an idle lane's state, the expert layer's
shares, the two kinds of cache in one manager, what refuses a model
with a state, and the counts a step reports.
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import solar_open2 as ref
from paddle_tpu.models.linear_moe import (LinearMoEConfig, init_params,
                                          linear_moe_tiny, param_shapes)
from paddle_tpu.ops.pallas import linear, moe as moe_kernel
from paddle_tpu.serving import (GenerationServer, LinearMoEServingModel,
                                SpecDecodeConfig)
from paddle_tpu.serving import blocks, kv_cache as kvc, moe
from paddle_tpu.serving.kv_cache import PagedKVCache


def _perturbed(cfg, seed, dtype=jnp.float32):
    """Seeded parameters with norm scales moved off one, so that a
    program that dropped a scale would not pass."""
    params = init_params(cfg, seed, dtype)
    key = jax.random.PRNGKey(seed + 100)

    def bump(path, a):
        name = path[-1].key
        if not name.endswith("_s"):
            return a
        # a stable hash: `hash` of a str differs from process to process
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        return (a.astype(jnp.float32)
                + 0.2 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(bump, params)


def _tiny(**kw):
    # weights wide enough that the logits are not flat at 64 wide
    return linear_moe_tiny(initializer_range=0.1, **kw)


def _drive(model, prompt, n_new, chunk=4, block_size=8, max_context=64):
    """Prefill `prompt` in chunks, then decode `n_new` greedy tokens,
    through a PagedKVCache of both kinds and the model's own fused step
    (the sampling variant, whose log-prob rows come before the step's
    counts). Lane 1 stays idle. Returns ({position: row}, ids, the
    final pools, the pools a fresh cache starts with)."""
    cfg = model.cfg
    s = 2
    m = max_context // block_size
    cache = PagedKVCache(model.num_layers, model.num_heads,
                         model.head_dim, 2 * m + 1, block_size=block_size,
                         dtype=model.kv_dtype,
                         num_kv_heads=model.num_kv_heads,
                         geometry=model.kv_geometry, num_slots=s)
    # lane 1 idles over a state that is NOT zero: it must come back as
    # it went in
    for i in cache.state_layers:
        cache.pools[i] = {
            n: a.at[1].set(jnp.arange(a[1].size, dtype=jnp.float32)
                           .reshape(a[1].shape).astype(a.dtype) * 1e-3)
            for n, a in cache.pools[i].items()}
    before = jax.tree_util.tree_map(np.asarray, cache.pools)
    tables = np.stack([cache.make_table(cache.allocate(m), m),
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
    return rows, out_ids, jax.tree_util.tree_map(np.asarray, pools), before


# ---------------------------------------------------------------------
# the program against the plain reference, in logits
# ---------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [
    ("float32", 2e-4),
    # bf16 program against float32 over the same bf16-rounded weights:
    # worst gap over a whole row measured here 0.27 nats (seeds 5, 11;
    # weights at 0.1, so the logits are far from flat)
    ("bfloat16", 0.6),
])
def test_chunked_prefill_then_decode_matches_reference_logits(dtype, tol):
    cfg = _tiny(n_routed_experts_held=8, expert_offset=4)
    for seed in (5, 11):
        params = _perturbed(cfg, seed, getattr(jnp, dtype))
        model = LinearMoEServingModel(params, cfg)
        # 19 tokens: the prompt ends mid-chunk (4) and mid-block (8)
        prompt = np.random.RandomState(seed).randint(1, 256, size=19)
        rows, out_ids, pools, before = _drive(model, prompt, n_new=6)
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
        # the idle lane's state and carried rows are bitwise unchanged,
        # the busy lane's moved
        for i in range(cfg.num_layers):
            if cfg.is_gqa_layer(i):
                continue
            for name in ("state", "conv"):
                np.testing.assert_array_equal(pools[i][name][1],
                                              before[i][name][1])
                assert np.abs(pools[i][name][0].astype(np.float32)).max() > 0


def test_the_spec_follows_gqa_layers_and_the_tables_hold_every_kind():
    assert set(blocks.ATTENTIONS) == {"mha", "latent", "gqa_gated", "gqa",
                                      "gqa_window", "kda", "power"}
    assert blocks.STATE_ATTENTIONS == ("kda", "power")
    cfg = linear_moe_tiny(num_layers=6, gqa_layers=(1, 2))
    model = LinearMoEServingModel(init_params(cfg, 0), cfg)
    spec = model.step_spec()
    assert [l.attention for l in spec.layers] == [
        "kda", "gqa_gated", "gqa_gated", "kda", "kda", "kda"]
    assert {l.mlp for l in spec.layers} == {"experts"}
    assert spec.positions == "none" and not spec.tied_head
    assert [isinstance(g, dict) for g in model.kv_geometry] == [
        True, False, False, True, True, True]
    # the published default: every fourth layer from 0
    assert LinearMoEConfig().gqa_layers == tuple(range(0, 48, 4))
    assert set(param_shapes(cfg)) == {"top", "gqa", "kda"}


def test_config_refuses_unknown_fields_bad_layers_and_bad_shares():
    with pytest.raises(TypeError, match="no field"):
        LinearMoEConfig(hidden=3)
    with pytest.raises(ValueError, match="not among"):
        LinearMoEConfig(n_routed_experts_held=20, expert_offset=310)
    with pytest.raises(ValueError, match="gqa_layers"):
        LinearMoEConfig(num_layers=4, gqa_layers=(0, 4))


def test_the_decays_initialisers_neither_freeze_nor_erase():
    cfg = linear_moe_tiny()
    lp = init_params(cfg, 3)["l1"]
    rate = np.exp(np.asarray(lp["a_log"]))
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 0.001 <= dt.min() * 1.0001 and dt.max() <= 0.1 * 1.0001
    assert lp["a_log"].dtype == lp["dt_bias"].dtype == jnp.float32


# ---------------------------------------------------------------------
# the chunked delta rule against the rule token by token
# ---------------------------------------------------------------------

def _kda_case(seed, beta_range, s=4, c=8, h=4, d=16, dtype=jnp.float32):
    r = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(r.randn(s, c, h, d)) * d ** -0.5
    k = unit(r.randn(s, c, h, d))
    v = r.randn(s, c, h, d)
    g = -np.exp(r.uniform(np.log(1e-3), np.log(2.4), (s, c, h, d)))
    beta = r.uniform(*beta_range, (s, c, h))
    state = r.randn(s, h, d, d) * 0.3
    return ([jnp.asarray(a, dtype) for a in (q, k, v)]
            + [jnp.asarray(a, jnp.float32) for a in (g, beta, state)])


@pytest.mark.parametrize("beta_range", [(0.0, 0.02), (1.98, 2.0),
                                        (0.0, 2.0)],
                         ids=["beta_near_0", "beta_near_2", "beta_any"])
@pytest.mark.parametrize("prefix", [0, 1, 7, 8])
def test_kda_chunk_and_its_jnp_form_are_the_rule_token_by_token(
        prefix, beta_range):
    args = _kda_case(prefix, beta_range)
    # lane 0 takes the prefix under test; the others mix
    counts = jnp.asarray([prefix, 8, 3, 0], jnp.int32)
    reset = jnp.asarray([False, True, False, False])
    want_o, want_s = linear.kda_recurrence(*args, counts, reset)
    live = (np.arange(8)[None] < np.asarray(counts)[:, None])[
        ..., None, None]
    for form in (linear.kda_chunk, linear.kda_chunk_reference):
        o, new = form(*args, counts, reset)
        np.testing.assert_allclose(np.where(live, o, 0),
                                   np.where(live, want_o, 0), atol=2e-5)
        np.testing.assert_allclose(new, want_s, atol=2e-5)
        # a lane with no valid column gets its state back bitwise
        for lane in np.flatnonzero(np.asarray(counts) == 0):
            np.testing.assert_array_equal(np.asarray(new[lane]),
                                          np.asarray(args[5][lane]))
    if prefix:
        # a padded column left the state as it was: feeding the valid
        # prefix alone gives the same state
        short = [a[:, :prefix] for a in args[:5]] + [args[5]]
        cut = jnp.minimum(counts, prefix)
        _, alone = linear.kda_chunk(*short, cut, reset)
        np.testing.assert_allclose(np.asarray(new[0]),
                                   np.asarray(alone[0]), atol=2e-5)


def test_kda_chunk_over_chunks_equals_one_pass_and_bf16_keeps_the_state_f32():
    q, k, v, g, beta, state = _kda_case(9, (0.0, 2.0), s=2, c=16)
    full = jnp.asarray([16, 16], jnp.int32)
    no = jnp.zeros((2,), bool)
    want_o, want_s = linear.kda_recurrence(q, k, v, g, beta, state, full,
                                           no)
    outs, st = [], state
    for lo in (0, 4, 8, 12):
        part = [a[:, lo:lo + 4] for a in (q, k, v, g, beta)]
        o, st = linear.kda_chunk(*part, st, jnp.asarray([4, 4]), no)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o,
                               atol=3e-5)
    np.testing.assert_allclose(st, want_s, atol=3e-5)
    bf = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    o, new = linear.kda_chunk(*bf, g, beta, state, full, no)
    assert new.dtype == o.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, atol=0.05)
    np.testing.assert_allclose(new, want_s, atol=0.05)


def test_kda_dispatcher_counts_by_name_and_pins(monkeypatch):
    args = _kda_case(2, (0.0, 2.0))
    counts = jnp.asarray([8, 2, 0, 1], jnp.int32)
    reset = jnp.asarray([True, False, False, False])
    before = kvc.kernel_dispatch_stats()
    got = kvc.kda_chunk(*args, counts, reset)
    after = kvc.kernel_dispatch_stats()
    assert after["kernel_dispatches"] == before["kernel_dispatches"] + 1
    assert after["kernel_names"].get("kda_chunk", 0) == \
        before["kernel_names"].get("kda_chunk", 0) + 1
    # the kernel walks no table: no generation is counted for it
    assert after["kernel_versions"] == before["kernel_versions"]
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    pinned = kvc.kda_chunk(*args, counts, reset)
    last = kvc.kernel_dispatch_stats()
    assert last["fallback_dispatches"] == after["fallback_dispatches"] + 1
    assert last["fallback_reasons"]["pinned_off"] >= 1
    for a, b in zip(got, pinned):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_short_conv_carries_its_rows_over_a_chunk_boundary():
    r = np.random.RandomState(0)
    z = jnp.asarray(r.randn(2, 12, 6), jnp.float32)
    taps = jnp.asarray(r.randn(4, 6), jnp.float32)
    zero = jnp.zeros((2, 3, 6))
    want, _ = blocks.short_conv(z, zero, taps, jnp.asarray([12, 12]))
    carried, got = zero, []
    # chunks of 5: lane 0 feeds 5, 5, 2; lane 1 feeds 5, 0 (idle), 5, 2
    plan = [(0, 5, 0, 5), (5, 5, 5, 0), (10, 2, 5, 5), (12, 0, 10, 2)]
    out = np.zeros((2, 12, 6), np.float32)
    for lo0, n0, lo1, n1 in plan:
        chunk = np.zeros((2, 5, 6), np.float32)
        chunk[0, :n0] = z[0, lo0:lo0 + n0]
        chunk[1, :n1] = z[1, lo1:lo1 + n1]
        y, new = blocks.short_conv(jnp.asarray(chunk), carried, taps,
                                   jnp.asarray([n0, n1]))
        if n1 == 0:     # an idle lane carries on what it had, bitwise
            np.testing.assert_array_equal(np.asarray(new[1]),
                                          np.asarray(carried[1]))
        carried = new
        out[0, lo0:lo0 + n0] = y[0, :n0]
        out[1, lo1:lo1 + n1] = y[1, :n1]
    np.testing.assert_allclose(out, want, atol=1e-6)


# ---------------------------------------------------------------------
# through the engine: resets, counts, one signature
# ---------------------------------------------------------------------

def _server(model, **kw):
    kw = {"num_slots": 3, "chunk": 4, "block_size": 8, "max_context": 64,
          "start": False, **kw}
    return GenerationServer(model, **kw)


def test_server_scores_match_reference_and_count_the_state():
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.observability.tracing import get_recorder
    cfg = _tiny(n_routed_experts_held=4, expert_offset=8)
    model = LinearMoEServingModel(_perturbed(cfg, 2), cfg)
    reg = global_registry()
    resets0 = reg.counter("serving.state.resets").value()
    columns0 = reg.counter("serving.state.columns").value()
    rec = get_recorder()
    rec.start()
    srv = _server(model)
    # five requests over three lanes: two lanes are reused
    prompts = [np.arange(1, 14), np.arange(40, 43), np.arange(7, 30),
               np.arange(100, 109), np.arange(60, 66)]
    futs = [srv.submit(p, max_new_tokens=5) for p in prompts]
    srv.run_until_idle()
    rec.stop()
    spans = [e for e in rec.events()
             if e.get("name") == "serving.iteration"]
    rec.clear()
    st = srv.get_stats()
    assert reg.gauge("serving.state.bytes").labels(
        server=srv._ledger_id).value() == srv.cache.state_bytes() > 0
    srv.close()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"] == {
        "mode": "auto", "engaged": True, "version": "v1",
        "name": "kda_chunk+paged_attention_v1",
        "kernel_dispatches": cfg.num_layers, "fallback_dispatches": 0,
        "pool_block_shape": [2, 8, 32]}
    for p, f in zip(prompts, futs):
        r = f.result()
        ids = np.concatenate([p, r.token_ids])
        rows = ref.forward_logprobs(model.params, cfg, ids, pad_to=64,
                                    first_row=len(p) - 1, n_rows=5)
        chosen = rows[np.arange(5), np.asarray(r.token_ids)]
        assert abs(chosen.sum() - r.score) < 5e-4
        assert (rows.max(-1) - chosen).max() < 1e-5
    # every iteration says what its state layers did (3 of the 5 layers
    # keep a state) beside the routers' counts
    assert spans
    for e in spans:
        a = e["args"]
        assert a["kda_columns"] == 3 * a["valid_columns"]
        assert a["kda_lane_calls"] == 3 * a["lanes"]
        assert 0 <= a["state_resets"] <= a["lanes"]
        assert a["moe_assignments"] == a["valid_columns"] * 4 * 5
    # a request is admitted once and starts once
    assert sum(e["args"]["state_resets"] for e in spans) == len(prompts)
    assert st["admitted"] == len(prompts)
    assert reg.counter("serving.state.resets").value() - resets0 == \
        len(prompts)
    assert reg.counter("serving.state.columns").value() - columns0 == \
        sum(e["args"]["kda_columns"] for e in spans)


def test_a_reused_lane_reads_as_a_fresh_server_does():
    cfg = _tiny(n_routed_experts_held=16)
    model = LinearMoEServingModel(_perturbed(cfg, 4), cfg)
    first, second = np.arange(3, 25), np.arange(90, 101)
    srv = _server(model, num_slots=1)
    a = srv.submit(first, max_new_tokens=4)
    b = srv.submit(second, max_new_tokens=6)        # the lane again
    srv.run_until_idle()
    reused = b.result()
    assert len(a.result().token_ids) == 4
    srv.close()
    fresh_srv = _server(model, num_slots=1)
    fresh = fresh_srv.submit(second, max_new_tokens=6)
    fresh_srv.run_until_idle()
    fresh_srv.close()
    assert list(reused.token_ids) == list(fresh.result().token_ids)
    assert reused.score == fresh.result().score


# ---------------------------------------------------------------------
# two kinds of cache in one manager, and what refuses a state
# ---------------------------------------------------------------------

def _state_geometry(h=4, d=16, ch=192):
    return {"state": ((h, d, d), jnp.float32), "conv": ((3, ch), None)}


def test_cache_holds_block_layers_and_state_layers_side_by_side():
    geo = [(2, 32), _state_geometry(), _state_geometry()]
    cache = PagedKVCache(3, 4, 16, 9, block_size=8, dtype=jnp.bfloat16,
                         num_kv_heads=2, geometry=geo, num_slots=5)
    assert cache.state_layers == [1, 2] and not cache.latent
    assert cache.pools[0]["kv"].shape == (9, 2, 8, 32)
    for i in (1, 2):
        assert cache.pools[i]["state"].shape == (5, 4, 16, 16)
        assert cache.pools[i]["state"].dtype == jnp.float32
        assert cache.pools[i]["conv"].shape == (5, 3, 192)
        assert cache.pools[i]["conv"].dtype == jnp.bfloat16
    assert cache.layer_shapes == [(9, 2, 8, 32), None, None]
    state_bytes = 2 * 5 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert cache.state_bytes() == state_bytes
    assert cache.pool_bytes() == 9 * 2 * 8 * 32 * 2 + state_bytes
    assert cache.dense_pool_bytes() == cache.pool_bytes()
    assert cache.scale_bytes() == 0
    # the allocator and the tables are as they were
    blocks_ = cache.allocate(3)
    assert len(blocks_) == 3 and cache.num_used == 3
    cache.free(blocks_)
    plain = PagedKVCache(3, 4, 16, 9, block_size=8)
    assert plain.state_layers == [] and plain.state_bytes() == 0
    with pytest.raises(ValueError, match="lane count"):
        PagedKVCache(3, 4, 16, 9, num_kv_heads=2, geometry=geo)


@pytest.mark.parametrize("rewriter,args", [
    ("cow_copy", (1, 2)), ("serialize_block", (1,)),
    ("deserialize_block", (1, {}, [])), ("enable_host_tier", (2,)),
    ("spill_block", (1,)), ("swap_in_block", (0, 1)),
    ("adopt_block_from", (None, 1, 2))])
def test_every_block_rewriter_refuses_a_cache_with_a_state(rewriter, args):
    cache = PagedKVCache(2, 4, 16, 9, block_size=8, num_kv_heads=2,
                         geometry=[(2, 32), _state_geometry()],
                         num_slots=2)
    with pytest.raises(NotImplementedError, match="ROADMAP R5") as e:
        getattr(cache, rewriter)(*args)
    assert "state layer" in str(e.value)


def _refusal_cases():
    from jax.sharding import Mesh
    draft_cfg = linear_moe_tiny()
    return {
        "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
        "host_tier": (dict(host_kv_blocks=4), "host tier and preemption"),
        "spec_decode": (lambda: dict(spec=SpecDecodeConfig(
            LinearMoEServingModel(init_params(draft_cfg, 1), draft_cfg),
            k=2)), "speculative decoding"),
        "mesh": (lambda: dict(mesh=Mesh(np.array(jax.devices()[:1]),
                                        ("tp",))), "a mesh"),
        "int8": (dict(kv_dtype="int8"), "int8 pools"),
    }


@pytest.mark.parametrize("case", ["prefix_cache", "host_tier",
                                  "spec_decode", "mesh", "int8"])
def test_the_server_refuses_by_name_what_cannot_carry_a_state(case):
    cfg = linear_moe_tiny()
    model = LinearMoEServingModel(init_params(cfg, 0), cfg)
    kw, names = _refusal_cases()[case]
    kw = kw() if callable(kw) else kw
    with pytest.raises(NotImplementedError) as e:
        _server(model, **kw)
    msg = str(e.value)
    assert names in msg and "state layer" in msg and "ROADMAP R5" in msg


def test_chain_handoff_and_fork_groups_refuse_a_state_by_name():
    from paddle_tpu.serving import worker
    cfg = linear_moe_tiny()
    model = LinearMoEServingModel(init_params(cfg, 0), cfg)
    srv = _server(model)
    try:
        for call, args in ((worker.export_chain, (np.arange(8), [])),
                           (worker.import_chain, ([], []))):
            with pytest.raises(NotImplementedError,
                               match="chain handoff") as e:
                call(srv, *args)
            assert "ROADMAP R5" in str(e.value)
        with pytest.raises(NotImplementedError, match="fork groups") as e:
            srv.submit(np.arange(1, 9), max_new_tokens=4, n=2)
        assert "ROADMAP R5" in str(e.value)
    finally:
        srv.close()


# ---------------------------------------------------------------------
# the expert layer's share at this family's counts
# ---------------------------------------------------------------------

@pytest.mark.parametrize("held", [4, 1], ids=["16_of_which_4",
                                              "16_of_which_1"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(held):
    """The sixteen shares of a 320 / 20 layer, tiny: 16 / 4 (four
    shares) and 16 / 1 (sixteen), the shared expert counted once."""
    whole = linear_moe_tiny()
    lp = init_params(whole, 4)["l1"]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lp, 4, 1.0, True, 0)     # uncut reference
        shared = ref._gated(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    live = jnp.ones((h.shape[0],), bool)
    total = jnp.zeros_like(h)
    counts = np.zeros(4, np.int64)
    for k in range(16 // held):
        cfg = linear_moe_tiny(n_routed_experts_held=held,
                              expert_offset=k * held)
        lp_k = init_params(cfg, 4)["l1"]
        # a share holds the same tensors the uncut layer holds there
        np.testing.assert_array_equal(
            np.asarray(lp_k["exp_gu"]),
            np.asarray(lp["exp_gu"][k * held:(k + 1) * held]))
        part, stats = moe.expert_share(
            h, lp_k, live, k=4, scaling=1.0, normalize=True,
            offset=k * held)
        total = total + (part - shared)     # the shared expert once
        counts += np.asarray(stats)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), atol=2e-5)
    # every assignment was held by exactly one share
    assert counts[1] == h.shape[0] * 4
    assert counts[0] == (16 // held) * h.shape[0] * 4


@pytest.mark.parametrize("budget,slices", [(1 << 30, 1), (800 << 10, 2),
                                           (400 << 10, 4)])
def test_an_expert_taken_in_slices_of_its_inner_width_is_the_same(
        monkeypatch, budget, slices):
    r = np.random.RandomState(0)
    t, h, inner, e = 24, 128, 512, 3
    x = jnp.asarray(r.randn(t, h), jnp.float32)
    sel = jnp.asarray(r.rand(t, e) < 0.4)
    comb = jnp.asarray(r.rand(t, e), jnp.float32)
    gu = jnp.asarray(r.randn(e, h, 2 * inner) * 0.1, jnp.float32)
    down = jnp.asarray(r.randn(e, inner, h) * 0.1, jnp.float32)
    monkeypatch.setattr(moe_kernel, "WHOLE_EXPERT_VMEM_BYTES", budget)
    assert moe_kernel._inner_blocks(h, inner, jnp.float32) == slices
    # a tile of its own per budget: one compiled call a case
    got = moe_kernel.moe_experts(x, sel, comb, gu, down, tile=8 * slices)
    want = sum(
        jnp.where(sel[:, k:k + 1], comb[:, k:k + 1], 0)
        * ((jax.nn.silu(x @ gu[k][:, :inner]) * (x @ gu[k][:, inner:]))
           @ down[k]) for k in range(e))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_blocking_holds_both_cells_widths():
    # JoyAI's expert arrives whole (its grid is what it was); this
    # family's 31.5 MB expert arrives in two slices of 640
    assert moe_kernel._inner_blocks(2048, 768, jnp.bfloat16) == 1
    assert moe_kernel._inner_blocks(4096, 1280, jnp.bfloat16) == 2
