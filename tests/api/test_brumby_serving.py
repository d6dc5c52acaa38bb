"""The power-retention family through the serving stack, at a tiny size
on the CPU with the kernel interpreted: the program against the plain
reference (the ATTENTION form) in LOGITS, prefill in chunks and then
decoding through the state; the chunked kernel and its `jax.numpy`
form against the rule token by token and, over many chunks, against
the attention form; the expansion's layout; a cache with no paged
layer at all; the state's reset, an idle lane's state and the counts a
step reports; what refuses the model.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import brumby as ref
from paddle_tpu.models.brumby import (BrumbyConfig, brumby_tiny,
                                      gate_bias, init_params)
from paddle_tpu.ops.pallas import retention
from paddle_tpu.serving import (BrumbyServingModel, GenerationServer,
                                SpecDecodeConfig)
from paddle_tpu.serving import blocks, kv_cache as kvc
from paddle_tpu.serving.kv_cache import PagedKVCache


def _tiny(**kw):
    # weights wide enough that the logits are not flat at 64 wide
    return brumby_tiny(initializer_range=0.15, **kw)


def _params(cfg, seed, dtype=jnp.float32):
    """Seeded parameters with the norm scales moved off one, so that a
    program that dropped a scale would not pass."""
    params = init_params(cfg, seed, dtype)
    key = jax.random.PRNGKey(seed + 100)
    for i in range(cfg.num_layers):
        lp = params[f"l{i}"]
        for j, name in enumerate(sorted(n for n in lp if n.endswith("_s"))):
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            lp[name] = (lp[name].astype(jnp.float32) + 0.2
                        * jax.random.normal(k, lp[name].shape)
                        ).astype(dtype)
    return params


def _drive(model, prompt, n_new, chunk=4, block_size=8, max_context=64):
    """Prefill `prompt` in chunks, then decode `n_new` greedy tokens
    through a cache whose every layer is a state, with the model's own
    fused step (the sampling variant, whose log-prob rows come before
    the step's counts). Lane 1 stays idle over a state that is not
    zero. Returns ({position: row}, ids, the final pools, the pools the
    cache started with)."""
    s = 2
    m = max_context // block_size
    cache = PagedKVCache(model.num_layers, model.num_heads,
                         model.head_dim, 2 * m + 1, block_size=block_size,
                         dtype=model.kv_dtype,
                         num_kv_heads=model.num_kv_heads,
                         geometry=model.kv_geometry, num_slots=s)
    for i in cache.state_layers:
        cache.pools[i] = {
            n: a.at[1].set(jnp.arange(a[1].size, dtype=jnp.float32)
                           .reshape(a[1].shape) * 1e-3)
            for n, a in cache.pools[i].items()}
    before = jax.tree_util.tree_map(np.asarray, cache.pools)
    tables = np.stack([cache.make_table(cache.allocate(m), m),
                       cache.make_table([], m)])
    fused = jax.jit(model.build_fused_step(block_size, sampling=True))
    v = model.cfg.vocab_size
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
    # worst gap over a whole row measured here 0.11 nats (seeds 5, 11;
    # weights at 0.15, so the logits are far from flat)
    ("bfloat16", 0.35),
])
def test_chunked_prefill_then_decode_matches_reference_logits(dtype, tol):
    cfg = _tiny()
    for seed in (5, 11):
        params = _params(cfg, seed, getattr(jnp, dtype))
        model = BrumbyServingModel(params, cfg)
        # 19 tokens: the prompt ends mid-chunk (4) and mid-block (8)
        prompt = np.random.RandomState(seed).randint(1, 256, size=19)
        rows, out_ids, pools, before = _drive(model, prompt, n_new=6)
        ids = np.concatenate([prompt, out_ids])
        want = ref.forward_logprobs(params, cfg, ids, pad_to=64)
        assert sorted(rows) == [3, 7, 11, 15, 18, 19, 20, 21, 22, 23]
        for t, row in rows.items():
            assert np.abs(row - want[t]).max() < tol, (seed, t)
        if dtype == "float32":
            # the greedy choice is the reference's argmax
            assert out_ids == [int(want[t].argmax())
                               for t in range(18, 24)]
        # the idle lane's state and normaliser are bitwise unchanged,
        # the busy lane's moved
        for i in range(cfg.num_layers):
            for name in ("state", "norm"):
                np.testing.assert_array_equal(pools[i][name][1],
                                              before[i][name][1])
                assert np.abs(pools[i][name][0]).max() > 0


# ---------------------------------------------------------------------
# the expansion and the kernel
# ---------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 16, 128])
def test_the_expansion_gives_the_square_of_the_product(d):
    rows = retention.state_rows(d)
    assert rows % 128 == 0 and rows - 128 < d * (d + 1) // 2 <= rows
    left, right, weight = (np.asarray(a) for a in
                           retention.expansion_layout(d))
    live = left >= 0
    assert live.sum() == d * (d + 1) // 2
    # every pair a <= b once, in row-major order; padding weighs nothing
    pairs = list(zip(left[live], right[live]))
    assert pairs == [(a, b) for a in range(d) for b in range(a, d)]
    assert (weight[~live] == 0).all()
    assert set(weight[live]) == {1.0, 2.0}
    x, y = np.random.RandomState(d).randn(2, 5, d)
    got = np.sum(np.asarray(retention.expand(jnp.asarray(x), "query"))
                 * np.asarray(retention.expand(jnp.asarray(y), "key")),
                 axis=-1)
    # float32 sums of d (d + 1) / 2 terms of either sign
    scale = np.sum(x * x, -1) * np.sum(y * y, -1)
    assert (np.abs(got - np.sum(x * y, -1) ** 2) <= 1e-6 * scale).all()


def _chunk_case(c, seed=0, s=6, h=4, kv=2, d=16):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.randn(s, c, h, d))
    k = unit(rng.randn(s, c, kv, d)) * d ** 0.5
    v = rng.randn(s, c, kv, d)
    a = -np.exp(rng.uniform(np.log(4e-5), np.log(0.7), (s, c, kv)))
    rows = retention.state_rows(d)
    # a carried state that is a state: a chunk of other tokens from zero
    state = np.zeros((s, kv, rows, d))
    norm = np.zeros((s, kv, rows))
    k0 = unit(rng.randn(8, kv, d)) * d ** 0.5
    for t in range(8):
        phi = np.asarray(retention.expand(jnp.asarray(k0[t]), "key"))
        v0 = rng.randn(s, kv, d)
        state = state * 0.9 + phi[None, ..., None] * v0[:, :, None]
        norm = norm * 0.9 + phi[None]
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, a, state, norm)]


@pytest.mark.parametrize("d", [16, 36], ids=["one_tile", "two_tiles"])
@pytest.mark.parametrize("c,counts,reset", [
    (1, [1, 0, 1, 1, 0, 1], [0, 0, 1, 0, 0, 0]),
    (4, [4, 0, 3, 1, 0, 2], [1, 0, 0, 1, 0, 0]),
    (16, [16, 0, 15, 1, 7, 0], [0, 0, 0, 1, 1, 0]),
    (16, [0, 0, 0, 0, 0, 5], [0, 0, 0, 0, 0, 1]),
    (4, [0] * 6, [0] * 6)],
    ids=["decode", "chunks", "full_chunks", "one_busy_last", "all_idle"])
def test_power_retention_chunk_and_its_jnp_form_are_the_rule(
        c, counts, reset, d):
    """At d 16 the state's 256 rows are one grid step; at d 36 its 768
    rows are two of 384 (`tile_rows`), so a lane's read accumulates
    across steps."""
    args = _chunk_case(c, d=d)
    counts = jnp.asarray(counts, jnp.int32)
    reset = jnp.asarray(reset, bool)
    got = retention.power_retention_chunk(*args, counts, reset)
    with jax.default_matmul_precision("highest"):
        rule = retention.power_retention_recurrence(*args, counts, reset)
        jnp_form = retention.power_retention_chunk_reference(
            *args, counts, reset)
    live = (np.arange(c)[None] < np.asarray(counts)[:, None])[
        ..., None, None]
    for want in (rule, jnp_form):
        np.testing.assert_allclose(np.where(live, got[0], 0),
                                   np.where(live, want[0], 0),
                                   atol=5e-4, rtol=1e-4)
        for i in (1, 2):
            np.testing.assert_allclose(got[i], want[i], atol=2e-4,
                                       rtol=1e-5)
    idle = np.asarray(counts) == 0
    for i in (1, 2):
        assert np.array_equal(np.asarray(got[i])[idle],
                              np.asarray(args[3 + i])[idle])


def _attention_form(q, k, v, a, d_scale=1.0):
    """o_t = sum_{s<=t} exp(A_t - A_s) (q_t . k_s)^2 v_s / (sum + eps)
    over one lane's whole sequence, in numpy float64: q (T, H, d), k, v
    (T, K, d), a (T, K)."""
    t, h, _ = q.shape
    g = h // k.shape[1]
    acc = np.cumsum(a, axis=0)
    out = np.zeros(q.shape)
    for i in range(h):
        j = i // g
        w = (q[:, i] @ k[:, j].T) ** 2 * np.exp(
            np.minimum(acc[:, j, None] - acc[None, :, j], 0))
        w = np.tril(w)
        out[:, i] = (w @ v[:, j]) / (w.sum(-1, keepdims=True)
                                     + retention.EPS)
    return out


@pytest.mark.parametrize("c", [1, 3, 16])
def test_chunks_carried_through_the_state_are_the_attention_form(c):
    """A 40-token sequence in chunks of c (the last one partial) through
    the kernel from the zero state, the lane reset at its first chunk
    and its neighbour idle, equals the attention form over the whole
    sequence: the state carries exactly what the earlier chunks left."""
    t, h, kv, d = 40, 4, 2, 16
    rng = np.random.RandomState(c)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.randn(t, h, d))
    k = unit(rng.randn(t, kv, d)) * d ** 0.5
    v = rng.randn(t, kv, d)
    a = -np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (t, kv)))
    rows = retention.state_rows(d)
    state = jnp.zeros((2, kv, rows, d))
    norm = jnp.full((2, kv, rows), 3.0)     # garbage the reset discards
    outs = []
    for lo in range(0, t, c):
        n = min(c, t - lo)

        def lanes(x):
            pad = np.zeros((2, c) + x.shape[1:])
            pad[0, :n] = x[lo:lo + n]
            return jnp.asarray(pad, jnp.float32)

        o, state, norm = retention.power_retention_chunk(
            lanes(q), lanes(k), lanes(v), lanes(a), state, norm,
            jnp.asarray([n, 0], jnp.int32), jnp.asarray([lo == 0, False]))
        outs.append(np.asarray(o[0, :n]))
    np.testing.assert_allclose(np.concatenate(outs),
                               _attention_form(q, k, v, a),
                               atol=5e-5, rtol=1e-4)
    # the idle lane kept its garbage
    assert (np.asarray(norm[1]) == 3.0).all()


def test_power_dispatcher_counts_by_name_and_pins(monkeypatch):
    args = _chunk_case(4)
    counts = jnp.asarray([4, 0, 3, 1, 0, 2], jnp.int32)
    reset = jnp.zeros((6,), bool)
    k0 = dict(kvc.KERNEL_NAMES)
    v0 = dict(kvc.KERNEL_VERSIONS)
    got = kvc.power_retention_chunk(*args, counts, reset)
    assert kvc.KERNEL_NAMES["power_retention_chunk"] == \
        k0.get("power_retention_chunk", 0) + 1
    assert kvc.KERNEL_VERSIONS == v0
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    f0 = kvc.FALLBACK_REASONS.get("pinned_off", 0)
    with jax.default_matmul_precision("highest"):
        want = kvc.power_retention_chunk(*args, counts, reset)
    assert kvc.FALLBACK_REASONS["pinned_off"] == f0 + 1
    live = (np.arange(4)[None] < np.asarray(counts)[:, None])[
        ..., None, None]
    np.testing.assert_allclose(np.where(live, got[0], 0),
                               np.where(live, want[0], 0), atol=5e-4,
                               rtol=1e-4)
    for x, y in zip(got[1:], want[1:]):
        np.testing.assert_allclose(x, y, atol=2e-4, rtol=1e-5)


# ---------------------------------------------------------------------
# the family, its cache and the engine
# ---------------------------------------------------------------------

def test_the_spec_is_power_everywhere_and_the_cache_holds_no_block():
    cfg = _tiny(num_layers=3)
    model = BrumbyServingModel(init_params(cfg, 0), cfg)
    spec = model.step_spec()
    assert [tuple(l) for l in spec.layers] == [
        ("rms_norm", "power", "gated")] * 3
    assert spec.positions == "rotary" and not spec.tied_head
    assert spec.dims == {"qk_rope": 16, "rope_theta": 1e4}
    assert "power" in blocks.STATE_ATTENTIONS
    rows = retention.state_rows(16)
    assert model.kv_geometry == [
        {"state": ((2, rows, 16), jnp.float32),
         "norm": ((2, rows), jnp.float32)}] * 3
    cache = PagedKVCache(3, 4, 16, 17, block_size=8, num_kv_heads=2,
                         geometry=model.kv_geometry, num_slots=5)
    assert cache.state_layers == [0, 1, 2] and cache.ring_layers == []
    assert cache.layer_shapes == [None] * 3
    state_bytes = 3 * 5 * (2 * rows * 16 + 2 * rows) * 4
    assert cache.state_bytes() == cache.pool_bytes() == state_bytes
    assert cache.dense_pool_bytes() == state_bytes
    # the allocator still counts positions into blocks that no pool holds
    got = cache.allocate(4)
    assert len(got) == 4 and cache.num_free == 12
    cache.free(got)
    assert kvc.lane_owned(cache.geometry) == ("state layer", "ROADMAP R5")


def test_config_refuses_unknown_fields_and_spreads_the_gate():
    with pytest.raises(TypeError, match="no field"):
        BrumbyConfig(hidden=3)
    with pytest.raises(ValueError, match="do not divide"):
        BrumbyConfig(num_heads=40, num_kv_heads=7)
    cfg = BrumbyConfig()
    b = gate_bias(cfg)
    assert b.shape == (8,) and b.dtype == np.float32
    # at a zero input, head j keeps exp(logsigmoid(b_j)) a token: half
    # lives 16 ... 16,384 tokens, evenly in log
    life = np.log(0.5) / -np.logaddexp(0.0, -b.astype(np.float64))
    np.testing.assert_allclose(life, np.geomspace(16, 16384, 8),
                               rtol=1e-3)
    p = init_params(_tiny(), 0, jnp.bfloat16)
    assert p["l0"]["ba"].dtype == jnp.float32
    assert p["l0"]["wq"].dtype == jnp.bfloat16


def _server(model, **kw):
    kw = {"num_slots": 3, "chunk": 4, "block_size": 8, "max_context": 64,
          "start": False, **kw}
    return GenerationServer(model, **kw)


def test_server_scores_match_reference_and_count_the_state():
    from paddle_tpu.observability.metrics import global_registry
    from paddle_tpu.observability.tracing import get_recorder
    cfg = _tiny()
    model = BrumbyServingModel(_params(cfg, 2), cfg)
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
    assert srv.cache.pool_bytes() == srv.cache.state_bytes()
    srv.close()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"] == {
        "mode": "auto", "engaged": True, "version": None,
        "name": "power_retention_chunk",
        "kernel_dispatches": cfg.num_layers, "fallback_dispatches": 0,
        "pool_block_shape": None}
    for p, f in zip(prompts, futs):
        r = f.result()
        ids = np.concatenate([p, r.token_ids])
        rows = ref.forward_logprobs(model.params, cfg, ids, pad_to=64,
                                    first_row=len(p) - 1, n_rows=5)
        chosen = rows[np.arange(5), np.asarray(r.token_ids)]
        assert abs(chosen.sum() - r.score) < 5e-4
        assert (rows.max(-1) - chosen).max() < 1e-5
    # every iteration says what its retention layers did
    assert spans
    for e in spans:
        a = e["args"]
        assert a["retention_columns"] == 2 * a["valid_columns"]
        assert a["retention_lane_calls"] == 2 * a["lanes"]
        assert 0 <= a["state_resets"] <= a["lanes"]
    # a request is admitted once and starts once
    assert sum(e["args"]["state_resets"] for e in spans) == len(prompts)
    assert reg.counter("serving.state.resets").value() - resets0 == \
        len(prompts)
    assert reg.counter("serving.state.columns").value() - columns0 == \
        sum(e["args"]["retention_columns"] for e in spans)


def test_a_reused_lane_reads_as_a_fresh_server_does():
    cfg = _tiny()
    model = BrumbyServingModel(_params(cfg, 4), cfg)
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


def _refusal_cases():
    from jax.sharding import Mesh
    draft_cfg = brumby_tiny()
    return {
        "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
        "host_tier": (dict(host_kv_blocks=4), "host tier and preemption"),
        "spec_decode": (lambda: dict(spec=SpecDecodeConfig(
            BrumbyServingModel(init_params(draft_cfg, 1), draft_cfg),
            k=2)), "speculative decoding"),
        "mesh": (lambda: dict(mesh=Mesh(np.array(jax.devices()[:1]),
                                        ("tp",))), "a mesh"),
        "int8": (dict(kv_dtype="int8"), "int8 pools"),
    }


@pytest.mark.parametrize("case", ["prefix_cache", "host_tier",
                                  "spec_decode", "mesh", "int8"])
def test_the_server_refuses_by_name_what_cannot_carry_a_state(case):
    cfg = brumby_tiny()
    model = BrumbyServingModel(init_params(cfg, 0), cfg)
    kw, names = _refusal_cases()[case]
    kw = kw() if callable(kw) else kw
    with pytest.raises(NotImplementedError) as e:
        _server(model, **kw)
    msg = str(e.value)
    assert names in msg and "state layer" in msg and "ROADMAP R5" in msg
    assert "BrumbyServingModel" in msg
