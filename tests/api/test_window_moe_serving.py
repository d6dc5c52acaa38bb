"""The window / mixture-of-experts family through the serving stack, at
a tiny size on the CPU with the kernels interpreted: the program
against the plain reference in LOGITS (prefill in chunks, then
decoding, through the full layers' blocks and the window layers'
rings, past the window with the rings wrapped), a reused lane, the
expert layer's shares under softmax routing, ReLU-gated experts, the
other families' steps unchanged, what refuses a model with a ring, and
the counts and gauge a step reports.
"""

import copy
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import smallthinker as ref
from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                            init_params, param_shapes,
                                            smallthinker_tiny)
from paddle_tpu.ops.pallas import moe as moe_kernel
from paddle_tpu.serving import (GenerationServer, SmallThinkerServingModel,
                                SpecDecodeConfig)
from paddle_tpu.serving import blocks, moe
from paddle_tpu.serving.kv_cache import PagedKVCache


def _tiny(**kw):
    # weights wide enough that the logits are not flat at 64 wide
    return smallthinker_tiny(initializer_range=0.1, **kw)


def _perturbed(cfg, seed, dtype=jnp.float32):
    """Seeded parameters with norm scales moved off one, so that a
    program that dropped a scale would not pass."""
    params = init_params(cfg, seed, dtype)
    key = jax.random.PRNGKey(seed + 100)

    def bump(path, a):
        if not path[-1].key.endswith("_s"):
            return a
        # a stable hash: `hash` of a str differs from process to process
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        return (a.astype(jnp.float32)
                + 0.2 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(bump, params)


def _server(model, **kw):
    kw = {"num_slots": 3, "chunk": 4, "block_size": 8, "max_context": 128,
          "start": False, **kw}
    return GenerationServer(model, **kw)


def _scores(model, cfg, prompts, n_new, window=None, **kw):
    """Serve `prompts` on one server; for each, the engine's result and
    the reference's rows over its generated tokens (the reference's
    window `window` where given, the configuration's otherwise)."""
    srv = _server(model, **kw)
    futs = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    srv.run_until_idle()
    srv.close()
    ref_cfg = cfg
    if window is not None:
        ref_cfg = copy.copy(cfg)
        ref_cfg.sliding_window = window
    out = []
    for p, f in zip(prompts, futs):
        r = f.result()
        ids = np.concatenate([p, r.token_ids])
        rows = ref.forward_logprobs(model.params, ref_cfg, ids, pad_to=128,
                                    first_row=len(p) - 1, n_rows=n_new)
        out.append((r, rows))
    return out


# ---------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------

PROMPTS = [np.arange(1, 40), np.arange(40, 43),
           np.arange(7, 67) % 250 + 1, np.arange(100, 109),
           np.arange(3, 100) % 251 + 2]


@pytest.mark.parametrize("dtype,tol", [
    ("float32", 5e-4),
    # bf16 program against float32 over the same bf16-rounded weights:
    # worst score gap measured here 0.011 nats a token (seeds 2, 7)
    ("bfloat16", 0.1),
])
def test_engine_matches_reference_past_the_window_with_rings_wrapped(
        dtype, tol):
    """Five requests over three lanes, contexts 12 to 103 against a
    window of 16 and rings of 4 blocks of 8 (32 positions): every
    window layer's ring wraps, two lanes are reused. Each request's
    score is the reference's, and (float32) every token is the
    reference's best. The same run against the reference with the
    window taken off is far off: the window is doing work here."""
    cfg = _tiny(n_routed_experts_held=8, expert_offset=4)
    model = SmallThinkerServingModel(_perturbed(cfg, 2, getattr(
        jnp, dtype)), cfg)
    got = _scores(model, cfg, PROMPTS, n_new=6)
    for r, rows in got:
        chosen = rows[np.arange(6), np.asarray(r.token_ids)]
        assert abs(chosen.sum() - r.score) / 6 < tol
        if dtype == "float32":
            assert (rows.max(-1) - chosen).max() < 1e-4
    if dtype == "float32":
        far = _scores(model, cfg, PROMPTS[4:], n_new=6, window=1 << 20)
        r, rows = far[0]
        chosen = rows[np.arange(6), np.asarray(r.token_ids)]
        assert abs(chosen.sum() - r.score) / 6 > 100 * tol


def test_a_reused_lane_reads_none_of_the_last_requests_keys():
    """One lane: a long request wraps every ring, every ring block is
    then overwritten with a huge finite value (the old request's keys
    and whatever else), and the next request, which writes its own
    positions before it reads them, reads exactly what it reads on a
    fresh server."""
    cfg = _tiny()
    model = SmallThinkerServingModel(_perturbed(cfg, 4), cfg)
    first, second = np.arange(3, 90) % 250 + 1, np.arange(90, 101)
    srv = _server(model, num_slots=1)
    a = srv.submit(first, max_new_tokens=4)
    srv.run_until_idle()
    assert len(a.result().token_ids) == 4
    with srv.cache.pools_lock:
        pools = list(srv.cache.pools)
        for i in srv.cache.ring_layers:
            pools[i] = {"kv": pools[i]["kv"].at[1:].set(1e4)}
        srv.cache.pools = pools
    b = srv.submit(second, max_new_tokens=6)
    srv.run_until_idle()
    reused = b.result()
    srv.close()
    fresh_srv = _server(model, num_slots=1)
    fresh = fresh_srv.submit(second, max_new_tokens=6)
    fresh_srv.run_until_idle()
    fresh_srv.close()
    assert list(reused.token_ids) == list(fresh.result().token_ids)
    assert reused.score == fresh.result().score


def test_the_spec_follows_window_layers_and_the_cache_holds_both():
    assert set(blocks.ATTENTIONS) >= {"gqa", "gqa_window", "gqa_gated"}
    assert blocks.RING_ATTENTIONS == ("gqa_window",)
    cfg = _tiny()
    model = SmallThinkerServingModel(init_params(cfg, 0), cfg)
    spec = model.step_spec()
    assert [l.attention for l in spec.layers] == [
        "gqa", "gqa_window", "gqa_window", "gqa_window"] * 2
    assert {l.mlp for l in spec.layers} == {"experts"}
    assert spec.dims["scoring"] == "softmax"
    assert spec.dims["expert_act"] == "relu"
    assert spec.dims["router_input"] == "attention"
    assert spec.positions == "rotary" and not spec.tied_head
    # the published pattern: every layer but each fourth is a window
    assert SmallThinkerConfig().window_layers == tuple(
        i for i in range(52) if i % 4)
    assert set(param_shapes(cfg)) == {"top", "layer"}
    srv = _server(model)
    try:
        cache = srv.cache
        assert cache.ring_layers == [1, 2, 3, 5, 6, 7]
        assert cache.state_layers == []
        # a ring of 4 blocks a lane of 3 lanes, and the NULL block
        assert cache.pools[1]["kv"].shape == (13, 2, 8, 32)
        assert cache.pools[0]["kv"].shape == (cache.num_blocks, 2, 8, 32)
        ring = 6 * 13 * 2 * 8 * 32 * 4
        assert cache.ring_bytes() == ring
        assert cache.pool_bytes() == \
            2 * cache.num_blocks * 2 * 8 * 32 * 4 + ring
        # the allocator hands out the full layers' blocks only
        assert cache.usable_blocks == cache.num_blocks - 1
        from paddle_tpu.observability.metrics import global_registry
        assert global_registry().gauge(
            "serving.kv.window_ring_bytes").labels(
                server=srv._ledger_id).value() == ring
    finally:
        srv.close()


def test_config_refuses_unknown_fields_bad_layers_and_bad_shares():
    with pytest.raises(TypeError, match="no field"):
        SmallThinkerConfig(hidden=3)
    with pytest.raises(ValueError, match="not among"):
        SmallThinkerConfig(n_routed_experts_held=8, expert_offset=60)
    with pytest.raises(ValueError, match="window_layers"):
        SmallThinkerConfig(num_layers=4, window_layers=(1, 4))


def test_rotate_half_is_interleaved_under_a_permutation():
    """The half-split pair (i, i + d/2) is the interleaved pair (2i,
    2i + 1) once the channels are reordered."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(3, 5, 16), jnp.float32)
    cos, sin = blocks.rotary_angles(jnp.arange(3)[:, None] * 7, 16, 1e4)
    perm = np.stack([np.arange(8), np.arange(8, 16)], -1).reshape(-1)
    half = blocks.rotate_half(x, cos, sin)
    inter = blocks.rotate_interleaved(x[..., perm], cos, sin)
    np.testing.assert_allclose(np.asarray(half)[..., perm],
                               np.asarray(inter), atol=1e-6)


# ---------------------------------------------------------------------
# through the engine: counts, the kernels, one signature
# ---------------------------------------------------------------------

def test_iterations_count_the_window_walks():
    from paddle_tpu.observability.tracing import get_recorder
    cfg = _tiny(n_routed_experts_held=4, expert_offset=12)
    model = SmallThinkerServingModel(_perturbed(cfg, 3), cfg)
    rec = get_recorder()
    rec.start()
    srv = _server(model)
    futs = [srv.submit(p, max_new_tokens=3) for p in PROMPTS[:3]]
    srv.run_until_idle()
    rec.stop()
    spans = [e["args"] for e in rec.events()
             if e.get("name") == "serving.iteration"]
    rec.clear()
    st = srv.get_stats()
    srv.close()
    assert all(f.result() for f in futs)
    assert st["fused_step_signatures"] == 1
    assert st["kernel"]["name"] == \
        "paged_attention_v1+paged_window_attention"
    assert st["kernel"]["kernel_dispatches"] == cfg.num_layers
    assert st["kernel"]["version"] == "v1"
    assert spans
    for a in spans:
        # six window layers; a lane's walk needs at most window + its
        # valid columns - 1 keys a layer, and at least its columns
        assert a["window_lane_calls"] == 6 * a["lanes"]
        assert 6 * a["valid_columns"] <= a["window_key_positions"] <= \
            6 * (a["lanes"] * 15 + a["valid_columns"])
        assert a["moe_assignments"] == a["valid_columns"] * 4 * 8
    # a long prompt's chunks past the window read 16 + 3 keys a layer
    assert any(a["window_key_positions"] == 6 * 19 * a["lanes"]
               for a in spans if a["valid_columns"] == 4 * a["lanes"])


def test_window_counts_of_a_step():
    spec = blocks.StepSpec(layers=(), positions="none", tied_head=False,
                           heads=1, kv_heads=1, head_dim=1, norm_eps=0,
                           dims={"window": 10})
    pos = jnp.asarray([[0, 1, 2, 3], [20, 21, 0, 0], [0, 0, 0, 0],
                       [7, 8, 9, 10]], jnp.int32)
    counts = jnp.asarray([4, 2, 0, 4], jnp.int32)
    ctx = blocks.StepContext(spec, 4, 4, jnp.float32, pos, None, None,
                             None, None, None, False, None, None, counts,
                             None)
    got = np.asarray(blocks.window_counts(ctx, 3))
    # keys 0-3, 11-21, none, 0-10: 4 + 11 + 11
    np.testing.assert_array_equal(got, [3 * 3, 3 * 26])


# ---------------------------------------------------------------------
# the expert layer: softmax routing, its shares, ReLU-gated experts
# ---------------------------------------------------------------------

def test_the_shares_of_a_softmax_routed_layer_add_up_to_the_uncut_layer():
    """Four shares of 16 experts (offsets 0, 4, 8, 12), the router on
    an input of its own: they add up to the reference's uncut layer,
    and every assignment is held by exactly one share."""
    whole = smallthinker_tiny()
    lp = init_params(whole, 4)["l1"]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, whole.hidden_size))
    y = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, y, lp, 24, 4, True, 0)
    live = jnp.ones((24,), bool)
    total = jnp.zeros_like(y)
    counts = np.zeros(4, np.int64)
    for off in (0, 4, 8, 12):
        cfg = smallthinker_tiny(n_routed_experts_held=4,
                                expert_offset=off)
        lp_k = init_params(cfg, 4)["l1"]
        np.testing.assert_array_equal(np.asarray(lp_k["exp_gu"]),
                                      np.asarray(lp["exp_gu"][off:off + 4]))
        part, stats = moe.expert_share(
            y, lp_k, live, k=4, scaling=1.0, normalize=True, offset=off,
            scoring="softmax", activation="relu", router_h=h)
        total = total + part
        counts += np.asarray(stats)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    assert counts[1] == 24 * 4 and counts[0] == 4 * 24 * 4


def test_softmax_routing_weighs_the_chosen_by_their_share():
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    ids, w = moe.route(h, w_r, None, 2, 1.0, True, "softmax")
    r = np.asarray(jax.nn.softmax(
        np.asarray(h, np.float64) @ np.asarray(w_r, np.float64), -1))
    want = np.argsort(-r, -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                  np.sort(want, -1))
    top = np.take_along_axis(r, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(w), top / top.sum(-1,
                                                            keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(h, w_r, None, 2, 1.0, True, "tanh")


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_experts_kernel_gates_by_its_activation(activation):
    r = np.random.RandomState(1)
    t, hid, inner, e = 24, 128, 256, 3
    x = jnp.asarray(r.randn(t, hid), jnp.float32)
    sel = jnp.asarray(r.rand(t, e) < 0.4)
    comb = jnp.asarray(r.rand(t, e), jnp.float32)
    gu = jnp.asarray(r.randn(e, hid, 2 * inner) * 0.1, jnp.float32)
    down = jnp.asarray(r.randn(e, inner, hid) * 0.1, jnp.float32)
    got = moe_kernel.moe_experts(x, sel, comb, gu, down, tile=8,
                                 activation=activation)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    want = sum(
        jnp.where(sel[:, k:k + 1], comb[:, k:k + 1], 0)
        * ((act(x @ gu[k][:, :inner]) * (x @ gu[k][:, inner:]))
           @ down[k]) for k in range(e))
    np.testing.assert_allclose(got, want, atol=1e-4)
    with pytest.raises(ValueError, match="activation"):
        moe_kernel.moe_experts(x, sel, comb, gu, down, activation="gelu")


# ---------------------------------------------------------------------
# the other families' steps are the program they were
# ---------------------------------------------------------------------

# what a fixed-seed stream gave on each of the other families before
# window layers, softmax routing and ReLU-gated experts were added beside
# sigmoid routing and SiLU: a greedy and a sampled request on one
# server, their token ids and scores. A deliberate change to these
# families' numbers updates them.
STREAMS = {
    ("gpt", "auto"): {
        "greedy": ([221, 221, 84, 139, 139], -18.41494846343994),
        "sampled": ([194, 69, 64, 78, 3], -14.798517942428589)},
    ("joyai", "auto"): {
        "greedy": ([241, 9, 40, 241, 9], -25.662625789642334),
        "sampled": ([108, 230, 172, 180, 14], -14.339096546173096)},
    # a table of two groups of 128 key positions and a greedy prompt of
    # 140 tokens: the latent walk's carry crosses from one group into
    # the next in prefill and in decode, and chunks past 128 see their
    # first group whole. Taken while the walk held its running max and
    # exp-sum as (rows, 1) columns; the lane-wide carry streams the same
    # bits
    ("joyai_long", "auto"): {
        "greedy": ([210, 1, 247, 233, 68], -25.523290634155273),
        "sampled": ([108, 230, 172, 180, 14], -14.339096546173096)},
    ("solar", "auto"): {
        "greedy": ([254, 238, 111, 254, 99], -25.523123741149902),
        "sampled": ([242, 219, 195, 198, 233], -14.473012685775757)},
    ("solar", "v2"): {
        "greedy": ([254, 238, 111, 254, 99], -25.523122787475586),
        "sampled": ([242, 219, 195, 198, 233], -14.473013401031494)},
}


def _tiny_gpt():
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GPTServingModel
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor().run(startup)
    return GPTServingModel(gpt.load_params(scope, cfg), cfg)


_STREAM_RUNS = {}


def _stream(family, mode):
    """A greedy and a sampled request of `family`'s tiny model on one
    server under kernel mode `mode`: {kind: (token ids, score)}, run
    once a (family, mode)."""
    if (family, mode) in _STREAM_RUNS:
        return _STREAM_RUNS[family, mode]
    from paddle_tpu.models.latent_moe import latent_moe_tiny
    from paddle_tpu.models.latent_moe import init_params as latent_init
    from paddle_tpu.models.linear_moe import linear_moe_tiny
    from paddle_tpu.models.linear_moe import init_params as linear_init
    from paddle_tpu.serving import (LatentMoEServingModel,
                                    LinearMoEServingModel)
    from paddle_tpu.serving.decode_strategies import SamplingParams
    max_context, prompt = 64, np.arange(1, 12)
    if family == "gpt":
        model = _tiny_gpt()
    elif family == "joyai":
        cfg = latent_moe_tiny(n_routed_experts_held=4, expert_offset=8)
        model = LatentMoEServingModel(latent_init(cfg, 1), cfg)
    elif family == "joyai_long":
        cfg = latent_moe_tiny(n_routed_experts_held=4, expert_offset=8,
                              max_position=256)
        model = LatentMoEServingModel(latent_init(cfg, 1), cfg)
        max_context, prompt = 256, np.arange(1, 141)
    else:
        cfg = linear_moe_tiny(n_routed_experts_held=4, expert_offset=4)
        model = LinearMoEServingModel(linear_init(cfg, 1), cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_TPU_PAGED_KERNEL", mode)
        srv = _server(model, num_slots=2, chunk=4, block_size=8,
                      max_context=max_context)
        greedy = srv.submit(prompt, max_new_tokens=5)
        sampled = srv.submit(
            np.arange(20, 29), max_new_tokens=5,
            sampling=SamplingParams(temperature=0.8, top_k=20, top_p=0.9,
                                    seed=3))
        srv.run_until_idle()
        srv.close()
    _STREAM_RUNS[family, mode] = {
        kind: ([int(t) for t in f.result().token_ids],
               float(f.result().score))
        for kind, f in (("greedy", greedy), ("sampled", sampled))}
    return _STREAM_RUNS[family, mode]


@pytest.mark.parametrize("family,mode,kind", [
    (family, mode, kind) for family, mode in sorted(STREAMS)
    for kind in ("greedy", "sampled")])
def test_sigmoid_silu_families_stream_what_they_streamed(family, mode,
                                                         kind):
    """GPT-2's, JoyAI's and Solar's fused steps (the layer loop that
    now hands each MLP kind the attention's input; sigmoid routing,
    SiLU-gated experts, Solar's gated full attention through v1 and
    v2), greedy and sampled, stream the token ids they streamed and
    score them bit for bit as they did."""
    assert _stream(family, mode)[kind] == STREAMS[family, mode][kind]


# ---------------------------------------------------------------------
# what refuses a ring, by name
# ---------------------------------------------------------------------

def _model():
    cfg = smallthinker_tiny()
    return SmallThinkerServingModel(init_params(cfg, 0), cfg)


def _refusal_cases():
    from jax.sharding import Mesh
    draft_cfg = smallthinker_tiny()
    return {
        "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
        "host_tier": (dict(host_kv_blocks=4), "host tier and preemption"),
        "spec_decode": (lambda: dict(spec=SpecDecodeConfig(
            SmallThinkerServingModel(init_params(draft_cfg, 1), draft_cfg),
            k=2)), "speculative decoding"),
        "mesh": (lambda: dict(mesh=Mesh(np.array(jax.devices()[:1]),
                                        ("tp",))), "a mesh"),
        "int8": (dict(kv_dtype="int8"), "int8 pools"),
    }


@pytest.mark.parametrize("case", ["prefix_cache", "host_tier",
                                  "spec_decode", "mesh", "int8"])
def test_the_server_refuses_by_name_what_cannot_carry_a_ring(case):
    kw, names = _refusal_cases()[case]
    kw = kw() if callable(kw) else kw
    with pytest.raises(NotImplementedError) as e:
        _server(_model(), **kw)
    msg = str(e.value)
    assert names in msg and "window ring" in msg and \
        "SmallThinkerServingModel" in msg and "ROADMAP R3" in msg


@pytest.mark.parametrize("rewriter,args", [
    ("cow_copy", (1, 2)), ("serialize_block", (1,)),
    ("deserialize_block", (1, {}, [])), ("enable_host_tier", (2,)),
    ("spill_block", (1,)), ("swap_in_block", (0, 1)),
    ("adopt_block_from", (None, 1, 2))])
def test_every_block_rewriter_refuses_a_cache_with_a_ring(rewriter, args):
    cache = PagedKVCache(2, 4, 16, 9, block_size=8, num_kv_heads=2,
                         geometry=[(2, 32), {"window": 16,
                                             "ring_blocks": 4}],
                         num_slots=2)
    with pytest.raises(NotImplementedError, match="ROADMAP R3") as e:
        getattr(cache, rewriter)(*args)
    assert "window ring" in str(e.value)


def test_chain_handoff_and_fork_groups_refuse_a_ring_by_name():
    from paddle_tpu.serving import worker
    srv = _server(_model())
    try:
        for call, args in ((worker.export_chain, (np.arange(8), [])),
                           (worker.import_chain, ([], []))):
            with pytest.raises(NotImplementedError,
                               match="chain handoff") as e:
                call(srv, *args)
            assert "window ring" in str(e.value)
        with pytest.raises(NotImplementedError, match="fork groups") as e:
            srv.submit(np.arange(1, 9), max_new_tokens=4, n=2)
        assert "window ring" in str(e.value)
    finally:
        srv.close()
