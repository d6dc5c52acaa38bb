"""COW-forked generation: parallel sampling, paged beam search, and
guided decoding on the shared KV cache (ISSUE 20,
paddle_tpu/serving/decode_strategies.py + guided.py).

Tier-1 (`serving` marker, no sleeps — time from injected chaos clocks).
The contract under test:

- paged beam search is BITWISE-identical to the dense
  inference.decoding.beam_decode reference — ids and (to float
  tolerance) GNMT-normalized scores — across f32 and GQA models, with
  EOS landing mid-run so finished-lane masking is exercised through
  t == max_len;
- `submit(n=K)` forks K sampling lanes off ONE prefill: the group's
  peak block footprint is under half of K independent submits, lane
  streams replay deterministically (counter RNG), and every block
  (shared, COW'd, spare) is reclaimed on finish, cancel, and deadline;
- guided decoding (regex / JSON constraint automata) only ever emits
  tokens the automaton allows — replaying the emitted ids through
  `advance` never hits a violation — while the fused-step signature
  budget stays at 1;
- beam + speculative verification commits the SAME hypotheses as the
  plain beam server (greedy acceptance, one widened verify call),
  within the <= 2 compiled-signature budget, on f32 and int8 pools;
- chaos hooks: `fork_storm_at` forces COW divergence bursts and
  `mask_starve_at` degrades guided masks to a single allowed token —
  both fire deterministically and the serving loop keeps its
  invariants;
- the FleetRouter routes and FAILS OVER a fork group as a unit: one
  replica owns all K lanes, a mid-group kill replays the whole group
  on the survivor bitwise, group streams dedupe per lane rank, and
  `tenant=` billing counts every lane's tokens.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.inference import decoding as dec
from paddle_tpu.models import gpt
from paddle_tpu.robustness import ChaosInjector
from paddle_tpu.serving import (BeamParams, DeadlineExceeded,
                                FleetRouter, GenerationServer,
                                GPTServingModel, JsonConstraint,
                                RegexConstraint, RequestCancelled,
                                SamplingParams, SpecDecodeConfig)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup)
    return cfg, gpt.load_params(scope, cfg)


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _gqa_cfg(cfg, kv_heads):
    return gpt.GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        inner_size=cfg.inner_size, max_position=cfg.max_position,
        dropout=0.0, kv_heads=kv_heads)


def _dense_beam(params, cfg, prompt, n_new, K, eos, lp=0.6,
                max_len=64):
    """The dense reference: teacher-force the prompt into a K-tiled
    dense cache, then inference.decoding.beam_decode from the prompt's
    last token (start_t = P - 1). Returns (ids (K, n_new) best-first,
    normalized scores (K,))."""
    d = cfg.hidden_size // cfg.num_heads
    step = gpt.build_kv_step(params, cfg, max_len)
    cache = dec.init_kv_cache(K, cfg.num_layers, cfg.num_heads,
                              max_len, d)
    for t, tok in enumerate(prompt[:-1]):
        _, cache = step(jnp.full((K,), int(tok), jnp.int32), cache, t)
    ids, norm = dec.beam_decode(
        step, cache, jnp.asarray([int(prompt[-1])], jnp.int32),
        n_new, K, eos, length_penalty=lp, start_t=len(prompt) - 1)
    return np.asarray(ids[0]), np.asarray(norm[0])


def _char_vocab(vocab_size):
    """Token id -> string map for the char-level constraint machines:
    ids 3..12 are the digits, a few JSON structural chars follow, and
    everything else maps to characters no JSON/regex test matches."""
    special = {3: "0", 4: "1", 5: "2", 6: "3", 7: "4", 8: "5", 9: "6",
               10: "7", 11: "8", 12: "9", 13: '"', 14: "{", 15: "}",
               16: ":", 17: ",", 18: "[", 19: "]", 20: "a", 21: "b",
               22: "t", 23: "r", 24: "u", 25: "e", 26: "."}
    return [special.get(i, chr(0x4E00 + i)) for i in range(vocab_size)]


def _assert_conforms(constraint, token_ids, eos):
    """Replay the emitted ids through the automaton: every non-eos
    token must be a legal transition, and eos only lands on an
    accepting (or exhausted) state."""
    state = constraint.initial_state()
    for t in token_ids:
        t = int(t)
        if t == eos:
            assert (constraint.accepting(state)
                    or not constraint.allowed_tokens(state).any())
            return
        state = constraint.advance(state, t)
        assert state is not None, f"token {t} violates the constraint"


# ---------------------------------------------------------------------------
# params surface
# ---------------------------------------------------------------------------

def test_params_validation():
    sp = SamplingParams(n=4, temperature=0.7, top_k=20, top_p=0.9,
                        seed=3)
    assert sp.do_sample and sp.n == 4
    assert not SamplingParams(temperature=0.0).do_sample
    assert not SamplingParams(temperature=None).do_sample
    with pytest.raises(ValueError):
        SamplingParams(n=0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)


# ---------------------------------------------------------------------------
# tentpole: paged beam search bitwise vs the dense reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["f32", "gqa"])
def test_paged_beam_bitwise_matches_dense(tiny_gpt, variant,
                                          monkeypatch):
    """The acceptance matrix: the paged engine's beam hypotheses are
    BITWISE the dense scan's ids — including an eos chosen to land
    mid-run, so finished lanes keep committing eos at zero cost
    through t == max_len exactly like the dense eos_only mask. The GQA
    cell serves sliced-KV params against the repeat-KV dense model
    (exact param round trip, ISSUE 16)."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    K, n_new = 3, 6
    prompt = np.array([5, 9, 11, 2, 7], np.int32)
    if variant == "gqa":
        srv_params = gpt.gqa_slice_kv_params(params, cfg, 2)
        dense_params = gpt.gqa_repeat_kv_params(srv_params, cfg, 2)
        srv_cfg = _gqa_cfg(cfg, 2)
    else:
        srv_params, dense_params, srv_cfg = params, params, cfg
    # probe run picks an eos the search actually emits mid-run (token
    # 0 is outside the prompt alphabet and vanishingly unlikely), so
    # the comparison run covers early-finished lanes
    probe, _ = _dense_beam(dense_params, cfg, prompt, n_new, K, eos=0)
    eos = int(probe[0, 2])
    ids, norm = _dense_beam(dense_params, cfg, prompt, n_new, K, eos)

    srv = _server(srv_params, srv_cfg)
    fut = srv.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                     beam=BeamParams(K))
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert res.kind == "beam" and len(res.hypotheses) == K
    for r in range(K):
        np.testing.assert_array_equal(
            np.asarray(res.hypotheses[r].token_ids, np.int32), ids[r])
        np.testing.assert_allclose(res.hypotheses[r].norm_score,
                                   norm[r], rtol=1e-5)
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1
    assert st["group.requests"] == 1 and st["group.lanes"] == K
    assert st["blocks_free"] == st["blocks_total"]
    srv.close()


def test_beam_spec_parity(tiny_gpt):
    """Beam + speculative verification (greedy acceptance, ONE widened
    verify call per iteration) commits the same hypotheses as the
    plain beam server, on f32 and int8 pools, within the <= 2
    compiled-signature budget. The self-draft makes every proposal
    acceptable, so the spec path's multi-column beam_step chain is
    exercised hard."""
    cfg, params = tiny_gpt
    K, n_new, eos = 3, 6, 1
    prompt = np.array([7, 3, 12, 4], np.int32)
    for kw in ({}, {"kv_dtype": "int8"}):
        plain = _server(params, cfg, **kw)
        f1 = plain.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                          beam=BeamParams(K))
        plain.run_until_idle()
        r1 = f1.result(timeout=5)
        plain.close()

        spec = _server(params, cfg,
                       spec=SpecDecodeConfig(
                           GPTServingModel(params, cfg), k=2), **kw)
        f2 = spec.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                         beam=BeamParams(K))
        spec.run_until_idle()
        r2 = f2.result(timeout=5)
        st = spec.get_stats()
        spec.close()

        for a, b in zip(r1.hypotheses, r2.hypotheses):
            assert list(a.token_ids) == list(b.token_ids)
            assert a.norm_score == pytest.approx(b.norm_score,
                                                 rel=1e-6)
        assert st["compiled_step_signatures"] <= 2
        # the widened verify ran every iteration; ACCEPTANCE depends on
        # identity-parent stretches, which this tiny near-uniform model
        # rarely produces — parity above is the correctness gate
        assert st["spec"]["proposed"] > 0
        assert st["blocks_free"] == st["blocks_total"]


def test_beam_rejects_invalid_compositions(tiny_gpt):
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    p = np.array([5, 6, 7], np.int32)
    with pytest.raises(ValueError, match="requires eos_id"):
        srv.submit(p, max_new_tokens=4, beam=BeamParams(2))
    with pytest.raises(ValueError, match="excludes sampling"):
        srv.submit(p, max_new_tokens=4, eos_id=1, beam=BeamParams(2),
                   sampling=SamplingParams())
    with pytest.raises(ValueError, match="cannot stream"):
        srv.submit(p, max_new_tokens=4, eos_id=1, beam=BeamParams(2),
                   stream=lambda r, t: None)
    with pytest.raises(ValueError, match="exceeds num_slots"):
        srv.submit(p, max_new_tokens=4, eos_id=1, beam=BeamParams(9))
    srv.close()


# ---------------------------------------------------------------------------
# fork groups: n=K sampling lanes off one prefill
# ---------------------------------------------------------------------------

def test_fork_group_halves_block_footprint(tiny_gpt):
    """THE sharing acceptance: n=4 lanes over a 12-block prompt peak
    at 20 blocks where 4 independent submits of the same request peak
    at 52 — the prompt's blocks are aliased via refcounts, each lane
    pays only its private suffix plus the pooled COW reserve. All of
    it comes back when the group retires."""
    cfg, params = tiny_gpt
    prompt = np.arange(3, 99, dtype=np.int32)       # 96 toks = 12 blk
    kw = dict(num_slots=4, max_context=128, num_blocks=60, chunk=16)

    def peak_blocks(srv):
        peak = 0
        while srv.step():
            st = srv.get_stats()
            peak = max(peak, st["blocks_total"] - st["blocks_free"])
        return peak

    grp = _server(params, cfg, **kw)
    gf = grp.submit(prompt, max_new_tokens=4, n=4)
    peak_group = peak_blocks(grp)
    lanes = gf.result(timeout=5).lanes
    st = grp.get_stats()
    assert len(lanes) == 4
    assert all(len(l.token_ids) == 4 for l in lanes)
    assert st["group.requests"] == 1 and st["group.lanes"] == 4
    assert st["group.forks"] == 3
    assert st["blocks_free"] == st["blocks_total"]   # every block back
    assert st["fused_step_signatures"] == 1
    grp.close()

    ind = _server(params, cfg, **kw)
    futs = [ind.submit(prompt, max_new_tokens=4) for _ in range(4)]
    peak_indep = peak_blocks(ind)
    for f in futs:
        f.result(timeout=5)
    ind.close()

    # 12 aliased prompt blocks + 4 lanes x (private tail + COW spare)
    # against 4 x 13
    assert (peak_group, peak_indep) == (20, 52)


def test_fork_group_sampling_deterministic_replay(tiny_gpt):
    """Counter RNG: lane r's key folds (seed, rank, position), so the
    SAME submit on a fresh server replays every lane bitwise — the
    property group failover's whole-group replay rides on — while
    distinct ranks decode distinct continuations."""
    cfg, params = tiny_gpt
    prompt = np.array([5, 9, 11, 2, 7], np.int32)
    sp = SamplingParams(n=3, temperature=1.3, top_k=40, seed=17)

    def run():
        srv = _server(params, cfg)
        fut = srv.submit(prompt, max_new_tokens=6, sampling=sp)
        srv.run_until_idle()
        out = [list(l.token_ids) for l in fut.result(timeout=5).lanes]
        srv.close()
        return out

    a, b = run(), run()
    assert a == b                       # bitwise replay
    assert len({tuple(x) for x in a}) > 1   # ranks actually diverge


def test_group_cancel_and_deadline_reclaim_all_lanes(tiny_gpt):
    """A group lives and dies as a unit: client cancel and deadline
    expiry (injected chaos clock) both tear down all K lanes and
    return every block — shared prompt refs, COW'd suffixes, and the
    pooled spare reserve."""
    cfg, params = tiny_gpt
    prompt = np.arange(5, 29, dtype=np.int32)       # 24 toks = 3 blk
    srv = _server(params, cfg)
    fut = srv.submit(prompt, max_new_tokens=12, n=4)
    for _ in range(3):
        srv.step()
    assert fut.cancel()
    srv.run_until_idle()
    with pytest.raises(RequestCancelled):
        fut.result(timeout=5)
    st = srv.get_stats()
    assert st["blocks_free"] == st["blocks_total"]
    assert st["active_slots"] == 0
    # the pool is genuinely whole: a follow-up group admits and runs
    f2 = srv.submit(prompt, max_new_tokens=2, n=4)
    srv.run_until_idle()
    assert len(f2.result(timeout=5).lanes) == 4
    srv.close()

    chaos = ChaosInjector()
    for it in range(1, 30):
        chaos.advance_clock_at(it, ms=100)
    srv2 = _server(params, cfg, chaos=chaos)
    f3 = srv2.submit(prompt, max_new_tokens=20, n=4, deadline_ms=450)
    srv2.run_until_idle()
    with pytest.raises(DeadlineExceeded):
        f3.result(timeout=5)
    st2 = srv2.get_stats()
    assert st2["blocks_free"] == st2["blocks_total"]
    assert st2["active_slots"] == 0
    srv2.close()


# ---------------------------------------------------------------------------
# guided decoding
# ---------------------------------------------------------------------------

def test_guided_regex_conformance(tiny_gpt):
    """Every emitted token must be a legal automaton transition, and
    the additive mask rides the fused step's sampling path — still ONE
    compiled signature."""
    cfg, params = tiny_gpt
    vocab = _char_vocab(cfg.vocab_size)
    eos = 1
    c = RegexConstraint("[0-9]+", vocab)
    srv = _server(params, cfg)
    fut = srv.submit(np.array([5, 9, 11, 2], np.int32),
                     max_new_tokens=8, eos_id=eos, guided=c)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert len(res.token_ids) >= 1
    _assert_conforms(c, res.token_ids, eos)
    # non-eos emissions are all digit tokens (ids 3..12)
    digits = [t for t in res.token_ids if t != eos]
    assert digits and all(3 <= t <= 12 for t in digits)
    st = srv.get_stats()
    assert st["guided.masked_steps"] >= len(res.token_ids)
    assert st["guided.violations"] == 0
    assert st["fused_step_signatures"] == 1
    srv.close()


def test_guided_json_composes_with_fork_group(tiny_gpt):
    """JSON pushdown times K sampled lanes: every lane's output
    independently replays through the automaton — the mask is
    per-lane data, never shape."""
    cfg, params = tiny_gpt
    vocab = _char_vocab(cfg.vocab_size)
    eos = 1
    c = JsonConstraint(vocab)
    srv = _server(params, cfg)
    fut = srv.submit(np.array([7, 3, 12], np.int32), max_new_tokens=8,
                     eos_id=eos, n=3,
                     sampling=SamplingParams(n=3, temperature=1.0,
                                             seed=5),
                     guided=c)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert len(res.lanes) == 3
    for lane in res.lanes:
        _assert_conforms(c, lane.token_ids, eos)
    st = srv.get_stats()
    assert st["guided.violations"] == 0
    assert st["fused_step_signatures"] == 1
    srv.close()


# ---------------------------------------------------------------------------
# chaos: divergence storms and starved masks
# ---------------------------------------------------------------------------

def test_chaos_fork_storm_forces_cow_burst(tiny_gpt):
    """fork_storm_at COWs live lanes' current blocks even though
    nothing wrote them — the max-divergence burst. The storm fires for
    exactly the lanes it copied, the copies come out of the group's
    own spare reserve, and lane results are UNCHANGED (COW preserves
    content)."""
    cfg, params = tiny_gpt
    prompt = np.array([5, 9, 11, 2], np.int32)
    sp = SamplingParams(n=3, temperature=1.0, seed=3)

    ref_srv = _server(params, cfg)
    rf = ref_srv.submit(prompt, max_new_tokens=6, sampling=sp)
    ref_srv.run_until_idle()
    ref = [list(l.token_ids) for l in rf.result(timeout=5).lanes]
    ref_srv.close()

    # iteration 1 prefills the leader and forks at commit; from
    # iteration 2 on all three lanes are live decode lanes, so the
    # storm deterministically finds (at least) its 2 targets
    chaos = ChaosInjector().fork_storm_at(2, 2)
    srv = _server(params, cfg, chaos=chaos)
    fut = srv.submit(prompt, max_new_tokens=6, sampling=sp)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert chaos.fired["fork_storm"] == 2
    st = srv.get_stats()
    assert st["group.cow_copies"] >= 2
    assert st["blocks_free"] == st["blocks_total"]
    assert [list(l.token_ids) for l in res.lanes] == ref
    srv.close()


def test_chaos_mask_starve_keeps_conformance(tiny_gpt):
    """mask_starve_at narrows a guided lane's mask to ONE allowed
    token: generation stays conformant (the surviving token is a
    member of the allowed set) and the loop never raises."""
    cfg, params = tiny_gpt
    vocab = _char_vocab(cfg.vocab_size)
    eos = 1
    c = RegexConstraint("[0-9]+", vocab)
    chaos = ChaosInjector().mask_starve_at(2)
    srv = _server(params, cfg, chaos=chaos)
    fut = srv.submit(np.array([5, 9, 11, 2], np.int32),
                     max_new_tokens=6, eos_id=eos, guided=c)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert chaos.fired["mask_starve"] == 1
    _assert_conforms(c, res.token_ids, eos)
    assert srv.get_stats()["guided.violations"] == 0
    srv.close()


# ---------------------------------------------------------------------------
# fleet: fork-group affinity, unit failover, per-lane billing
# ---------------------------------------------------------------------------

def test_router_fork_group_unit_failover_and_billing(tiny_gpt):
    """A fork group routes and fails over AS A UNIT: one replica owns
    all K lanes, killing it mid-group replays the whole group on the
    survivor with ids bitwise the single-server run's (counter RNG is
    replica-independent), per-rank streams never deliver a token
    twice, and the survivor's tenant ledger bills every lane's
    tokens."""
    cfg, params = tiny_gpt
    prompt = np.array([5, 9, 11, 2], np.int32)
    sp = SamplingParams(n=3, temperature=1.2, seed=11)
    n_new = 6

    ref_srv = _server(params, cfg)
    rf = ref_srv.submit(prompt, max_new_tokens=n_new, sampling=sp)
    ref_srv.run_until_idle()
    ref = [list(l.token_ids) for l in rf.result(timeout=5).lanes]
    ref_srv.close()

    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    streams = {}

    def stream(rid, rank, tok):
        streams.setdefault(rank, []).append((rid, tok))

    fut = router.submit(prompt, max_new_tokens=n_new, sampling=sp,
                        stream=stream, tenant="acme")
    for _ in range(3):
        router.step()
    owner = next(i for i, s in enumerate(servers)
                 if s.get_stats()["active_slots"] > 0)
    # unit ownership: the OTHER replica holds no lane of this group
    assert servers[1 - owner].get_stats()["active_slots"] == 0
    router.kill_replica(owner)
    router.run_until_idle()
    res = fut.result(timeout=5)

    assert res.group_id == fut.request_id   # router-rid'd GroupResult
    assert [list(l.token_ids) for l in res.lanes] == ref
    assert router.counts["failovers"] >= 1
    survivor = servers[1 - owner].get_stats()
    # the survivor served the WHOLE group (group re-admission is
    # all-or-nothing) and billed the tenant for every lane's tokens
    assert survivor["group.requests"] == 1
    assert survivor["group.lanes"] == 3
    acme = survivor["tenants"]["tenants"]["acme"]
    assert acme["requests"] == 3            # one ledger row per lane
    assert acme["decode_tokens"] == 3 * n_new
    # per-rank stream dedup: exactly the lane ids, all under the
    # router's rid, no token twice
    for r in range(3):
        assert streams[r] == [(fut.request_id, t) for t in ref[r]]
    router.close()


def test_router_routes_beam_group(tiny_gpt):
    """Paged beam search through the fleet front door: the GroupResult
    comes back re-keyed under the router's rid with the same
    hypotheses a direct server submit produces."""
    cfg, params = tiny_gpt
    prompt = np.array([7, 3, 12, 4], np.int32)
    K, n_new, eos = 3, 5, 1

    direct = _server(params, cfg)
    df = direct.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                       beam=BeamParams(K))
    direct.run_until_idle()
    want = [list(h.token_ids) for h in df.result(timeout=5).hypotheses]
    direct.close()

    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    with pytest.raises(ValueError, match="does not stream"):
        router.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                      beam=BeamParams(K), stream=lambda *a: None)
    fut = router.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                        beam=BeamParams(K))
    router.run_until_idle()
    res = fut.result(timeout=5)
    assert res.kind == "beam"
    assert res.group_id == fut.request_id
    assert [list(h.token_ids) for h in res.hypotheses] == want
    router.close()
