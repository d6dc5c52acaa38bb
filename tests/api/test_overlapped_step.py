"""One step in flight (docs/serving.md "One step in flight"): the engine
dispatches iteration i+1 before it fetches and commits iteration i, and
a decode lane's token crosses from one step to the next on the device.
Tiny models on the CPU, the engine pumped by hand:

- every family's streams and scores are its plain reference's, over a
  mix with a one-token request, an EOS mid-stream, a cancel and a
  deadline that land while a step is in flight;
- the jit's cache holds one step, and a run compiles the programs a
  run in the serial order compiles;
- a lane that EOS ends keeps its blocks and its state lane until the
  step that still writes them has been fetched;
- speculative, guided and beam plans take the serial order;
- `close()` commits a step left in flight;
- `serving.overlapped_iterations` counts the iterations dispatched
  while the one before was not yet fetched;
- a poisoned KV pool fails the engine with a step in flight as it does
  without one.
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.robustness import ChaosInjector
from paddle_tpu.robustness.guard import NonFiniteError
from paddle_tpu.serving import (DeadlineExceeded, GenerationServer,
                                GPTServingModel, RequestCancelled,
                                SpecDecodeConfig)

pytestmark = pytest.mark.serving

OVERLAPPED = "serving.overlapped_iterations"


def _counter(name):
    return global_registry().counter(name).value()


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor().run(startup)
    return cfg, gpt.load_params(scope, cfg)


def _perturbed(params, seed):
    """Norm scales (`*_s`) moved off one by seeded noise, as the
    families' own tests do, so that the logits are not those of a
    model whose every norm is the identity."""
    key = jax.random.PRNGKey(seed)

    def bump(path, a):
        if not path[-1].key.endswith("_s"):
            return a
        k = jax.random.fold_in(key, zlib.crc32(
            jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        return (a.astype(jnp.float32)
                + 0.2 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(bump, params)


def _family(name):
    """(serving model, config, plain reference module) of one family at
    its tiny size, float32 weights."""
    if name == "gpt2":
        from benchmark.reference import gpt2 as ref
        cfg = gpt.gpt_tiny()
        main, startup = framework.Program(), framework.Program()
        main.random_seed = startup.random_seed = 11
        with framework.program_guard(main, startup):
            gpt.build_lm_net(cfg, seq_len=8)
        scope = Scope()
        with scope_guard(scope):
            fluid.Executor().run(startup)
        return (GPTServingModel(gpt.load_params(scope, cfg), cfg), cfg,
                ref)
    if name == "latent_moe":
        from benchmark.reference import joyai_llm_flash as ref
        from paddle_tpu.models.latent_moe import (init_params,
                                                  latent_moe_tiny)
        from paddle_tpu.serving import LatentMoEServingModel as M
        cfg = latent_moe_tiny(n_routed_experts_held=4, expert_offset=8)
    elif name == "linear_moe":
        from benchmark.reference import solar_open2 as ref
        from paddle_tpu.models.linear_moe import (init_params,
                                                  linear_moe_tiny)
        from paddle_tpu.serving import LinearMoEServingModel as M
        cfg = linear_moe_tiny(initializer_range=0.1,
                              n_routed_experts_held=4, expert_offset=8)
    elif name == "window_moe":
        from benchmark.reference import smallthinker as ref
        from paddle_tpu.models.smallthinker import (init_params,
                                                    smallthinker_tiny)
        from paddle_tpu.serving import SmallThinkerServingModel as M
        cfg = smallthinker_tiny(initializer_range=0.1,
                                n_routed_experts_held=8, expert_offset=4)
    else:
        from benchmark.reference import brumby as ref
        from paddle_tpu.models.brumby import brumby_tiny, init_params
        from paddle_tpu.serving import BrumbyServingModel as M
        cfg = brumby_tiny(initializer_range=0.15)
    return M(_perturbed(init_params(cfg, 0), 2), cfg), cfg, ref


def _greedy(ref, model, cfg, prompt, n, pad_to):
    """The reference's greedy tokens after `prompt`, one at a time."""
    ids = list(prompt)
    for _ in range(n):
        row = ref.forward_logprobs(model.params, cfg, np.asarray(
            ids, np.int32), pad_to=pad_to, first_row=len(ids) - 1,
            n_rows=1)
        ids.append(int(np.argmax(row[0])))
    return ids[len(prompt):]


def _matches_reference(ref, model, cfg, prompt, tokens, score, pad_to):
    """The engine's tokens are the reference's best at every step, and
    its score is the reference's log-probabilities of them."""
    n = len(tokens)
    rows = ref.forward_logprobs(
        model.params, cfg, np.concatenate([prompt, tokens]).astype(
            np.int32), pad_to=pad_to, first_row=len(prompt) - 1, n_rows=n)
    chosen = rows[np.arange(n), np.asarray(tokens)]
    assert abs(float(chosen.sum()) - score) < 5e-4
    assert float((rows.max(-1) - chosen).max()) < 1e-4


FAMILIES = ("gpt2", "latent_moe", "linear_moe", "window_moe", "brumby")


@pytest.mark.parametrize("family", FAMILIES)
def test_every_family_streams_its_reference_with_a_step_in_flight(family):
    """Five requests over three lanes, pumped by hand: a one-token
    request, one that EOS ends mid-stream, one cancelled and one whose
    deadline passes while a step feeds them, one on a reused lane.
    What finishes is the reference's, token for token; what a cancel
    or deadline ended streamed a prefix of the reference; every block
    and slot comes back; the jit held one step."""
    model, cfg, ref = _family(family)
    pad_to = 64
    chaos = ChaosInjector()
    for it in range(1, 200):
        chaos.advance_clock_at(it, ms=10)
    srv = GenerationServer(model, num_slots=3, chunk=4, block_size=8,
                           max_context=pad_to, chaos=chaos, start=False)
    prompts = [np.arange(1, 14), np.arange(40, 43), np.arange(7, 30),
               np.arange(20, 26), np.arange(60, 66)]
    eos_prompt = prompts[1]
    want = _greedy(ref, model, cfg, eos_prompt, 6, pad_to)
    eos = want[2]
    seen = {}

    def stream(rid, tok):
        seen.setdefault(rid, []).append(tok)

    overlapped0 = _counter(OVERLAPPED)
    one = srv.submit(prompts[0], max_new_tokens=1, stream=stream)
    ended = srv.submit(eos_prompt, max_new_tokens=6, eos_id=eos,
                       stream=stream)
    cancelled = srv.submit(prompts[2], max_new_tokens=8, stream=stream)
    for _ in range(7):
        srv.step()
    assert srv._inflight is not None        # a step feeds the victim
    assert cancelled.cancel()
    late = srv.submit(prompts[3], max_new_tokens=8, stream=stream,
                      deadline_ms=80)
    reused = srv.submit(prompts[4], max_new_tokens=5, stream=stream)
    srv.run_until_idle()
    assert srv._inflight is None
    st = srv.get_stats()
    assert st["compiled_step_signatures"] == 1
    assert srv._fused._cache_size() == 1
    assert st["blocks_free"] == st["blocks_total"]
    assert st["active_slots"] == 0
    assert _counter(OVERLAPPED) - overlapped0 > 0
    srv.close()

    r = one.result()
    assert len(r.token_ids) == 1 and r.finish_reason == "length"
    _matches_reference(ref, model, cfg, prompts[0], r.token_ids, r.score,
                       pad_to)
    r = ended.result()
    assert r.finish_reason == "eos"
    assert list(r.token_ids) == want[:want.index(eos) + 1]
    _matches_reference(ref, model, cfg, eos_prompt, r.token_ids, r.score,
                       pad_to)
    r = reused.result()
    assert len(r.token_ids) == 5
    _matches_reference(ref, model, cfg, prompts[4], r.token_ids, r.score,
                       pad_to)
    assert cancelled.cancelled()
    with pytest.raises(DeadlineExceeded):
        late.result()
    for fut, prompt in ((cancelled, prompts[2]), (late, prompts[3])):
        got = seen.get(fut.request_id, [])
        assert got == _greedy(ref, model, cfg, prompt, len(got), pad_to)
    for fut in (one, ended, reused):
        assert seen[fut.request_id] == list(fut.result().token_ids)
    assert st["cancelled"] == 1 and st["deadline_cancels"] == 1


def _run(srv, prompts, n_new):
    futs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    srv.run_until_idle()
    return [list(f.result().token_ids) for f in futs]


def test_one_compiled_step_and_the_serial_orders_programs(tiny_gpt):
    """A run whose every iteration overlaps compiles exactly the
    programs of the same run in the serial order (a host tier keeps a
    server there) and no more: the fused step once."""
    cfg, params = tiny_gpt
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    prompts = [np.arange(2, 9), np.arange(30, 33), np.arange(5, 18),
               np.arange(40, 44)]
    n_new = [5, 1, 7, 4]

    def counted(**kw):
        n0 = len(compiles)
        srv = GenerationServer(GPTServingModel(params, cfg), num_slots=3,
                               chunk=4, block_size=8, max_context=64,
                               start=False, **kw)
        ov0 = _counter(OVERLAPPED)
        ids = _run(srv, prompts, n_new)
        st = srv.get_stats()
        srv.close()
        return (ids, len(compiles) - n0, _counter(OVERLAPPED) - ov0,
                st["compiled_step_signatures"], srv._fused._cache_size())

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # the process's small programs (zeros, casts) compile once, first
        counted()
        counted(host_kv_blocks=16)
        ids, n, overlapped, sigs, cached = counted()
        serial_ids, serial_n, serial_overlapped, *_ = counted(
            host_kv_blocks=16)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert ids == serial_ids
    assert overlapped > 0 and serial_overlapped == 0
    assert n == serial_n == 1
    assert sigs == cached == 1


def test_an_eos_lane_keeps_its_blocks_until_the_step_in_flight_is_fetched(
        tiny_gpt):
    """One slot: the EOS that ends request A is found when step i is
    fetched, after step i+1 (which fed A once more) was dispatched. A's
    slot and blocks stay taken while step i+1 is in flight, and request
    B, queued, is admitted only at the plan after that step's commit."""
    cfg, params = tiny_gpt
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=1,
                           chunk=4, block_size=8, max_context=64,
                           start=False)
    prompt = np.array([5, 9, 11], np.int32)
    probe = srv.submit(prompt, max_new_tokens=6)
    srv.run_until_idle()
    toks = list(probe.result().token_ids)
    eos = toks[2]
    k = toks.index(eos)
    a = srv.submit(prompt, max_new_tokens=6, eos_id=eos)
    b = srv.submit(np.array([7, 8], np.int32), max_new_tokens=2)
    while not a.done():
        srv.step()
    # the step after the EOS is in flight and still writes A's blocks
    assert srv._inflight is not None
    slot = srv._sched._slots[0]
    assert slot is not None and slot.req.rid == a.request_id
    assert slot.done
    held = set(slot.blocks)
    assert held and not held & set(srv.cache._free)
    assert srv.get_stats()["queue_depth"] == 1
    srv.step()          # commits that step: A's slot and blocks go back
    assert srv._sched._slots[0] is None or \
        srv._sched._slots[0].req.rid == b.request_id
    srv.run_until_idle()
    assert list(a.result().token_ids) == toks[:k + 1]
    assert len(b.result().token_ids) == 2
    st = srv.get_stats()
    assert st["blocks_free"] == st["blocks_total"]


def test_an_eos_lane_keeps_its_state_lane_until_the_step_is_fetched():
    """The same with a model whose every layer is a state a lane: the
    lane an EOS ended is not handed to the queued request until the
    step that still moves its state has been fetched, and the request
    it is then handed to reads as on a fresh server."""
    model, cfg, _ref = _family("brumby")
    kw = dict(num_slots=1, chunk=4, block_size=8, max_context=64,
              start=False)
    srv = GenerationServer(model, **kw)
    first = np.arange(3, 12)
    probe = srv.submit(first, max_new_tokens=5)
    srv.run_until_idle()
    eos = list(probe.result().token_ids)[1]
    a = srv.submit(first, max_new_tokens=5, eos_id=eos)
    b = srv.submit(np.arange(90, 97), max_new_tokens=4)
    while not a.done():
        srv.step()
    assert srv._inflight is not None and srv._sched._slots[0].done
    assert srv._sched._slots[0].req.rid == a.request_id
    srv.run_until_idle()
    fresh = GenerationServer(model, **kw)
    want = fresh.submit(np.arange(90, 97), max_new_tokens=4)
    fresh.run_until_idle()
    assert list(b.result().token_ids) == list(want.result().token_ids)
    assert b.result().score == want.result().score


def test_speculative_guided_and_beam_plans_take_the_serial_order(
        tiny_gpt):
    """Where the next feed needs this step's outputs on the host, no
    step is left in flight: a draft model's server, a guided request,
    a beam group. A plain request beside them overlaps until its
    neighbour's plan needs the host."""
    from paddle_tpu.serving import BeamParams
    from paddle_tpu.serving.guided import RegexConstraint
    cfg, params = tiny_gpt

    def pumped(srv, submit):
        ov0 = _counter(OVERLAPPED)
        fut = submit(srv)
        while srv.step():
            assert srv._inflight is None
        fut.result()
        return _counter(OVERLAPPED) - ov0

    spec = SpecDecodeConfig(GPTServingModel(params, cfg), k=2)
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=3,
                           chunk=4, block_size=8, max_context=64,
                           start=False, spec=spec)
    assert pumped(srv, lambda s: s.submit([5, 6, 7], max_new_tokens=6)) \
        == 0
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=3,
                           chunk=4, block_size=8, max_context=64,
                           start=False)
    # ids 3..12 spell the digits, the rest characters nothing matches
    vocab = [chr(ord("0") + i - 3) if 3 <= i <= 12 else chr(0x4E00 + i)
             for i in range(cfg.vocab_size)]
    digits = RegexConstraint("[0-9]+", vocab)
    assert pumped(srv, lambda s: s.submit(
        [5, 9, 11, 2], max_new_tokens=6, eos_id=1, guided=digits)) == 0
    assert pumped(srv, lambda s: s.submit(
        [5, 6], max_new_tokens=4, eos_id=3,
        beam=BeamParams(beam_size=2))) == 0


def test_overlapped_iterations_count_what_was_dispatched_in_flight(
        tiny_gpt):
    """One request of a 3-token prompt and 5 tokens at chunk 4 is five
    iterations: the first is dispatched with nothing in flight, the
    other four each while the one before it is unfetched. The
    `serving.iteration` record says which."""
    from paddle_tpu.observability.tracing import get_recorder
    cfg, params = tiny_gpt
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=2,
                           chunk=4, block_size=8, max_context=64,
                           start=False)
    ov0, it0 = _counter(OVERLAPPED), _counter("serving.iterations")
    rec = get_recorder()
    rec.start()
    try:
        fut = srv.submit([5, 9, 11], max_new_tokens=5)
        srv.run_until_idle()
    finally:
        rec.stop()
    marks = [e["args"]["overlapped"] for e in rec.events()
             if e["name"] == "serving.iteration"]
    rec.clear()
    assert len(fut.result().token_ids) == 5
    assert _counter("serving.iterations") - it0 == 5
    assert _counter(OVERLAPPED) - ov0 == 4
    assert marks == [0, 1, 1, 1, 1]


def test_a_poisoned_pool_fails_the_engine_with_a_step_in_flight(
        tiny_gpt):
    """KV poisoned at iteration 5: the NaN is found when step 5 is
    fetched, with step 6 in flight. The error is the serial engine's
    (a NonFiniteError naming step 5), step 6 is waited for and dropped,
    no token of step 5 or 6 is streamed, and every future fails."""
    cfg, params = tiny_gpt
    chaos = ChaosInjector().poison_serving_at(5)
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=3,
                           chunk=4, block_size=8, max_context=64,
                           chaos=chaos, start=False)
    streamed = {}

    def stream(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    # a 6-token prompt emits from step 2, a 3-token one from step 1
    futs = [srv.submit(np.arange(3, 9), max_new_tokens=20, stream=stream),
            srv.submit(np.arange(20, 23), max_new_tokens=20,
                       stream=stream)]
    with pytest.raises(NonFiniteError) as ei:
        srv.run_until_idle()
    assert ei.value.step == 5
    assert srv._inflight is None
    assert srv.get_stats()["engine_fault"] is not None
    for f in futs:
        assert f.done() and f.exception() is not None
    # the tokens of steps 1 to 4, and none of 5 or 6
    assert [len(streamed[f.request_id]) for f in futs] == [3, 4]
    assert chaos.fired["serving_poison"] == 1


@pytest.mark.parametrize("drain", [True, False])
def test_close_commits_the_step_left_in_flight(tiny_gpt, drain):
    """A pump that stops with a step in flight: `close(drain=True)`
    finishes every request, `close(drain=False)` fails them; either
    way the step in flight is committed and every block comes back."""
    cfg, params = tiny_gpt
    srv = GenerationServer(GPTServingModel(params, cfg), num_slots=2,
                           chunk=4, block_size=8, max_context=64,
                           start=False)
    it0 = _counter("serving.iterations")
    futs = [srv.submit([5, 9, 11], max_new_tokens=4),
            srv.submit([7, 8], max_new_tokens=6)]
    srv.step()
    srv.step()
    assert srv._inflight is not None
    srv.close(drain=drain)
    assert srv._inflight is None
    st = srv.get_stats()
    assert st["blocks_free"] == st["blocks_total"]
    # every dispatched step was accounted, the one left in flight too
    assert _counter("serving.iterations") - it0 == st["iteration"]
    if drain:
        assert [len(f.result().token_ids) for f in futs] == [4, 6]
    else:
        for f in futs:
            with pytest.raises(RequestCancelled):
                f.result(timeout=5)
