"""The fused step's sampled tail sits under ONE device-side branch on
`do_sample.any()` (`step.py:_sampled_where_asked`): a step whose lanes
are all greedy runs no sort, no nucleus sum and no noise, and every
step's outputs are bit for bit those of the unguarded arithmetic, which
this file keeps as its reference.

Three things are held: the values (a parametrised comparison over
all-greedy, all-sampled and mixed lanes, each filter on and off, both
tails), the structure (every `sort` of the traced step lies inside the
one `cond`: what keeps a later edit from hoisting the draw out again;
`tests/ops/test_mosaic_compiles.py` holds the same for the module
compiled for a v5e), and the counter that says how often the sampled
branch engaged.
"""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.observability.tracing import get_recorder
from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                SamplingParams, SpecDecodeConfig)
from paddle_tpu.serving import step
from paddle_tpu.serving.decode_strategies import fold_key
from paddle_tpu.serving.kv_cache import NEG_INF

S, C, HID, V = 6, 4, 32, 211


def _unguarded_tail(x, head, tokens, valid, per_column, mask, rng,
                    temperature, do_sample, top_k, top_p):
    """The sampling step's tail as it was before the guard: the draw
    computed for every lane of every step, `where` keeping the greedy
    value of the lanes that did not ask."""
    s, c = tokens.shape
    if not per_column:
        last = jnp.clip(valid.sum(1) - 1, 0, c - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logp = jax.nn.log_softmax((xl @ head).astype(jnp.float32) + mask)
        nxt = jnp.argmax(logp, axis=-1)
        chosen = jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]
        samp, samp_lp = step._sample_rows(logp, rng, temperature,
                                            top_k, top_p)
        nxt = jnp.where(do_sample, samp, nxt).astype(jnp.int32)
        chosen = jnp.where(do_sample, samp_lp, chosen)
        return nxt, chosen, logp
    logits = (x.reshape(s * c, -1) @ head).reshape(s, c, head.shape[1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32) + mask)
    nxt = jnp.argmax(logp, axis=-1)
    chosen = jnp.take_along_axis(logp, nxt[..., None], -1)[..., 0]
    nt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    fed = jnp.take_along_axis(logp, nt[..., None], -1)[..., 0]
    samp, samp_lp = step._sample_rows(logp[:, 0], rng, temperature,
                                        top_k, top_p)
    nxt = nxt.at[:, 0].set(jnp.where(do_sample, samp, nxt[:, 0]))
    chosen = chosen.at[:, 0].set(
        jnp.where(do_sample, samp_lp, chosen[:, 0]))
    return nxt.astype(jnp.int32), chosen, fed, logp


def _tail_inputs(lanes, use_top_k, use_top_p, per_column,
                 dims=(S, C, HID, V), dtype=np.float32):
    """The tail's operands and its six controls for one case, seeded;
    `tests_tpu/test_sampled_tail_tpu.py` asks for the cell's sizes."""
    s, c, hid, v = dims
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(s, c, hid), dtype)
    head = jnp.asarray(r.randn(hid, v) * 0.3, dtype)
    tokens = r.randint(0, v, (s, c)).astype(np.int32)
    valid = np.arange(c)[None, :] < r.randint(1, c + 1, (s, 1))
    # a guided lane's mask rides outside the branch: block a third of
    # lane 1's vocabulary so the masked rows reach both branches
    mask = np.zeros((s, c, v) if per_column else (s, v), np.float32)
    mask[1, ..., ::3] = NEG_INF
    do_sample = {"greedy": np.zeros(s, bool), "sampled": np.ones(s, bool),
                 "mixed": np.arange(s) % 2 == 1}[lanes]
    temperature = np.where(do_sample, 0.8, 1.0).astype(np.float32)
    top_k = np.where(do_sample & use_top_k, 5, 0).astype(np.int32)
    top_p = np.where(do_sample & use_top_p, 0.7, 2.0).astype(np.float32)
    rng = np.stack([fold_key(17, lane, 3 + lane) for lane in range(s)])
    rng = np.where(do_sample[:, None], rng, 0).astype(np.uint32)
    return (x, head, tokens, valid), (mask, rng, temperature, do_sample,
                                      top_k, top_p)


@pytest.mark.parametrize(
    "lanes,use_top_k,use_top_p,per_column",
    list(itertools.product(("greedy", "sampled", "mixed"), (False, True),
                           (False, True), (False, True))),
    ids=lambda v: v if isinstance(v, str) else str(int(v)))
def test_guarded_tail_is_bitwise_the_unguarded_one(lanes, use_top_k,
                                                   use_top_p, per_column):
    check_guarded_tail_bitwise(
        per_column, lanes,
        *_tail_inputs(lanes, use_top_k, use_top_p, per_column))


def both_tails(per_column):
    """(guarded, unguarded), jitted: `step._step_tail` as it is and
    this file's reference, over the same arguments."""
    def guarded(x, head, tokens, valid, *ctl):
        return step._step_tail(x, head, tokens, valid, [], per_column,
                               True, *ctl)[1:]

    def unguarded(x, head, tokens, valid, *ctl):
        return _unguarded_tail(x, head, tokens, valid, per_column, *ctl)

    return jax.jit(guarded), jax.jit(unguarded)


def check_guarded_tail_bitwise(per_column, lanes, operands, ctl,
                               tails=None):
    guarded, unguarded = tails or both_tails(per_column)
    got = guarded(*operands, *ctl)
    want = unguarded(*operands, *ctl)
    assert len(got) == len(want) == (4 if per_column else 3)
    for g, w in zip(got, want, strict=True):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    # the case is what it says: sampled lanes drew (a filtered
    # distribution's log-prob differs from the unfiltered argmax's)
    ids, chosen, rows = got[0], got[1], got[-1]
    col = (ids[:, 0], chosen[:, 0], rows[:, 0]) if per_column \
        else (ids, chosen, rows)
    greedy_lp = np.asarray(col[2]).max(-1)
    drew = np.asarray(col[1]) != greedy_lp
    assert not drew[~ctl[3]].any()
    assert lanes == "greedy" or drew[ctl[3]].any()


# ---------------------------------------------------------------------------
# structure: every sort of the traced step lies inside the one cond
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _sorts(jaxpr, under=()):
    """(chain of enclosing primitives, eqn) of every `sort` in `jaxpr`,
    however deep."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield under, eqn
        for inner in _sub_jaxprs(eqn):
            yield from _sorts(inner, (*under, eqn))


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


@pytest.mark.parametrize("per_column", [False, True],
                         ids=["last_column", "per_column"])
def test_every_sort_of_the_sampling_step_is_inside_its_one_cond(
        tiny_gpt, per_column):
    cfg, params = tiny_gpt
    model = GPTServingModel(params, cfg)
    s, c, bs, m = 4, 4, 8, 8
    fused = model.build_fused_step(bs, per_column=per_column,
                                   sampling=True)
    pools = [{"kv": jnp.zeros((1 + s * m, model.num_kv_heads, bs,
                               2 * model.head_dim), jnp.float32)}
             for _ in range(model.num_layers)]
    grid = jnp.zeros((s, c), jnp.int32)
    mask = jnp.zeros((s, c, cfg.vocab_size) if per_column
                     else (s, cfg.vocab_size), jnp.float32)
    jaxpr = jax.make_jaxpr(fused)(
        pools, grid, grid, jnp.zeros((s, c), bool),
        jnp.zeros((s, m), jnp.int32), mask, jnp.zeros((s, 2), jnp.uint32),
        jnp.ones((s,), jnp.float32), jnp.zeros((s,), bool),
        jnp.zeros((s,), jnp.int32), jnp.full((s,), 2.0, jnp.float32)).jaxpr
    found = list(_sorts(jaxpr))
    assert len(found) == 2          # top-k's and the nucleus'
    guards = set()
    for under, _eqn in found:
        assert under, "a sort at the step's top level: it runs every step"
        assert under[0].primitive.name == "cond"
        guards.add(id(under[0]))
    assert len(guards) == 1         # one branch a tail, not one a sort
    # and the guard is the only cond the tail adds: the step's own
    # top level holds no other
    top_conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(top_conds) == 1
    # its predicate is computed on the device from do_sample: the
    # branch index is an outcome of the step's own arguments
    assert top_conds[0].invars[0] not in jaxpr.constvars


# ---------------------------------------------------------------------------
# the counter that says how often the sampled branch engaged
# ---------------------------------------------------------------------------

def _pump(srv, submits):
    reg = global_registry()
    names = ("serving.sampled_iterations", "serving.iterations")
    before = {n: reg.counter(n).value() for n in names}
    rec = get_recorder()
    rec.start()
    try:
        futs = [srv.submit(np.asarray(p, np.int32), max_new_tokens=n, **kw)
                for p, n, kw in submits]
        srv.run_until_idle()
        results = [f.result(timeout=30) for f in futs]
    finally:
        rec.stop()
    records = [e["args"] for e in rec.events()
               if e["name"] == "serving.iteration"]
    rec.clear()
    delta = {n: reg.counter(n).value() - before[n] for n in names}
    signatures = srv.get_stats()["fused_step_signatures"]
    srv.close()
    return results, records, delta, signatures


GREEDY = [([5, 9, 11, 2, 7], 6, {}), ([3, 4], 5, {}), ([1], 3, {})]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_greedy_traffic_never_takes_the_sampled_branch(tiny_gpt, spec):
    cfg, params = tiny_gpt
    kw = {"spec": SpecDecodeConfig(GPTServingModel(params, cfg), k=2)} \
        if spec else {}
    _res, records, delta, signatures = _pump(_server(params, cfg, **kw),
                                             GREEDY)
    assert delta["serving.iterations"] == len(records) >= 5
    assert delta["serving.sampled_iterations"] == 0
    assert all(r["sampled_lanes"] == 0 for r in records)
    assert signatures == 1


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_one_sampled_request_counts_the_iterations_it_emitted_in(
        tiny_gpt, spec):
    cfg, params = tiny_gpt
    kw = {"spec": SpecDecodeConfig(GPTServingModel(params, cfg), k=2)} \
        if spec else {}
    sampled = ([8, 1, 6, 3, 2, 9], 4,
               {"sampling": SamplingParams(temperature=1.0, seed=5)})
    results, records, delta, signatures = _pump(
        _server(params, cfg, **kw), [GREEDY[0], sampled, GREEDY[1]])
    emitted = len(results[1].token_ids)
    assert emitted == 4
    # the lane draws in the iterations it emits in, one token each (a
    # sampled lane runs one column, with a draft model too), and in no
    # other: its prefill's first chunk and the greedy lanes' longer
    # tails are iterations of the greedy branch
    assert delta["serving.sampled_iterations"] == emitted
    assert sum(r["sampled_lanes"] > 0 for r in records) == emitted
    assert {r["sampled_lanes"] for r in records} == {0, 1}
    assert delta["serving.iterations"] == len(records) > emitted
    assert signatures == 1
