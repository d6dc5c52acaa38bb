"""The fused step's feed crosses to the device as ONE packed int32 buffer
(`serving/feed.py`), and the strategies' all-zero mask stays on the
device unless a lane is guided.

Held here: the layout returns every field bit for bit, whatever its
dtype; a server's ids and log-probs through the packed buffer are
bitwise those of the model's step called with the unpacked host arrays,
greedy, sampled and per column; and a mask crosses on exactly the
iterations that have guided lanes, which `serving.feed.mask_uploads`
counts.
"""

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
                                RegexConstraint, SamplingParams,
                                SpecDecodeConfig)
from paddle_tpu.serving.feed import FeedLayout

pytestmark = pytest.mark.serving


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


F32 = np.array([1.5, -0.0, 0.0, np.inf, -np.inf, 3.4028235e38, 1e-45,
                np.float32(np.nan)], np.float32)
# a NaN with a payload of its own: bits, not a value, cross
F32_NAN = np.array([0x7FC12345, 0xFF800001], np.uint32).view(np.float32)

CASES = {
    "int32": np.array([[0, -1, 2**31 - 1], [-2**31, 7, 65536]], np.int32),
    "bool": np.array([[True, False, True], [False, False, True]]),
    "uint32_keys": np.array([[0, 2**32 - 1], [2**31, 12345],
                             [0x9E3779B9, 1]], np.uint32),
    "float32": np.concatenate([F32, F32_NAN]),
}


@pytest.mark.parametrize("kind", list(CASES))
def test_pack_then_unpack_returns_every_field_bit_for_bit(kind):
    """Each dtype between two other fields, so that its offsets are
    read as well: through the host pack and the jitted unpack."""
    field = CASES[kind]
    before = np.arange(5, dtype=np.int32) - 2
    after = np.array([-0.0, 2.0, np.inf], np.float32)
    arrays = (before, field, after)
    layout = FeedLayout([(a.shape, a.dtype) for a in arrays])
    packed = layout.pack(arrays)
    assert packed.dtype == np.int32 and packed.shape == (layout.size,)
    assert layout.size == sum(a.size for a in arrays)
    out = jax.jit(layout.unpack)(jnp.asarray(packed))
    assert [_bits(o) for o in out] == [_bits(a) for a in arrays]


def test_pack_refuses_a_field_of_another_shape_or_dtype():
    layout = FeedLayout([((2, 3), np.int32), ((2,), np.bool_)])
    with pytest.raises(ValueError):
        layout.pack((np.zeros((2, 3), np.int64), np.zeros(2, bool)))
    with pytest.raises(ValueError):
        layout.pack((np.zeros((3, 2), np.int32), np.zeros(2, bool)))
    with pytest.raises(ValueError):
        FeedLayout([((2,), np.float64)])


# ---------------------------------------------------------------------------
# a server's outputs through the packed feed
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
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _char_vocab(vocab_size):
    """Ids 3..12 are the digits; every other id a character no
    constraint here matches."""
    return [str(i - 3) if 3 <= i <= 12 else chr(0x4E00 + i)
            for i in range(vocab_size)]


PROMPTS = [([5, 9, 11, 2, 7], 6), ([3, 4], 5), ([1], 3)]
RUNS = {
    "greedy": ({}, {}),
    "sampled": ({}, {"sampling": SamplingParams(temperature=1.0, seed=5)}),
    "per_column": ({"spec": True}, {}),
}


@pytest.mark.parametrize("run", list(RUNS))
def test_ids_and_logps_are_those_of_the_unpacked_feeds(tiny_gpt, run):
    """Every iteration: the model's own step, jitted apart and called
    with the host arrays as the feed had them before packing (the pools
    copied first), gives the served step's ids and log-probs bit for
    bit."""
    cfg, params = tiny_gpt
    server_kw, submit_kw = RUNS[run]
    step_kw = {"sampling": True}
    if server_kw.get("spec"):
        server_kw = {"spec": SpecDecodeConfig(GPTServingModel(params, cfg),
                                              k=2)}
        step_kw["per_column"] = True
    srv = _server(params, cfg, **server_kw)
    plain = jax.jit(GPTServingModel(params, cfg).build_fused_step(
        srv.block_size, **step_kw))
    fed, compared = [], []
    pack, served = srv._feed.pack, srv._fused

    def recording_pack(feeds):
        fed.append([np.array(a) for a in feeds])
        return pack(feeds)

    def comparing(pools, packed, prev_ids, mask):
        host = fed[-1]
        # a lane whose token the step before holds takes it from there
        tokens = host[0].copy()
        from_prev = host[-1]
        tokens[from_prev, 0] = np.asarray(prev_ids)[from_prev]
        ref = plain(jax.tree_util.tree_map(jnp.copy, pools), tokens,
                    *host[1:4], np.asarray(mask), *host[4:-1])
        out = served(pools, packed, prev_ids, mask)
        assert len(out) == len(ref)
        compared.append([(_bits(o), _bits(r))
                         for o, r in zip(out[1:], ref[1:])])
        return out

    srv._feed.pack, srv._fused = recording_pack, comparing
    futs = [srv.submit(np.asarray(p, np.int32), max_new_tokens=n,
                       **submit_kw) for p, n in PROMPTS]
    srv.run_until_idle()
    results = [f.result(timeout=30) for f in futs]
    assert [len(r.token_ids) for r in results] == [n for _p, n in PROMPTS]
    assert len(compared) == srv.get_stats()["iteration"] >= 5
    for outputs in compared:
        for got, want in outputs:
            assert got == want
    if run == "sampled":
        # the controls reached the step: the lanes drew their tokens
        assert any(h[6].any() for h in fed)
    # a step left in flight handed its ids to the next, except where a
    # draft model keeps every step's outputs on the host first
    assert any(h[-1].any() for h in fed) == (run != "per_column")
    srv.close()


# ---------------------------------------------------------------------------
# the mask crosses only where a lane is guided
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_a_mask_crosses_on_exactly_the_guided_iterations(tiny_gpt, spec):
    cfg, params = tiny_gpt
    kw = {"spec": SpecDecodeConfig(GPTServingModel(params, cfg), k=2)} \
        if spec else {}
    srv = _server(params, cfg, **kw)
    guided_at = []
    plan = srv._sched.plan

    def recording_plan():
        p = plan()
        if p is not None and p.guided_lanes:
            guided_at.append(srv._sched.iteration)
        return p

    srv._sched.plan = recording_plan
    reg = global_registry()
    counter = reg.counter("serving.feed.mask_uploads")
    before = counter.value()
    rec = get_recorder()
    rec.start()
    try:
        eos = 1
        digits = RegexConstraint("[0-9]+", _char_vocab(cfg.vocab_size))
        # the guided request starts after a long unguided prefill and
        # ends before the unguided decode does, so both kinds of
        # iteration occur
        free = srv.submit(np.arange(2, 14, dtype=np.int32),
                          max_new_tokens=10)
        srv.step()
        guided = srv.submit(np.array([5, 9, 11, 2], np.int32),
                            max_new_tokens=3, eos_id=eos, guided=digits)
        srv.run_until_idle()
        free.result(timeout=30)
        res = guided.result(timeout=30)
    finally:
        rec.stop()
    uploads = {e["args"]["iteration"]: e["args"] for e in rec.events()
               if e["name"] == "serving.feed.upload"}
    rec.clear()
    assert all(3 <= t <= 12 for t in res.token_ids if t != eos)
    s, c = srv._sched.num_slots, srv._sched.chunk
    mask_bytes = 4 * s * cfg.vocab_size * (c if spec else 1)
    packed_bytes = 4 * srv._feed.size
    assert guided_at and len(uploads) > len(guided_at)
    for it, args in uploads.items():
        if it in guided_at:
            assert args == {"iteration": it, "arrays": 2,
                            "bytes": packed_bytes + mask_bytes}
        else:
            assert args == {"iteration": it, "arrays": 1,
                            "bytes": packed_bytes}
    assert counter.value() - before == len(guided_at)
    assert srv.get_stats()["fused_step_signatures"] == 1
    srv.close()
