"""The KV pools are donated to every step that rewrites them (ISSUE 26).

Tier-1 (`serving` marker, CPU, no sleeps). The contract under test:

- the compiled fused step aliases every pool leaf's output to its input
  and nothing else, in each variant `build_fused_step` returns: plain,
  `sampling`, `per_column`, int8 KV with its scale pools, the
  `shard_map` body on a mesh;
- a step consumes the pools it was handed (the array that was
  `pools[0]["kv"]` is deleted after it) and counts it:
  `serving.kv.pool_donations` moves with `serving.iterations`;
- so do the other rewriters: `cow_copy`, `swap_in_block`,
  `deserialize_block`, `adopt_block_from` (destination consumed, source
  alive) and the speculative draft step;
- nobody on another thread is caught holding dead pools: blocks are
  serialised out of, written into and adopted between two engines whose
  worker threads are stepping, for a few hundred iterations;
- a fused call that dies after it consumed the pools fail-stops the
  server with its own error.
"""

import re
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.serving import (GenerationServer, GPTServingModel,
                                PagedKVCache, SpecDecodeConfig)

pytestmark = pytest.mark.serving


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


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _counters():
    reg = global_registry()
    return (reg.counter("serving.kv.pool_donations").value(),
            reg.counter("serving.iterations").value())


def _leaves(cache):
    return jax.tree_util.tree_leaves(cache.pools)


ALIAS = re.compile(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)")


# ---------------------------------------------------------------------------
# the fused step, in every variant
# ---------------------------------------------------------------------------

def _plain(params, cfg):
    class Original(GPTServingModel):
        # the pre-strategies signature: the server then builds the
        # plain (pools, ids, logps) step
        def build_fused_step(self, block_size, mesh=None, axis="tp"):
            return super().build_fused_step(block_size, mesh=mesh,
                                            axis=axis)
    return GenerationServer(Original(params, cfg), num_slots=3,
                            block_size=8, max_context=64, chunk=4,
                            start=False)


VARIANTS = {
    "plain": _plain,
    "sampling": lambda p, c: _server(p, c),
    "per_column": lambda p, c: _server(
        p, c, spec=SpecDecodeConfig(GPTServingModel(p, c), k=3)),
    "int8_kv": lambda p, c: _server(p, c, kv_dtype="int8"),
    "tp_mesh": lambda p, c: _server(
        p, c, mesh=Mesh(np.array(jax.devices()[:2]), ("tp",))),
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_fused_step_aliases_every_pool_leaf_and_consumes_them(
        tiny_gpt, variant):
    cfg, params = tiny_gpt
    srv = VARIANTS[variant](params, cfg)
    n_leaves = (3 if variant == "int8_kv" else 1) * cfg.num_layers
    assert len(_leaves(srv.cache)) == n_leaves
    if variant == "plain":
        assert not srv._strategies
    calls = []
    fused = srv._fused

    def recording(pools, *args):
        calls.append(args)
        return fused(pools, *args)

    srv._fused = recording
    don0, it0 = _counters()
    fut = srv.submit([5, 6, 7, 8, 9], max_new_tokens=4)
    k0 = srv.cache.pools[0]["kv"]
    assert srv.step()
    # the step took the pools it was handed, and said so
    assert k0.is_deleted()
    assert not srv.cache.pools[0]["kv"].is_deleted()
    assert _counters() == (don0 + 1, it0 + 1)
    srv.run_until_idle()
    assert len(fut.result(timeout=5).token_ids) == 4
    don1, it1 = _counters()
    assert don1 - don0 == it1 - it0 == srv.get_stats()["iteration"]
    assert srv.get_stats()["fused_step_signatures"] == 1

    # the compiled module: outputs 0..n-1 are the pools, written into
    # parameters 0..n-1, which are the pools; no other pair
    text = fused.lower(srv.cache.pools, *calls[0]).compile().as_text()
    header = text[:text.index("\n")]
    pairs = sorted((int(o), int(p)) for o, p in ALIAS.findall(header))
    assert pairs == [(i, i) for i in range(n_leaves)], header[:2000]
    layout = header[header.index("entry_computation_layout={(") + 27:]
    shapes = [tuple(a.shape) for a in _leaves(srv.cache)]
    if variant == "tp_mesh":        # each device holds half the heads
        shapes = [(n, h // 2, *rest) for n, h, *rest in shapes]
    for want, got in zip(shapes, layout.split("}, ")):
        assert "[" + ",".join(map(str, want)) + "]" in got, (want, got)
    srv.close()


# ---------------------------------------------------------------------------
# the other rewriters
# ---------------------------------------------------------------------------

def _cache(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("num_blocks", 6)
    kw.setdefault("block_size", 4)
    return PagedKVCache(**kw)


def _fill(cache, block, seed):
    """Write seeded rows into `block` through the wire path; returns
    them as serialize_block would."""
    rng = np.random.default_rng(seed)
    meta, zeros = cache.serialize_block(block)
    rows = [rng.integers(-100, 100, a.shape).astype(a.dtype)
            for a in zeros]
    cache.deserialize_block(block, meta, rows)
    return rows


def _rows(cache, block):
    return cache.serialize_block(block)[1]


def _same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _cow(c):
    sib = _cache(num_heads=1)
    c.attach_sibling(sib)
    rows, sib_rows = _fill(c, 2, 1), _fill(sib, 2, 2)
    before = _leaves(c) + _leaves(sib)
    c.cow_copy(2, 4)
    _same(_rows(c, 4), rows)
    _same(_rows(sib, 4), sib_rows)
    return before, _leaves(c) + _leaves(sib)


def _swap_in(c):
    c.enable_host_tier(2)
    rows = _fill(c, 3, 3)
    kept = _leaves(c)
    hb = c.spill_block(3)
    assert not any(a.is_deleted() for a in kept)    # a spill only reads
    before = _leaves(c)
    c.swap_in_block(hb, 5)
    _same(_rows(c, 5), rows)
    return before, _leaves(c)


def _deserialize(c):
    before = _leaves(c)
    rows = _fill(c, 1, 4)
    _same(_rows(c, 1), rows)
    return before, _leaves(c)


def _adopt(c):
    src = _cache(kv_dtype=c.kv_dtype)
    rows = _fill(src, 2, 5)
    before, src_leaves = _leaves(c), _leaves(src)
    c.adopt_block_from(src, 2, 3)
    # the source is only read: it stays its owner's
    assert not any(a.is_deleted() for a in src_leaves)
    assert all(a is b for a, b in zip(src_leaves, _leaves(src)))
    _same(_rows(c, 3), rows)
    _same(_rows(src, 2), rows)
    return before, _leaves(c)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("rewrite", [_cow, _swap_in, _deserialize, _adopt],
                         ids=lambda f: f.__name__.strip("_"))
def test_pool_rewriters_consume_what_they_rewrite(rewrite, kv_dtype):
    c = _cache(kv_dtype=kv_dtype)
    before, after = rewrite(c)
    assert len(before) == len(after) >= (3 if kv_dtype else 1) * 2
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in after)


def test_draft_step_consumes_the_draft_pools(tiny_gpt):
    cfg, params = tiny_gpt
    srv = VARIANTS["per_column"](params, cfg)
    assert srv._draft_cache.pools_lock is srv.cache.pools_lock
    srv.submit([5, 6, 7], max_new_tokens=6)
    for _ in range(3):
        before = _leaves(srv._draft_cache) + _leaves(srv.cache)
        assert srv.step()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in
                       _leaves(srv._draft_cache) + _leaves(srv.cache))
    srv.close()


# ---------------------------------------------------------------------------
# nobody is caught holding dead pools
# ---------------------------------------------------------------------------

def test_handoff_from_a_second_thread_while_both_engines_step(tiny_gpt):
    """Two engines under their worker threads; this thread plays the
    fleet's handoff against them: it writes blocks into the source
    (deserialize_block), reads them back (serialize_block), and adopts
    them into the destination (adopt_block_from), while both step."""
    cfg, params = tiny_gpt
    kw = dict(num_slots=3, num_blocks=40, start=True)
    src, dst = _server(params, cfg, **kw), _server(params, cfg, **kw)
    prompts = [[3 + i, 7, 11 + i] for i in range(14)]
    want = []
    ref = _server(params, cfg, num_blocks=40)
    for p in prompts[:2]:
        f = ref.submit(p, max_new_tokens=50)
        ref.run_until_idle()
        want.append(list(f.result(timeout=5).token_ids))
    ref.close()

    it0 = global_registry().counter("serving.iterations").value()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        futs = [(s.submit(p, max_new_tokens=50), i)
                for i, p in enumerate(prompts) for s in (src, dst)]
        # blocks of this thread's own, as import_chain takes them
        with src._sched._lock:
            sblocks = src.cache.allocate(3)
        with dst._sched._lock:
            dblocks = dst.cache.allocate(3)
        written, rounds = {}, 0
        while not all(f.done() for f, _ in futs):
            i = rounds % 3
            written[i] = _fill(src.cache, sblocks[i], 100 + rounds)
            _same(_rows(src.cache, sblocks[i]), written[i])
            with dst._sched._lock:      # as router._transfer_chain_local
                dst.cache.adopt_block_from(src.cache, sblocks[i],
                                           dblocks[i])
            rounds += 1
        for f, i in futs:
            ids = list(f.result(timeout=60).token_ids)
            if i < 2:
                assert ids == want[i]
        # whatever the engines did meanwhile, the last rows written are
        # the rows both sides hold
        for i, rows in written.items():
            _same(_rows(src.cache, sblocks[i]), rows)
            _same(_rows(dst.cache, dblocks[i]), rows)
    finally:
        sys.setswitchinterval(interval)
        src.close(drain=False, timeout=30)
        dst.close(drain=False, timeout=30)
    iterations = global_registry().counter(
        "serving.iterations").value() - it0
    assert iterations >= 400 and rounds >= 10, (iterations, rounds)
    for s in (src, dst):
        assert s.get_stats()["engine_fault"] is None
        assert not s._worker.is_alive()


def test_fused_call_that_dies_with_the_pools_fail_stops_with_its_error(
        tiny_gpt):
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    first = srv.submit([5, 6, 7], max_new_tokens=3)
    srv.run_until_idle()
    assert len(first.result(timeout=5).token_ids) == 3
    fused = srv._fused

    def dying(pools, *args):
        fused(pools, *args)                 # consumes the pools
        raise RuntimeError("the device fell over")

    srv._fused = dying
    futs = [srv.submit([5 + i, 9], max_new_tokens=4) for i in range(2)]
    with pytest.raises(RuntimeError, match="the device fell over"):
        srv.step()
    for f in futs:
        with pytest.raises(RuntimeError, match="the device fell over"):
            f.result(timeout=5)
    assert "the device fell over" in srv.get_stats()["engine_fault"]
    # stopped, and not by tripping over a deleted array a step later
    assert srv.step() is False
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit([1, 2], max_new_tokens=2)
    srv.close()

    # a call that fails before it took the pools stops nothing
    srv = _server(params, cfg)
    fused = srv._fused
    srv._fused = lambda pools, *args: (_ for _ in ()).throw(
        ValueError("bad feed"))
    fut = srv.submit([5, 6, 7], max_new_tokens=3)
    with pytest.raises(ValueError, match="bad feed"):
        srv.step()
    assert srv.get_stats()["engine_fault"] is None
    assert not fut.done()
    srv.close(drain=False)


@pytest.mark.filterwarnings(
    # the thread that died before the pools went re-raises, for the
    # traceback on stderr
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("consumed", [True, False],
                         ids=["after_the_pools_went", "before"])
def test_worker_thread_fails_its_futures_and_exits(tiny_gpt, consumed):
    """Under the worker thread nobody can pump the engine again, so
    whatever kills a step there fails the futures with it: a call that
    ran out of device memory before it took the pools used to leave
    them waiting for ever (PR 26's chip runs: 900 s a time)."""
    cfg, params = tiny_gpt
    srv = _server(params, cfg, start=True)
    srv.submit([5, 6], max_new_tokens=2).result(timeout=60)
    fused = srv._fused

    def dying(pools, *args):
        if consumed:
            fused(pools, *args)
        raise RuntimeError("the device fell over")

    srv._fused = dying
    fut = srv.submit([5, 6, 7], max_new_tokens=3)
    with pytest.raises(RuntimeError, match="the device fell over"):
        fut.result(timeout=60)
    srv._worker.join(timeout=30)
    assert not srv._worker.is_alive()
    assert "the device fell over" in srv.get_stats()["engine_fault"]
    srv.close()
