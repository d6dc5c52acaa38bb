"""Tiered KV cache (ISSUE 18): host-RAM spill pool + swap-aware
preempt-and-resume scheduling.

Tier-1 (`serving` marker, manual pump, no sleeps). The contract under
test:

- HostKVTier mirrors the device geometry ((N, H_kv, bs, D) pools,
  int8 scales alongside codes), with loud double-free accounting and
  no NULL reservation (host ids never enter a block table);
- spill_block / swap_in_block round-trip KV BITWISE (dense f32/bf16
  and int8+scales), on ONE jitted signature per direction for the
  cache lifetime;
- prefix eviction SPILLS instead of destroying: the chain entry
  survives under tier="host", match() still token-verifies it (router
  affinity counts spilled depth), and claim() materializes it by
  swap-in instead of re-prefilling;
- THE bugfix regression: the PR 10 protected-entry rule extends to
  spilled entries — an admission that matched a chain keeps it alive
  across a concurrent spill AND across host-pool pressure
  (_drop_host_lru respects protect), so the match→claim window can
  never destroy what it is about to claim;
- chaos hooks spill_chain_at / preempt_request_at fire
  deterministically at injected iterations (fired counters, no
  sleeps);
- preempt→resume streams are BITWISE identical to an uninterrupted
  run: greedy dense, int8, GQA, and (single-request) the
  rejection-sampled spec mode;
- lazy admission under a host tier exceeds the full-reservation
  concurrency ceiling while every stream still completes bitwise (a
  preempted request's host blocks are its reservation — no mid-flight
  OOM);
- observability: serving.kv.tier.* gauges live server-labeled, the
  HBM ledger splits device/host (host_ram rows never inflate the
  resident total), kv_tier stats populate, lane records carry a tier
  tag;
- the fleet chaos path: spilled chains survive a replica kill into
  the resurrection re-warm — the popularity digest still names them
  and the survivor's host tier serves them without re-prefill.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.robustness import (ChaosInjector, CheckpointManager,
                                   SupervisorConfig,
                                   make_checkpoint_spawn)
from paddle_tpu.serving import (FleetRouter, GenerationServer,
                                GPTServingModel, PagedKVCache,
                                SpecDecodeConfig, prompt_chain_keys)
from paddle_tpu.serving.kv_cache import HostKVTier, fuse_kv, split_kv
from paddle_tpu.serving.prefix_cache import PrefixCacheIndex

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# HostKVTier: pool geometry and accounting
# ---------------------------------------------------------------------------

def _cache(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("num_blocks", 8)
    kw.setdefault("block_size", 4)
    return PagedKVCache(**kw)


def test_host_tier_mirrors_device_geometry():
    c = _cache()
    host = c.enable_host_tier(5)
    assert host is c.host and isinstance(host, HostKVTier)
    assert len(host.pools) == c.num_layers
    for layer in host.pools:
        assert set(layer) == {"kv"}
        assert layer["kv"].shape == (5, c.num_kv_heads, c.block_size,
                                     2 * c.head_dim)
        assert layer["kv"].dtype == np.dtype(c.dtype)
    # no NULL reservation: all 5 ids usable, id 0 included
    got = host.allocate(5)
    assert sorted(got) == [0, 1, 2, 3, 4]
    assert host.num_free == 0 and host.allocate(1) is None
    host.free(got)
    assert host.num_free == 5 and host.num_used == 0


def test_host_tier_int8_carries_scale_pools():
    c = _cache(kv_dtype="int8")
    host = c.enable_host_tier(3)
    layer = host.pools[0]
    assert set(layer) == {"kv", "k_scale", "v_scale"}
    assert layer["kv"].dtype == np.int8
    assert layer["k_scale"].dtype == np.float32
    assert layer["k_scale"].shape == (3, c.num_kv_heads, c.block_size)
    # unwritten rows carry scale 1.0 (the 0*NaN lesson from the
    # device pools)
    assert float(layer["k_scale"][0, 0, 0]) == 1.0
    # pool_bytes counts codes AND scales, both k and v, every layer
    per_layer = layer["kv"].nbytes + 2 * layer["k_scale"].nbytes
    assert host.pool_bytes() == c.num_layers * per_layer


def test_host_tier_double_free_raises():
    c = _cache()
    host = c.enable_host_tier(2)
    b = host.allocate(1)
    host.free(b)
    with pytest.raises(ValueError, match="double free"):
        host.free(b)


def test_enable_host_tier_is_once_per_lifetime():
    c = _cache()
    c.enable_host_tier(2)
    with pytest.raises(ValueError, match="already enabled"):
        c.enable_host_tier(4)
    with pytest.raises(ValueError, match="host tier needs"):
        _cache().enable_host_tier(0)


def test_spill_without_tier_raises():
    c = _cache()
    with pytest.raises(ValueError, match="enable_host_tier"):
        c.spill_block(1)
    with pytest.raises(ValueError, match="enable_host_tier"):
        c.swap_in_block(0, 1)


# ---------------------------------------------------------------------------
# spill / swap-in: bitwise round trip, one signature per direction
# ---------------------------------------------------------------------------

def _fill_block(c, blk, seed):
    """Write deterministic rows into device block `blk` of every
    layer/pool; returns the expected numpy rows for later compare."""
    rng = np.random.default_rng(seed)
    want = []
    for li in range(c.num_layers):
        row = {}
        for name, arr in c.pools[li].items():
            shape = arr.shape[1:]
            if arr.dtype == jnp.int8:
                vals = rng.integers(-127, 128, shape).astype(np.int8)
            else:
                vals = rng.standard_normal(shape).astype(
                    np.dtype(arr.dtype))
            c.pools[li][name] = arr.at[blk].set(vals)
            row[name] = vals
        want.append(row)
    return want


@pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8"])
def test_spill_swap_in_roundtrip_bitwise(kv_dtype):
    c = _cache(kv_dtype=kv_dtype)
    c.enable_host_tier(4)
    blocks = c.allocate(2)
    want = {b: _fill_block(c, b, seed=b + 1) for b in blocks}

    hbs = {b: c.spill_block(b) for b in blocks}
    assert c.host_spills == 2 and c.host.num_used == 2
    # the host rows hold the device bytes 1:1
    for b, hb in hbs.items():
        for li in range(c.num_layers):
            for name, vals in want[b][li].items():
                np.testing.assert_array_equal(
                    np.asarray(c.host.pools[li][name][hb]), vals)

    # swap back into FRESH device blocks: bitwise what was spilled
    dst = c.allocate(2)
    for (b, hb), d in zip(hbs.items(), dst):
        c.swap_in_block(hb, d)
        for li in range(c.num_layers):
            for name, vals in want[b][li].items():
                np.testing.assert_array_equal(
                    np.asarray(c.pools[li][name][d]), vals)
    assert c.host_swap_ins == 2
    # the owner frees host blocks explicitly — swap_in must not
    c.host.free(list(hbs.values()))
    assert c.host.num_used == 0


def test_one_jit_signature_per_direction():
    """The one-signature-per-lifetime invariant: the block id rides as
    a traced scalar and the host rows ride as jit arguments, so N
    spills and N swap-ins each compile exactly once."""
    c = _cache()
    c.enable_host_tier(6)
    blocks = c.allocate(4)
    for b in blocks:
        _fill_block(c, b, seed=b)
    hbs = [c.spill_block(b) for b in blocks]
    assert c._spill_fn._cache_size() == 1
    dst = c.allocate(3)
    for hb, d in zip(hbs, dst):
        c.swap_in_block(hb, d)
    assert c._swap_in_fn._cache_size() == 1


def test_sibling_pools_spill_and_swap_at_mirrored_ids():
    """A draft cache attached as a sibling mirrors the host tier at
    the SAME host ids: one spill moves target and draft KV together,
    one swap-in restores both (spec servers preempt cleanly)."""
    c = _cache()
    d = _cache(num_layers=1, num_heads=2, head_dim=4)
    c.attach_sibling(d)
    c.enable_host_tier(4)
    assert d.host is not None and d.host.num_blocks == 4
    blk = c.allocate(1)[0]
    want_c = _fill_block(c, blk, seed=3)
    want_d = _fill_block(d, blk, seed=4)
    hb = c.spill_block(blk)
    np.testing.assert_array_equal(
        split_kv(np.asarray(d.host.pools[0]["kv"][hb]))[0],
        split_kv(want_d[0]["kv"])[0])
    nb = c.allocate(1)[0]
    c.swap_in_block(hb, nb)
    np.testing.assert_array_equal(
        split_kv(np.asarray(c.pools[1]["kv"][nb]))[1],
        split_kv(want_c[1]["kv"])[1])
    np.testing.assert_array_equal(
        split_kv(np.asarray(d.pools[0]["kv"][nb]))[1],
        split_kv(want_d[0]["kv"])[1])
    c.host.free([hb])


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_spill_and_swap_in_return_the_same_k_and_v_rows(kv_dtype):
    """A block is one (H_kv, bs, 2 * head_dim) array, K beside V. Rows
    written as a K half and a V half come back from the host tier as
    the same two halves, in a block of another id."""
    c = _cache(kv_dtype=kv_dtype)
    c.enable_host_tier(2)
    rng = np.random.default_rng(17)
    shape = (c.num_kv_heads, c.block_size, c.head_dim)
    blk, other = c.allocate(2)
    want = []
    for li in range(c.num_layers):
        k, v = (rng.integers(-100, 100, shape).astype(np.dtype(c.dtype))
                for _ in range(2))
        c.pools[li]["kv"] = c.pools[li]["kv"].at[blk].set(fuse_kv(k, v))
        want.append((k, v))
    hb = c.spill_block(blk)
    c.swap_in_block(hb, other)
    for li, (k, v) in enumerate(want):
        for rows in (c.host.pools[li]["kv"][hb], c.pools[li]["kv"][other]):
            got_k, got_v = split_kv(np.asarray(rows))
            np.testing.assert_array_equal(got_k, k)
            np.testing.assert_array_equal(got_v, v)
    c.host.free([hb])


# ---------------------------------------------------------------------------
# prefix index: spill-instead-of-destroy, materialize on claim
# ---------------------------------------------------------------------------

def _chain(idx, c, prompt):
    """Register `prompt`'s full chunks as an idle chain (authors
    retired); returns (keys, blocks)."""
    bs = c.block_size
    n = len(prompt) // bs
    keys = prompt_chain_keys(prompt, bs)
    blocks = c.allocate(n)
    parent = None
    for i, (k, b) in enumerate(zip(keys, blocks)):
        assert idx.register(k, parent, prompt[i * bs:(i + 1) * bs], b)
        parent = k
    for b in blocks:
        c.unref(b)          # author retires: index ref is the last one
    return keys, blocks


def test_evict_spills_chain_and_claim_materializes():
    c = _cache(num_blocks=6, block_size=4)
    c.enable_host_tier(4)
    idx = PrefixCacheIndex(c)
    prompt = np.arange(3, 11, dtype=np.int32)          # 2 full chunks
    keys, blocks = _chain(idx, c, prompt)

    # leaf-first drain: the child spills, THEN the parent (its only
    # child is host-tier, so it is spill-eligible — the chain drains
    # instead of stalling after one leaf)
    assert idx.evict_lru() == blocks[1]
    assert idx.evict_lru() == blocks[0]
    assert idx.counts["spills"] == 2 and idx.host_entry_count() == 2
    assert c.num_free == c.usable_blocks       # device fully reclaimed

    # match still token-verifies the whole chain — None placeholders
    # keep len(match) the TRUE depth (router affinity sees it)
    m = idx.match(prompt, keys)
    assert m == [None, None]
    assert idx.peek(keys[0]) is None           # host entries peek None

    # claim materializes by swap-in: fully-device block list back
    got = idx.claim(keys, m, probed=2)
    assert len(got) == 2 and all(b is not None for b in got)
    assert idx.counts["swap_ins"] == 2
    assert idx.counts["reprefills_avoided"] == 2
    assert idx.host_entry_count() == 0 and c.host.num_used == 0
    assert idx.peek(keys[1]) is not None
    idx.release(got)
    idx.drop_gauges()


def test_materialize_key_lifts_spilled_entry_for_rewarm():
    """The router's handoff/re-warm path: peek None -> materialize_key
    -> peek yields a device block to adopt from."""
    c = _cache(num_blocks=5, block_size=4)
    c.enable_host_tier(2)
    idx = PrefixCacheIndex(c)
    prompt = np.arange(5, 9, dtype=np.int32)
    keys, _ = _chain(idx, c, prompt)
    assert idx.evict_lru() is not None
    assert idx.peek(keys[0]) is None
    db = idx.materialize_key(keys[0])
    assert db is not None
    assert idx.peek(keys[0])[0] == db
    assert idx.materialize_key(keys[0]) is None    # already device
    assert idx.materialize_key("nope") is None     # absent
    idx.drop_gauges()


def test_protected_entry_survives_match_to_claim_race_across_spill():
    """THE eviction-accounting regression (the PR 10 protected-entry
    rule extended to spilled entries): an admission matched chain A,
    then — inside the same match→claim window — pool pressure spills A
    and a SECOND eviction hits a full host pool. _drop_host_lru must
    skip the protected A (dropping it would destroy the KV the claim
    is about to swap in) and the device eviction must fall back to
    destroying the unprotected chain instead."""
    c = _cache(num_blocks=6, block_size=4)
    c.enable_host_tier(1)                   # ONE host block: A fills it
    idx = PrefixCacheIndex(c)
    prompt_a = np.arange(3, 7, dtype=np.int32)
    prompt_b = np.arange(20, 24, dtype=np.int32)
    keys_a, _ = _chain(idx, c, prompt_a)
    keys_b, blocks_b = _chain(idx, c, prompt_b)
    protect = frozenset(keys_a)

    m = idx.match(prompt_a, keys_a)
    assert m == [idx.peek(keys_a[0])[0]]

    # spill A (the race: protect allows eviction of OTHER entries; A
    # itself got spilled by earlier un-protected pressure)
    assert idx.evict_lru(frozenset()) is not None
    assert idx.host_entry_count() == 1 and c.host.num_free == 0

    # second eviction under THIS admission's protect: host full, the
    # only host entry is protected -> not droppable -> B is destroyed
    assert idx._drop_host_lru(protect) is None
    assert idx.evict_lru(protect) == blocks_b[0]
    assert idx.counts["host_drops"] == 0
    assert keys_a[0] in idx._entries           # A survived, spilled
    assert keys_b[0] not in idx._entries       # B destroyed outright

    # the claim lands: matched-as-None A swaps in, bitwise-live
    m2 = idx.match(prompt_a, keys_a)
    assert m2 == [None]
    got = idx.claim(keys_a, m2, probed=1)
    assert len(got) == 1 and got[0] is not None
    assert idx.counts["reprefills_avoided"] == 1
    idx.release(got)                           # the request retires
    # without protect, the unprotected host entry IS droppable
    assert idx.evict_lru() is not None         # A spills again (idle)
    assert idx._drop_host_lru() is not None
    assert idx.counts["host_drops"] == 1
    idx.drop_gauges()


# ---------------------------------------------------------------------------
# engine integration: tiny GPT
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
    return cfg, gpt.load_params(scope, cfg), main, scope, exe


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _run(srv, prompts, n_new):
    futs = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    srv.run_until_idle()
    return [list(f.result(timeout=5).token_ids) for f in futs]


def test_chaos_spill_then_hit_serves_from_host_tier(tiny_gpt):
    """spill_chain_at parks an idle chain in the host tier at an exact
    injected iteration (fired counter proves it), and the next hit on
    that chain swaps it back in — reprefills_avoided moves, the stream
    is bitwise the device-tier one, one fused-step signature."""
    cfg, params, *_ = tiny_gpt
    rng = np.random.default_rng(21)
    prompt = rng.integers(3, cfg.vocab_size, 17).astype(np.int32)

    ref = _run(_server(params, cfg, prefix_cache=True), [prompt], 6)[0]

    chaos = ChaosInjector()
    srv = _server(params, cfg, prefix_cache=True, host_kv_blocks=8,
                  chaos=chaos)
    first = _run(srv, [prompt], 6)[0]
    assert first == ref
    # inject: spill BOTH chain entries at the next live iteration
    chaos.spill_chain_at(srv._sched.iteration + 1, 2)
    second = _run(srv, [prompt], 6)[0]
    assert second == ref                       # bitwise through the tier
    assert chaos.fired["spill"] == 2
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1
    kt = st["kv_tier"]
    assert kt["host_blocks"] == 8
    assert kt["spills"] >= 2 and kt["swap_ins"] >= 2
    assert kt["reprefills_avoided"] >= 2
    assert st["prefix"]["hits"] >= 2
    # the tier gauges are LIVE and server-labeled while serving
    g = global_registry().gauge("serving.kv.tier.reprefills_avoided")
    assert any(c.value() >= 2 for _lbl, c in g.series())
    srv.close()
    # ... and retired on close (the mesh/quant gauge discipline)
    assert not list(
        global_registry().gauge("serving.kv.tier.host_blocks").series())


def test_host_ram_ledger_rows_never_inflate_resident_total(tiny_gpt):
    """The HBM ledger's device/host split: a host-tier server adds a
    kind="host_ram" row carrying host_pool_bytes, and the RESIDENT
    total (what the OOM math protects) is unchanged by it."""
    from paddle_tpu.observability.compile_insight import (
        LEDGER_KINDS, RESIDENT_KINDS, hbm_ledger)
    assert "host_ram" in LEDGER_KINDS
    assert "host_ram" not in RESIDENT_KINDS    # never in the OOM math
    cfg, params, *_ = tiny_gpt
    off = _server(params, cfg)
    on = _server(params, cfg, host_kv_blocks=8)
    st_off, st_on = off.get_stats(), on.get_stats()
    assert st_on["memory"]["host_ram"] == on.cache.host_pool_bytes()
    assert "host_ram" not in st_off["memory"]
    # resident kinds are IDENTICAL: the host pool adds no HBM
    assert st_on["memory"]["kv_cache"] == st_off["memory"]["kv_cache"]
    assert st_on["memory"]["params"] == st_off["memory"]["params"]
    rows = {e["name"]: e for e in hbm_ledger().snapshot()["entries"]
            if e["component"] == on._ledger_id}
    host_row = rows["kv_pool_host"]
    assert host_row["kind"] == "host_ram"
    assert host_row["detail"]["tier"] == "host"
    assert host_row["detail"]["num_blocks"] == 8
    assert rows["kv_pool"]["detail"]["tier"] == "device"
    assert st_off.get("kv_tier") is None
    assert st_on["kv_tier"]["host_pool_bytes"] > 0
    off.close()
    on.close()


def _preempt_parity(params, cfg, *, n_new=10, **kw):
    """Run the same greedy stream uninterrupted and preempted-at-6,
    return (ref_ids, ids, stats, chaos)."""
    rng = np.random.default_rng(33)
    prompts = [rng.integers(3, cfg.vocab_size,
                            int(rng.integers(9, 14))).astype(np.int32)
               for _ in range(3)]
    ref = _run(_server(params, cfg, **kw), prompts, n_new)

    chaos = ChaosInjector()
    srv = _server(params, cfg, host_kv_blocks=24, chaos=chaos, **kw)
    futs = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    chaos.preempt_request_at(6, futs[0].request_id)
    srv.run_until_idle()
    ids = [list(f.result(timeout=5).token_ids) for f in futs]
    st = srv.get_stats()
    srv.close()
    return ref, ids, st, chaos


def test_preempt_resume_bitwise_greedy_dense(tiny_gpt):
    cfg, params, *_ = tiny_gpt
    ref, ids, st, chaos = _preempt_parity(params, cfg)
    assert chaos.fired["preempt"] == 1
    assert st["preempts"] == 1 and st["resumes"] == 1
    assert ids == ref                          # BITWISE, all 3 streams
    assert st["fused_step_signatures"] == 1
    assert st["blocks_free"] == st["blocks_total"]
    assert st["kv_tier"]["host_blocks_used"] == 0   # all swapped back
    assert st["kv_tier"]["preempted_depth"] == 0


def test_preempt_resume_bitwise_int8(tiny_gpt):
    cfg, params, *_ = tiny_gpt
    ref, ids, st, _ = _preempt_parity(params, cfg, kv_dtype="int8")
    assert st["preempts"] == 1 and st["resumes"] == 1
    assert ids == ref
    assert st["kv_quant"]["kv_dtype"] == "int8"


def test_preempt_resume_bitwise_gqa(tiny_gpt):
    cfg, params, *_ = tiny_gpt
    kv = 2
    gqa_cfg = gpt.GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        inner_size=cfg.inner_size, max_position=cfg.max_position,
        dropout=0.0, kv_heads=kv)
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
    ref, ids, st, _ = _preempt_parity(gqa_params, gqa_cfg)
    assert st["preempts"] == 1 and st["resumes"] == 1
    assert ids == ref


def test_preempt_resume_bitwise_sampled_spec(tiny_gpt):
    """The sampled mode: rejection-spec with a seeded RNG is stream-
    deterministic for a SINGLE request, so a preempt+resume must
    reproduce the uninterrupted sampled stream bitwise (the draft
    sibling's KV rides the same host blocks)."""
    cfg, params, *_ = tiny_gpt
    dcfg = gpt.GPTConfig(vocab_size=cfg.vocab_size, hidden_size=64,
                         num_layers=2, num_heads=2, inner_size=128,
                         max_position=128, dropout=0.0)
    dmain, dstart = framework.Program(), framework.Program()
    dmain.random_seed = dstart.random_seed = 99
    with framework.program_guard(dmain, dstart):
        gpt.build_lm_net(dcfg, seq_len=8)
    dscope = Scope()
    exe = fluid.Executor()
    with scope_guard(dscope):
        exe.run(dstart)
    dparams = gpt.load_params(dscope, dcfg)

    def spec_server(**kw):
        return _server(params, cfg,
                       spec=SpecDecodeConfig(
                           GPTServingModel(dparams, dcfg),
                           k=3, mode="rejection", seed=123), **kw)

    prompt = np.arange(3, 15, dtype=np.int32)
    ref_srv = spec_server()
    ref = _run(ref_srv, [prompt], 8)[0]
    ref_srv.close()

    chaos = ChaosInjector()
    srv = spec_server(host_kv_blocks=24, chaos=chaos)
    f = srv.submit(prompt, max_new_tokens=8)
    chaos.preempt_request_at(5, f.request_id)
    srv.run_until_idle()
    ids = list(f.result(timeout=5).token_ids)
    st = srv.get_stats()
    assert chaos.fired["preempt"] == 1
    assert st["preempts"] == 1 and st["resumes"] == 1
    assert ids == ref                          # bitwise, sampled
    assert st["spec"]["mode"] == "rejection"
    srv.close()


def test_lazy_admission_exceeds_full_reservation_ceiling(tiny_gpt):
    """Retiring the concurrency ceiling: a 9-block pool full-reserves
    4 blocks per (8 prompt + 24 new) request — at most 2 concurrent.
    With a host tier the scheduler admits on the PREFILL footprint and
    pledges the rest against host blocks, so all 3 run concurrently;
    pressure preempts-and-resumes instead of OOMing, and every stream
    is still bitwise the big-pool reference."""
    cfg, params, *_ = tiny_gpt
    rng = np.random.default_rng(44)
    prompts = [rng.integers(3, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    ref = _run(_server(params, cfg), prompts, 24)

    def max_active(srv):
        futs = [srv.submit(p, max_new_tokens=24) for p in prompts]
        peak = 0
        while srv.step():
            peak = max(peak, srv._sched.active_count)
        return peak, [list(f.result(timeout=5).token_ids)
                      for f in futs]

    base = _server(params, cfg, num_blocks=9)
    base_peak, base_ids = max_active(base)
    assert base_peak <= 2 and base_ids == ref
    base.close()

    srv = _server(params, cfg, num_blocks=9, host_kv_blocks=16)
    peak, ids = max_active(srv)
    st = srv.get_stats()
    assert peak == 3                   # above the 2-slot ceiling
    assert peak > base_peak
    assert ids == ref                  # bitwise through any preempts
    assert st["preempts"] >= 1         # pressure parked someone...
    assert st["resumes"] == st["preempts"]     # ...and brought it back
    assert st["blocks_free"] == st["blocks_total"]
    assert st["kv_tier"]["host_blocks_used"] == 0
    srv.close()


def test_lane_records_carry_tier_tag(tiny_gpt):
    """LANE_FIELDS grew a `tier` tag: fresh lanes snapshot as
    "device", a resumed (swapped-in) lane as "host"."""
    from paddle_tpu.observability.serving_telemetry import LANE_FIELDS
    assert LANE_FIELDS[-3:] == ("tier", "group", "beam_rank")
    cfg, params, *_ = tiny_gpt
    chaos = ChaosInjector()
    srv = _server(params, cfg, host_kv_blocks=16, chaos=chaos)
    f = srv.submit(np.arange(3, 13, dtype=np.int32), max_new_tokens=8)
    chaos.preempt_request_at(5, f.request_id)
    tiers = set()
    while srv.step():
        for t in srv._sched.lane_snapshot():
            lane = dict(zip(LANE_FIELDS, t))
            tiers.add(lane["tier"])
    f.result(timeout=5)
    assert tiers == {"device", "host"}     # resumed lane re-tagged
    srv.close()


# ---------------------------------------------------------------------------
# fleet: spilled chains survive a replica kill into resurrection re-warm
# ---------------------------------------------------------------------------

@pytest.mark.fleet
@pytest.mark.chaos
def test_spilled_chains_survive_kill_into_resurrection_rewarm(
        tiny_gpt, tmp_path):
    """Kill-a-replica chaos over a host-tiered fleet: the tenant chain
    is SPILLED on the survivor when replica 0 dies. The popularity
    digest still names the chain (it lives in the router, not the dead
    index), resurrection re-warms the fresh replica from it, the
    survivor's affinity depth still counts the spilled chunks, and a
    follow-up tenant request is served from the HOST tier — swap-ins
    move, re-prefills are avoided, the stream is bitwise."""
    cfg, params, main, scope, exe = tiny_gpt
    rng = np.random.default_rng(55)
    kw = dict(num_slots=3, block_size=8, max_context=64, chunk=4,
              start=False, prefix_cache=True, host_kv_blocks=16)
    manager = CheckpointManager(str(tmp_path / "ck"), program=main)
    manager.save(exe, 0, scope=scope)
    spawn = make_checkpoint_spawn(manager, cfg, **kw)

    tenant = rng.integers(3, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([tenant, rng.integers(
        3, cfg.vocab_size, 3).astype(np.int32)]) for _ in range(4)]
    ref_ids = _run(_server(params, cfg, prefix_cache=True), prompts, 5)

    kill_chaos = ChaosInjector()
    engine_chaos = [ChaosInjector() for _ in range(2)]
    servers = [_server(params, cfg, **dict(kw, chaos=engine_chaos[i]))
               for i in range(2)]
    router = FleetRouter(
        servers, start=False, chaos=kill_chaos, spawn_fn=spawn,
        supervisor=SupervisorConfig(backoff_heartbeats=2,
                                    warm_chains=2))
    futs = [router.submit(p, max_new_tokens=5) for p in prompts]
    router.run_until_idle()
    assert [list(f.result(timeout=5).token_ids)
            for f in futs] == ref_ids

    # spill every idle chain on every replica that holds one (the
    # deterministic chaos hook, fired at the next engine iteration)
    tkeys = prompt_chain_keys(prompts[0], 8)
    for ci, rep in zip(engine_chaos, router.replicas()):
        idx = rep.server._prefix
        if not len(idx):
            continue
        ci.spill_chain_at(rep.server._sched.iteration + 1, len(idx))
        probe = rep.server.submit(
            rng.integers(3, cfg.vocab_size, 4).astype(np.int32),
            max_new_tokens=1)
        rep.server.run_until_idle()
        probe.result(timeout=5)
        assert ci.fired["spill"] >= 1
        assert idx.host_entry_count() >= 2
        # spilled chunks STILL count toward affinity depth
        assert rep.affinity_depth(prompts[0], tkeys) >= 2

    # kill replica 0 at the next router iteration; the supervisor
    # resurrects it and re-warms from the digest — which survived the
    # death AND names the (now spilled) tenant chain
    survivor = router.replicas()[1]
    before = survivor.server._prefix.counts["reprefills_avoided"]
    kill_chaos.kill_replica_at(router.iteration + 1, 0)
    f2 = router.submit(prompts[0], max_new_tokens=5)
    router.run_until_idle()
    assert list(f2.result(timeout=5).token_ids) == ref_ids[0]
    assert kill_chaos.fired["replica_kill"] == 1
    st = router.get_stats()
    assert st["live_replicas"] == 2 and st["resurrections"] == 1
    assert st["supervisor"]["warm_prompts"] >= 1
    assert st["popularity_digest"]["entries"] >= 2

    # the HOST tier served the chain: affinity routed f2 to the
    # survivor (spilled depth beats cold replicas) and claim swapped
    # the tenant chunks in instead of re-prefilling
    assert survivor.server._prefix.counts["reprefills_avoided"] >= \
        before + 2
    assert survivor.server.get_stats()["kv_tier"]["swap_ins"] >= 2

    # follow-up tenant traffic now finds the chain device-tier, bitwise
    f3 = survivor.server.submit(prompts[1], max_new_tokens=5)
    survivor.server.run_until_idle()
    assert list(f3.result(timeout=5).token_ids) == ref_ids[1]
    router.close()
