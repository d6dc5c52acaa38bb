"""FleetRouter: multi-replica serving (paddle_tpu/serving/router.py).

Tier-1 (`fleet` marker): manual-drive replicas pumped by the router's
own step() loop, time from injected chaos clocks, no sleeps. The
contract under test:

- affinity keys derive from the SAME chain hash as the prefix index
  (no second hasher), and affinity routing beats least-loaded for
  shared-prefix streams (a hot tenant lands on the warm replica even
  when it is the busier one);
- admission sheds on `check_slo` BURN RATE, never on queue depth, and
  a rejection is a structured AdmissionRejected with a retry-after
  hint;
- the e2e fleet test: a mixed-tenant staggered stream over 3 replicas
  with a chaos replica kill mid-stream — every request completes with
  ids bitwise-identical to a single-server run, streams never deliver
  a token twice, the prefix hit rate recovers on the survivors, and
  each replica keeps its invariants (one fused-step signature, HBM
  ledger rows retired on kill);
- disaggregated prefill/decode: the KV handoff moves full-chunk
  blocks across replica caches (adopt_block_from + index
  registration) so decode replicas prefill only the tails, ids stay
  bitwise;
- the fleet registry view exposes every replica's serving.* series
  with a replica= label from ONE mount.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.robustness import ChaosInjector
from paddle_tpu.serving import (AdmissionPolicy, AdmissionRejected,
                                FleetRouter, GenerationServer,
                                GPTServingModel, PagedKVCache,
                                PrefixCacheIndex, RouterPolicy,
                                prompt_chain_keys)
from paddle_tpu.serving.kv_cache import fuse_kv, split_kv

pytestmark = pytest.mark.fleet


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
    kw.setdefault("prefix_cache", True)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _mixed_prompts(cfg, n, rng, tenant, shared_every=3):
    """Mixed-tenant stream: every `shared_every`-th request shares the
    tenant prefix plus a short unique suffix; the rest are private."""
    out = []
    for i in range(n):
        if i % shared_every == 0:
            sfx = rng.integers(3, cfg.vocab_size, 3).astype(np.int32)
            out.append(np.concatenate([tenant, sfx]))
        else:
            out.append(rng.integers(
                3, cfg.vocab_size,
                int(rng.integers(8, 24))).astype(np.int32))
    return out


def _reference_ids(params, cfg, prompts, n_new):
    srv = _server(params, cfg)
    futs = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    srv.run_until_idle()
    ids = [list(f.result(timeout=5).token_ids) for f in futs]
    srv.close()
    return ids


# ---------------------------------------------------------------------------
# affinity keys + transfer primitive
# ---------------------------------------------------------------------------

def test_chain_keys_match_index_derivation():
    """The router's affinity keys ARE the index's chain keys — one
    hash implementation, bitwise-equal keys (a second hasher would
    silently never match a replica's cache)."""
    cache = PagedKVCache(1, 2, 4, 9, block_size=8)
    idx = PrefixCacheIndex(cache)
    prompt = np.arange(35, dtype=np.int32)
    assert prompt_chain_keys(prompt, 8) == idx.chain_keys(prompt, 4)
    # partial chunks never key
    assert prompt_chain_keys(prompt[:7], 8) == []


def test_adopt_block_from_copies_rows_across_caches():
    src = PagedKVCache(2, 2, 4, 6, block_size=4)
    dst = PagedKVCache(2, 2, 4, 9, block_size=4)    # num_blocks may differ
    (sb,) = src.allocate(1)
    (db,) = dst.allocate(1)
    rng = np.random.default_rng(3)
    want = []
    for i in range(2):
        rows = rng.standard_normal((2, 4, 4)).astype(np.float32)
        src.pools[i]["kv"] = src.pools[i]["kv"].at[sb].set(
            fuse_kv(rows, rows + 1))
        want.append(rows)
    dst.adopt_block_from(src, sb, db)
    for i in range(2):
        k, v = split_kv(np.asarray(dst.pools[i]["kv"][db]))
        np.testing.assert_array_equal(k, want[i])
        np.testing.assert_array_equal(v, want[i] + 1)
    other = PagedKVCache(2, 4, 4, 6, block_size=4)  # wrong head count
    with pytest.raises(ValueError):
        other.adopt_block_from(src, sb, 1)


# ---------------------------------------------------------------------------
# construction validation
# ---------------------------------------------------------------------------

def test_router_validation(tiny_gpt):
    cfg, params = tiny_gpt
    a = _server(params, cfg, block_size=8)
    b = _server(params, cfg, block_size=16, max_context=32)
    with pytest.raises(ValueError, match="block_size"):
        FleetRouter([a, b], start=False)
    b.close()
    # disaggregated pools must be disjoint and prefix-cached
    with pytest.raises(ValueError, match="disjoint"):
        RouterPolicy("disaggregated", prefill=(0,), decode=(0, 1))
    no_pfx = _server(params, cfg, prefix_cache=False)
    with pytest.raises(ValueError, match="prefix_cache"):
        FleetRouter([a, no_pfx], start=False,
                    policy=RouterPolicy("disaggregated", prefill=(0,),
                                        decode=(1,)))
    # SLO admission needs telemetry everywhere
    no_tel = _server(params, cfg, telemetry=False)
    with pytest.raises(ValueError, match="telemetry"):
        FleetRouter([a, no_tel], start=False,
                    admission=AdmissionPolicy({"ttft_ms": {"p99": 1.0}}))
    for s in (a, no_pfx, no_tel):
        s.close()


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------

def test_affinity_beats_least_loaded_on_shared_prefix(tiny_gpt):
    """A shared-prefix request routes to the replica whose cache holds
    the prefix even when that replica is the BUSIER one; a cold prompt
    falls back to power-of-two-choices (the less-loaded replica)."""
    cfg, params = tiny_gpt
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    rng = np.random.default_rng(1)
    tenant = rng.integers(3, cfg.vocab_size, 16).astype(np.int32)
    warm = np.concatenate([tenant,
                           rng.integers(3, cfg.vocab_size,
                                        2).astype(np.int32)])
    # warm replica 0's prefix cache directly (router pumps all replicas)
    f0 = servers[0].submit(warm, max_new_tokens=2)
    router.run_until_idle()
    f0.result(timeout=5)
    # make replica 0 the busier one: a long private request keeps its
    # slots occupied while the shared-prefix submit routes
    busy = servers[0].submit(
        rng.integers(3, cfg.vocab_size, 30).astype(np.int32),
        max_new_tokens=30)
    for _ in range(3):
        router.step()
    load0 = servers[0]._sched.load_snapshot()
    load1 = servers[1]._sched.load_snapshot()
    assert load0[1] > load1[1]          # replica 0 busier by active slots
    reg = global_registry()
    aff0 = reg.counter("serving.fleet.routed").labels(
        policy="affinity").value()
    adm1_before = servers[1].get_stats()["admitted"]
    hits_before = servers[0].get_stats()["prefix"]["hits"]
    fut = router.submit(
        np.concatenate([tenant, rng.integers(3, cfg.vocab_size,
                                             2).astype(np.int32)]),
        max_new_tokens=2)
    router.run_until_idle()
    fut.result(timeout=5)
    busy.result(timeout=5)
    assert reg.counter("serving.fleet.routed").labels(
        policy="affinity").value() == aff0 + 1
    assert servers[0].get_stats()["prefix"]["hits"] > hits_before
    assert servers[1].get_stats()["admitted"] == adm1_before
    # cold prompt: no affinity anywhere -> p2c lands on the less-loaded
    busy2 = servers[0].submit(
        rng.integers(3, cfg.vocab_size, 30).astype(np.int32),
        max_new_tokens=30)
    for _ in range(2):
        router.step()
    ll0 = reg.counter("serving.fleet.routed").labels(
        policy="least_loaded").value()
    cold = router.submit(rng.integers(3, cfg.vocab_size,
                                      9).astype(np.int32),
                         max_new_tokens=2)
    assert servers[1].get_stats()["admitted"] == adm1_before  # queued yet
    router.run_until_idle()
    cold.result(timeout=5)
    busy2.result(timeout=5)
    assert reg.counter("serving.fleet.routed").labels(
        policy="least_loaded").value() == ll0 + 1
    assert servers[1].get_stats()["admitted"] == adm1_before + 1
    router.close()


def test_shed_on_burn_rate_not_queue_depth(tiny_gpt):
    """Admission control is SLO-driven: a breached burn rate sheds
    even with an EMPTY queue, and a deep queue admits as long as the
    error budget holds. Rejections carry the retry-after hint."""
    cfg, params = tiny_gpt
    rng = np.random.default_rng(2)
    prompt = rng.integers(3, cfg.vocab_size, 12).astype(np.int32)
    # (a) burn breach, empty queue -> shed
    chaos = ChaosInjector()
    for it in range(1, 50):
        chaos.advance_clock_at(it, 500.0)   # 500 ms per iteration
    srv = _server(params, cfg, chaos=chaos)
    router = FleetRouter(
        [srv], start=False,
        admission=AdmissionPolicy({"ttft_ms": {"p50": 10.0}},
                                  retry_after_ms=50.0))
    f = router.submit(prompt, max_new_tokens=3)     # cold digest admits
    router.run_until_idle()
    f.result(timeout=5)
    assert srv.get_stats()["queue_depth"] == 0      # nothing queued
    sheds0 = router.counts["sheds"]
    with pytest.raises(AdmissionRejected) as ei:
        router.submit(prompt, max_new_tokens=3)
    assert ei.value.scope == "fleet"
    assert ei.value.burn_rate is not None and ei.value.burn_rate > 1.0
    assert ei.value.retry_after_ms >= 50.0
    assert router.counts["sheds"] == sheds0 + 1
    assert global_registry().counter("serving.fleet.sheds").labels(
        scope="fleet").value() >= 1
    router.close()
    # (b) deep queue, healthy burn -> admits (queue depth is NOT the
    # signal)
    srv2 = _server(params, cfg, num_slots=1)
    router2 = FleetRouter(
        [srv2], start=False,
        admission=AdmissionPolicy({"ttft_ms": {"p50": 1e9}}))
    futs = [router2.submit(prompt, max_new_tokens=2) for _ in range(5)]
    assert srv2.get_stats()["queue_depth"] >= 3     # deep queue, no shed
    router2.run_until_idle()
    for f in futs:
        f.result(timeout=5)
    router2.close()


def test_fleet_check_slo_merges_replica_digests(tiny_gpt):
    cfg, params = tiny_gpt
    rng = np.random.default_rng(4)
    prompt = rng.integers(3, cfg.vocab_size, 12).astype(np.int32)
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    futs = [router.submit(prompt, max_new_tokens=2,
                          priority=i % 2) for i in range(4)]
    router.run_until_idle()
    for f in futs:
        f.result(timeout=5)
    rep = router.check_slo({"ttft_ms": {"p50": 1e9}})
    (chk,) = rep["checks"]
    assert rep["ok"] and chk["met"] and chk["observed_ms"] is not None
    assert chk["burn_rate"] == 0.0      # nothing over a 1e9 ms target
    with pytest.raises(ValueError, match="unknown SLO metric"):
        router.check_slo({"nope_ms": {"p50": 1.0}})
    router.close()


# ---------------------------------------------------------------------------
# lifecycle: drain, cancel, kill + failover (the acceptance chaos test)
# ---------------------------------------------------------------------------

def test_drain_replica_finishes_inflight_then_closes(tiny_gpt):
    cfg, params = tiny_gpt
    rng = np.random.default_rng(5)
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    long = router.submit(rng.integers(3, cfg.vocab_size,
                                      10).astype(np.int32),
                         max_new_tokens=12)
    for _ in range(2):
        router.step()
    router.drain_replica(0)
    assert servers[0] is router.replicas()[0].server
    # new submits only land on replica 1
    adm0 = servers[0].get_stats()["admitted"]
    f2 = router.submit(rng.integers(3, cfg.vocab_size,
                                    9).astype(np.int32),
                       max_new_tokens=2)
    router.run_until_idle()
    long.result(timeout=5)              # in-flight finished normally
    f2.result(timeout=5)
    assert servers[0].get_stats()["admitted"] == adm0
    assert router.replicas()[0].state == "drained"
    assert router.health()["live_replicas"] == 1
    router.close()


def test_client_cancel_through_router(tiny_gpt):
    cfg, params = tiny_gpt
    rng = np.random.default_rng(6)
    servers = [_server(params, cfg)]
    router = FleetRouter(servers, start=False)
    fut = router.submit(rng.integers(3, cfg.vocab_size,
                                     16).astype(np.int32),
                        max_new_tokens=20)
    for _ in range(3):
        router.step()
    assert fut.cancel()
    router.run_until_idle()
    assert fut.cancelled()
    # the slot and blocks came back; no failover was attempted
    assert servers[0].get_stats()["active_slots"] == 0
    assert router.counts["failovers"] == 0
    assert router.pending() == 0
    router.close()


def test_fleet_kill_mid_stream_failover_e2e(tiny_gpt):
    """THE acceptance chaos test: 3 replicas, mixed-tenant staggered
    stream, one replica killed mid-stream. Every request completes
    with ids bitwise-identical to an unkilled single-server run, no
    stream delivers a token twice, a shared-prefix follow-up hits a
    SURVIVOR's prefix cache, and every replica keeps its invariants
    (fused-step signature budget, ledger rows retired on kill)."""
    cfg, params = tiny_gpt
    rng = np.random.default_rng(0)
    tenant = rng.integers(3, cfg.vocab_size, 16).astype(np.int32)
    prompts = _mixed_prompts(cfg, 9, rng, tenant)
    ref_ids = _reference_ids(params, cfg, prompts, 8)

    chaos = ChaosInjector().kill_replica_at(4, 0)
    servers = [_server(params, cfg) for _ in range(3)]
    router = FleetRouter(servers, start=False, chaos=chaos)
    streams = {i: [] for i in range(len(prompts))}
    futs = []
    # staggered: first wave, a few iterations, second wave
    for i, p in enumerate(prompts[:5]):
        futs.append(router.submit(
            p, max_new_tokens=8,
            stream=lambda rid, t, toks=streams[i]: toks.append(t)))
    for _ in range(2):
        router.step()
    for i, p in enumerate(prompts[5:], start=5):
        futs.append(router.submit(
            p, max_new_tokens=8,
            stream=lambda rid, t, toks=streams[i]: toks.append(t)))
    router.run_until_idle()
    results = [f.result(timeout=5) for f in futs]

    assert chaos.fired["replica_kill"] == 1
    assert router.counts["failovers"] >= 1      # someone was in flight
    assert router.replicas()[0].state == "dead"
    assert router.get_stats()["live_replicas"] == 2
    # bitwise-correct completed ids, router rids preserved
    ids = [list(r.token_ids) for r in results]
    assert ids == ref_ids
    assert [r.request_id for r in results] == list(range(len(prompts)))
    # stream dedupe: exactly the result ids, no token twice
    for i, r in enumerate(results):
        assert streams[i] == list(r.token_ids)
    # shared-prefix follow-up re-hits a survivor's cache
    hits0 = sum(s.get_stats()["prefix"]["hits"] for s in servers[1:])
    f2 = router.submit(
        np.concatenate([tenant, rng.integers(
            3, cfg.vocab_size, 2).astype(np.int32)]), max_new_tokens=2)
    router.run_until_idle()
    f2.result(timeout=5)
    assert sum(s.get_stats()["prefix"]["hits"]
               for s in servers[1:]) > hits0
    # invariants through the router: one fused signature per replica,
    # the dead replica's HBM-ledger rows retired by the kill
    from paddle_tpu.observability.compile_insight import hbm_ledger
    for s in servers:
        assert s.get_stats()["fused_step_signatures"] == 1
    assert not hbm_ledger().component_bytes(servers[0]._ledger_id)
    # failover metric recorded
    assert global_registry().counter(
        "serving.fleet.failovers").value() >= 1
    # replica gauges: the dead replica's load series is gone, the
    # live-replica gauge reads 2
    g = global_registry().gauge("serving.fleet.replica_load")
    series = {lbl.get("replica") for lbl, _c in g.series()
              if lbl.get("router") == router.name}
    assert router.replicas()[0].name not in series
    assert global_registry().gauge("serving.fleet.replicas").labels(
        router=router.name).value() == 2
    router.close()
    # close retires the router's gauge series entirely
    series_after = {lbl for lbl, _c in global_registry().gauge(
        "serving.fleet.replica_load").series()
        if lbl.get("router") == router.name}
    assert not series_after


def test_engine_fault_death_fails_over(tiny_gpt):
    """A replica dying ORGANICALLY (chaos KV poison -> NonFiniteError
    fail-stop) is also a fleet event: the router marks it dead and
    re-admits its stream on the survivor, ids intact."""
    cfg, params = tiny_gpt
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(2)]
    ref_ids = _reference_ids(params, cfg, prompts, 6)
    poison = ChaosInjector().poison_serving_at(4)
    a = _server(params, cfg, chaos=poison, telemetry=False)
    b = _server(params, cfg)
    router = FleetRouter([a, b], start=False)
    # route both onto the poisoned replica deliberately; the pump
    # CONTAINS the engine's NonFiniteError (the fleet outlives one
    # replica) — the direct submits fail, the replica reads dead
    futs = [a.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(200):
        if all(f.done() for f in futs):
            break
        router.step()
    for f in futs:
        with pytest.raises(Exception):
            f.result(timeout=5)
    assert router.replicas()[0].state == "dead"
    # an ORGANIC death (no kill_replica call) also drops the dead
    # replica's load-gauge series — the spec's 'removed when the
    # replica dies' holds on every death path
    series = {lbl.get("replica") for lbl, _c in global_registry().gauge(
        "serving.fleet.replica_load").series()
        if lbl.get("router") == router.name}
    assert router.replicas()[0].name not in series
    # router-routed requests now land on the survivor and complete
    futs2 = [router.submit(p, max_new_tokens=6) for p in prompts]
    router.run_until_idle()
    assert [list(f.result(timeout=5).token_ids)
            for f in futs2] == ref_ids
    router.close()


# ---------------------------------------------------------------------------
# disaggregated prefill/decode
# ---------------------------------------------------------------------------

def test_disaggregated_handoff_bitwise_and_sublinear(tiny_gpt):
    cfg, params = tiny_gpt
    rng = np.random.default_rng(8)
    prompts = [rng.integers(3, cfg.vocab_size, 19).astype(np.int32)
               for _ in range(4)]
    ref_ids = _reference_ids(params, cfg, prompts, 6)
    servers = [_server(params, cfg) for _ in range(3)]
    router = FleetRouter(
        servers, start=False,
        policy=RouterPolicy("disaggregated", prefill=(0,),
                            decode=(1, 2)))
    futs = [router.submit(p, max_new_tokens=6) for p in prompts]
    router.run_until_idle()
    ids = [list(f.result(timeout=5).token_ids) for f in futs]
    assert ids == ref_ids
    st = router.get_stats()
    assert st["handoffs"] == len(prompts)
    # every full chunk moved as KV, not recomputed: 19 tokens / bs 8
    # -> 2 full chunks per prompt
    assert st["handoff_blocks"] == 2 * len(prompts)
    # decode replicas prefilled ONLY the tails (3 tokens each + the
    # full-cover re-feed never applies here), prefill replica did the
    # chunks
    decode_prefill = sum(s.get_stats()["prefill_tokens"]
                         for s in servers[1:])
    total_prompt = sum(len(p) for p in prompts)
    assert decode_prefill < total_prompt / 2
    assert servers[0].get_stats()["prefill_tokens"] == total_prompt
    # the prefill pool emitted exactly its one forced token per request
    assert servers[0].get_stats()["generated_tokens"] == len(prompts)
    # handoff metrics recorded
    reg = global_registry()
    assert reg.counter("serving.fleet.handoffs").value() >= len(prompts)
    assert reg.counter("serving.fleet.handoff_blocks").value() >= \
        st["handoff_blocks"]
    assert reg.counter("serving.fleet.routed").labels(
        policy="prefill").value() >= len(prompts)
    assert reg.counter("serving.fleet.routed").labels(
        policy="decode").value() >= len(prompts)
    router.close()


def test_disaggregated_short_prompt_skips_prefill_pool(tiny_gpt):
    """A prompt with no full chunk has no KV to hand off: it routes
    straight to the decode pool."""
    cfg, params = tiny_gpt
    rng = np.random.default_rng(9)
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(
        servers, start=False,
        policy=RouterPolicy("disaggregated", prefill=(0,),
                            decode=(1,)))
    f = router.submit(rng.integers(3, cfg.vocab_size,
                                   5).astype(np.int32),
                      max_new_tokens=3)
    router.run_until_idle()
    f.result(timeout=5)
    assert router.counts["handoffs"] == 0
    assert servers[0].get_stats()["admitted"] == 0
    assert servers[1].get_stats()["admitted"] == 1
    router.close()


# ---------------------------------------------------------------------------
# fleet registry view (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

def test_fleet_registry_view_labels_every_replica(tiny_gpt):
    """ONE /metrics mount exposes every replica's serving.* series
    with a replica= label — previously two servers in one process
    needed two ports to be scraped without clobbering context."""
    cfg, params = tiny_gpt
    rng = np.random.default_rng(10)
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    futs = [router.submit(rng.integers(3, cfg.vocab_size,
                                       10).astype(np.int32),
                          max_new_tokens=2) for _ in range(4)]
    router.run_until_idle()
    for f in futs:
        f.result(timeout=5)
    ep = router.serve_metrics(port=0)
    try:
        body = urllib.request.urlopen(
            f"{ep.url}/metrics", timeout=5).read().decode()
        names = [r.name for r in router.replicas()]
        for name in names:
            assert f'serving_admitted{{replica="{name}"}}' in body
            assert f'serving_iterations{{replica="{name}"}}' in body
            assert f'serving_prefix_hits{{replica="{name}"}}' in body
        # exposition stays parseable: one family block per name, all
        # samples contiguous inside it
        assert body.count("# TYPE serving_admitted ") == 1
        lines = body.splitlines()
        fam = [i for i, ln in enumerate(lines)
               if ln.startswith("serving_admitted")]
        assert fam == list(range(fam[0], fam[0] + len(fam)))
        # replica-labeled values are the PER-REPLICA numbers; the
        # unlabeled sample stays the process aggregate
        per = {name: int(float(next(
            ln.split()[-1] for ln in lines
            if ln.startswith(f'serving_admitted{{replica="{name}"}}'))))
            for name in names}
        assert sum(per.values()) == 4
        assert sorted(per.values()) == sorted(
            s.get_stats()["admitted"] for s in servers)
        # /healthz carries the fleet payload
        health = json.loads(urllib.request.urlopen(
            f"{ep.url}/healthz", timeout=5).read().decode())
        assert health["status"] == "ok"
        assert health["live_replicas"] == 2
        assert len(health["replicas"]) == 2
    finally:
        router.close()      # closes the exporter with the router
    assert ep.closed


def test_fleet_registry_view_drops_dead_replica_series(tiny_gpt):
    cfg, params = tiny_gpt
    servers = [_server(params, cfg) for _ in range(2)]
    router = FleetRouter(servers, start=False)
    from paddle_tpu.observability.exporter import FleetRegistryView
    view = FleetRegistryView(lambda: [
        (r.name, r.server.get_stats()) for r in router.replicas()
        if r.alive()])
    assert 'replica="r0"' in view.to_prometheus()
    router.kill_replica(0)
    text = view.to_prometheus()
    assert 'replica="r0"' not in text
    assert 'replica="r1"' in text
    router.close()
