"""Tensor-parallel continuous batching (ISSUE 9): the WHOLE serving
stack — head-sharded PagedKVCache pools, the shard_map fused
prefill/decode step, the Pallas paged-attention kernel engaging per
shard — sharded over a mesh must reproduce the single-device
GenerationServer token for token, while keeping every PR-5 invariant:
ONE compiled fused-step signature for the server lifetime, blocks
reclaimed on cancel, kernel engagement asserted.

Runs in tier-1 on the conftest-forced 8-virtual-CPU-device session
(`serving` + `tp` markers); the subprocess test additionally proves the
standalone XLA_FLAGS=--xla_force_host_platform_device_count=2 recipe
works outside this session (the tp conftest fixture).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability.metrics import global_registry
from paddle_tpu.serving import GenerationServer, GPTServingModel
from paddle_tpu.serving import kv_cache as kvc

pytestmark = [pytest.mark.serving, pytest.mark.tp]


@pytest.fixture(scope="module")
def trained():
    """Briefly-trained tiny GPT (test_tp_decode's idiom): greedy argmax
    must be decisive, because the tp psum sums partial products in a
    different order than the single-device contraction — an untrained
    model's near-tied logits could flip under that 1-ulp drift."""
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        tokens, loss, _ = gpt.build_lm_net(cfg, seq_len=16)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    rng = np.random.default_rng(0)
    seq = rng.integers(3, cfg.vocab_size, (4, 16)).astype(np.int32)
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(30):
            exe.run(main, feed={"tokens": seq}, fetch_list=[loss])
        params = gpt.load_params(scope, cfg)
    return cfg, params


def _server(params, cfg, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def _drive_staggered_stream(srv):
    """The PR-5 acceptance scenario, verbatim: staggered arrivals,
    mixed prompt/output lengths, one mid-stream cancel. Returns the
    surviving requests' token ids."""
    p1 = np.array([5, 9, 11, 2, 7], np.int32)
    p2 = np.array([7] * 11, np.int32)
    f1 = srv.submit(p1, max_new_tokens=8)
    f2 = srv.submit(p2, max_new_tokens=6)
    for _ in range(2):
        srv.step()
    f3 = srv.submit(np.array([3, 4], np.int32), max_new_tokens=10)
    f4 = srv.submit(np.array([12, 13, 14, 15, 16, 17, 18], np.int32),
                    max_new_tokens=12)
    srv.step()
    assert f4.cancel()
    srv.run_until_idle()
    assert f4.cancelled()
    return [list(f.result(timeout=5).token_ids) for f in (f1, f2, f3)]


# ---------------------------------------------------------------------------
# the acceptance test: tp=2 engine == tp=1 engine, every invariant held
# ---------------------------------------------------------------------------

def test_tp2_engine_bitwise_ids_one_signature(trained):
    cfg, params = trained
    ref_srv = _server(params, cfg)
    ref_ids = _drive_staggered_stream(ref_srv)
    ref_srv.close()

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh)
    got_ids = _drive_staggered_stream(srv)

    # BITWISE-identical token ids on the same stream
    assert got_ids == ref_ids
    st = srv.get_stats()
    # the shape-static design survives the mesh: ONE compiled signature
    assert st["fused_step_signatures"] == 1, st
    # the Pallas kernel engaged per shard (each shard's pool slice
    # (N, H/tp, bs, D) matches the kernel contract)
    assert st["kernel"]["engaged"] is True, st["kernel"]
    assert st["kernel"]["fallback_dispatches"] == 0
    # bookkeeping stays replicated host state
    assert st["cancelled"] == 1 and st["retired"] == 3
    assert st["blocks_free"] == st["blocks_total"]
    # mesh facts surface in get_stats
    assert st["mesh"]["tp"] == 2 and st["mesh"]["axis"] == "tp"
    assert st["mesh"]["shard_pool_bytes"] * 2 == st["mesh"]["pool_bytes"]
    assert st["mesh"]["psums_per_step"] == 2 * cfg.num_layers
    # watermark math in per-shard bytes (the unit one device protects)
    shard_block = srv.cache.shard_pool_bytes() // srv.cache.num_blocks
    assert st["free_shard_bytes"] == st["blocks_free"] * shard_block
    srv.close()


def test_tp2_shared_prefix_stream_bitwise_parity(trained):
    """ISSUE 10: prefix caching composes with the mesh — block sharing
    is replicated HOST state, so a shared-prefix stream (repeats +
    divergent suffixes, full-cover COW included) through a tp=2 server
    reproduces the tp=1 prefix server's ids bitwise, with the same
    hit/COW accounting and exact block reclamation."""
    cfg, params = trained

    def drive(srv):
        shared = np.arange(3, 19, dtype=np.int32)   # 2 full blocks
        ids = []
        f = srv.submit(shared, max_new_tokens=4)    # seeds the index
        srv.run_until_idle()
        ids.append(list(f.result(timeout=5).token_ids))
        futs = [srv.submit(np.concatenate([shared, extra]).astype(
            np.int32), max_new_tokens=5)
            for extra in ([30, 31], [40, 41, 42])]  # live divergence
        futs.append(srv.submit(shared, max_new_tokens=4))  # full cover
        srv.run_until_idle()
        ids += [list(f.result(timeout=5).token_ids) for f in futs]
        return ids

    ref_srv = _server(params, cfg, prefix_cache=True)
    ref_ids = drive(ref_srv)
    ref_prefix = ref_srv.get_stats()["prefix"]
    ref_srv.close()

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh, prefix_cache=True)
    assert drive(srv) == ref_ids
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"]["engaged"] is True
    # identical host-side sharing decisions on the mesh
    assert st["prefix"]["hits"] == ref_prefix["hits"] > 0
    assert st["prefix"]["cow_copies"] == ref_prefix["cow_copies"] == 1
    # exact reclamation: only the cached chunks stay resident
    assert srv.cache.num_free == \
        srv.cache.usable_blocks - st["prefix"]["entries"]
    srv.close()


def test_tp2_mesh_metrics_recorded_and_retired(trained):
    """serving.mesh.* gauges (satellite): axis size, per-shard pool
    bytes, psums per step — recorded per server, removed on close."""
    cfg, params = trained
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh)
    reg = global_registry()
    sid = srv._ledger_id
    assert reg.gauge("serving.mesh.axis_size").labels(
        server=sid).value() == 2
    assert reg.gauge("serving.mesh.shard_pool_bytes").labels(
        server=sid).value() == srv.cache.shard_pool_bytes()
    assert reg.gauge("serving.mesh.psums_per_step").labels(
        server=sid).value() == 2 * cfg.num_layers
    srv.close()
    for name in ("serving.mesh.axis_size",
                 "serving.mesh.shard_pool_bytes",
                 "serving.mesh.psums_per_step"):
        assert not [lbl for lbl, _c in reg.get(name).series()
                    if lbl.get("server") == sid], name


def test_tp2_fused_step_compiles_collectives_and_sharded_pools(trained):
    """White-box (test_tp_decode's idiom): the compiled fused step must
    contain all-reduces (GSPMD/shard_map partitioned the step instead
    of replicating it) and head-sharded pool tensors (N, H/tp, bs, D)
    — the per-chip KV bandwidth win serving scales with."""
    cfg, params = trained
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh)
    # the feed: tokens, positions, valid and tables in one int32 buffer;
    # the ids of the step before (none in flight: zeros)
    packed = jnp.zeros((srv._feed.size,), jnp.int32)
    text = srv._fused.lower(srv.cache.pools, packed,
                            srv._zero_ids).compile().as_text()
    assert "all-reduce" in text or "all_reduce" in text, \
        "tp fused step compiled without any all-reduce"
    kp = srv.cache.pools[0]["kv"]
    n, h, bs, d2 = kp.shape
    sharded_pool = f"f32[{n},{h // 2},{bs},{d2}]"
    assert sharded_pool in text, \
        f"no head-sharded pool tensor {sharded_pool} in compiled step"
    srv.close()


# ---------------------------------------------------------------------------
# head-sharded paged_attention op (satellite): kernel + reference paths
# ---------------------------------------------------------------------------

def _ragged_case(h=4, b=3, c=2, d=8, bs=4, m=5, seed=0):
    """Ragged tables with NULL padding and one fully-idle lane (all
    positions 0, table all NULL) — the engine's masked-lane shape."""
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    k_pool = rng.standard_normal((n, h, bs, d)).astype(np.float32)
    v_pool = rng.standard_normal((n, h, bs, d)).astype(np.float32)
    k_pool[kvc.NULL_BLOCK] = 0.0
    v_pool[kvc.NULL_BLOCK] = 0.0
    q = rng.standard_normal((b, h, c, d)).astype(np.float32)
    tables = np.full((b, m), kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    free = list(range(1, n))
    rng.shuffle(free)
    for i in range(1, b):               # lane 0 stays idle
        length = int(rng.integers(1, m * bs - c))
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        q_pos[i] = np.arange(length, length + c)
    return (jnp.asarray(q),
            kvc.fuse_kv(jnp.asarray(k_pool), jnp.asarray(v_pool)),
            jnp.asarray(tables), jnp.asarray(q_pos))


@pytest.mark.parametrize("mode", ["1", "0"], ids=["kernel", "reference"])
def test_head_sharded_paged_attention_bitwise(monkeypatch, mode):
    """tp=2 paged_attention over head-sharded pools — BOTH dispatch
    routes — must be bitwise-identical to the single-device gather
    reference on ragged NULL-padded tables with an idle lane. Attention
    is head-independent, so sharding the head axis must change no bit
    (the jit context matters: the bitwise pin lives under jit, like
    tests/ops/test_paged_kernel.py)."""
    from jax import shard_map

    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", mode)
    q, kv_pool, tables, q_pos = _ragged_case()
    ref = jax.jit(kvc.paged_attention_reference)(q, kv_pool, tables,
                                                 q_pos)

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    head_ns = NamedSharding(mesh, P(None, "tp", None, None))
    q_s = jax.device_put(q, NamedSharding(mesh, P(None, "tp")))
    kvp_s = jax.device_put(kv_pool, head_ns)
    k0, f0 = kvc.KERNEL_DISPATCHES, kvc.FALLBACK_DISPATCHES
    fn = shard_map(kvc.paged_attention, mesh=mesh,
                   in_specs=(P(None, "tp"), P(None, "tp"), P(), P()),
                   out_specs=P(None, "tp"), check_vma=False)
    out = jax.jit(fn)(q_s, kvp_s, tables, q_pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    if mode == "1":     # the kernel really engaged inside shard_map
        assert kvc.KERNEL_DISPATCHES == k0 + 1
    else:
        assert kvc.FALLBACK_DISPATCHES == f0 + 1


def test_force_mode_unsupported_under_shard_map_falls_back(monkeypatch):
    """ISSUE 9 satellite: force mode + non-qualifying operands INSIDE a
    jit(shard_map) trace must fall back with the distinct
    unsupported_under_shard_map reason label instead of raising
    mid-trace. The dispatcher cannot see the shard_map from inside the
    trace; the caller that wrapped it says so (`in_shard_map=True`,
    what build_fused_step's tensor-parallel branch passes)."""
    import functools
    from jax import shard_map

    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    q, kv_pool, tables, q_pos = _ragged_case(seed=3)
    q16 = q.astype(jnp.float16)
    kv16 = kv_pool.astype(jnp.float16)
    reason = global_registry().counter(
        "serving.kernel.fallback").labels(
        reason="unsupported_under_shard_map")
    r0 = reason.value()
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    fn = shard_map(functools.partial(kvc.paged_attention,
                                     in_shard_map=True), mesh=mesh,
                   in_specs=(P(None, "tp"), P(None, "tp"), P(), P()),
                   out_specs=P(None, "tp"), check_vma=False)
    out = jax.jit(fn)(q16, kv16, tables, q_pos)   # must NOT raise
    assert reason.value() == r0 + 1
    ref = jax.jit(kvc.paged_attention_reference)(q16, kv16, tables,
                                                 q_pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # plain (no-transform) force misuse still raises loudly
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q16, kv16, tables, q_pos)


# ---------------------------------------------------------------------------
# HBM ledger per-device rows (satellite)
# ---------------------------------------------------------------------------

def test_tp2_ledger_per_device_rows_sum_to_pool_bytes(trained):
    """Under the mesh the kv rows are per DEVICE (each holding its
    H/tp shard's bytes) and must SUM to the pool's logical bytes —
    memory.total_bytes is never tp x overcounted — and retire on both
    close paths."""
    from paddle_tpu.observability.compile_insight import hbm_ledger
    cfg, params = trained
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh)
    pool_bytes = srv.cache.pool_bytes()
    rows = [e for e in hbm_ledger().snapshot()["entries"]
            if e["component"] == srv._ledger_id
            and e["kind"] == "kv_cache"]
    assert len(rows) == 2
    assert {r["name"] for r in rows} == {"kv_pool/shard0",
                                         "kv_pool/shard1"}
    assert all(r["bytes"] == pool_bytes // 2 for r in rows)
    assert {r["detail"]["device"] for r in rows} == {
        str(d) for d in mesh.devices.flat}
    assert srv.get_stats()["memory"]["kv_cache"] == pool_bytes
    srv.close()
    assert srv.get_stats()["memory"] == {}

    # the fault-stop path (close()'s early-return branch) must retire
    # the rows too: _on_engine_fault sets _closed without reaching the
    # normal teardown
    srv2 = _server(params, cfg, mesh=mesh)
    assert srv2.get_stats()["memory"]["kv_cache"] == pool_bytes
    with srv2._rid_lock:
        srv2._closed = True             # what _on_engine_fault does
    srv2.close()
    assert srv2.get_stats()["memory"] == {}
    assert not [lbl for lbl, _c in
                global_registry().get("serving.mesh.axis_size").series()
                if lbl.get("server") == srv2._ledger_id]


# ---------------------------------------------------------------------------
# validation + the standalone host-device-count recipe (satellites)
# ---------------------------------------------------------------------------

def test_mesh_divisibility_validated(trained):
    cfg, params = trained
    mesh3 = Mesh(np.array(jax.devices()[:3]), ("tp",))
    with pytest.raises(ValueError, match="divide"):
        _server(params, cfg, mesh=mesh3)
    with pytest.raises(ValueError, match="divide"):
        kvc.PagedKVCache(2, 4, 8, 9, block_size=4, mesh=mesh3)
    # tp divides heads but NOT inner_size: the engine must fail BEFORE
    # allocating pools/scheduler/telemetry (allocation-free constructor
    # check), not from build_fused_step with device arrays half-built
    cfg_odd = gpt.GPTConfig(
        **{k: getattr(cfg, k)
           for k in ("vocab_size", "hidden_size", "num_layers",
                     "num_heads", "max_position", "dropout")},
        inner_size=513)
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="inner_size"):
        _server(params, cfg_odd, mesh=mesh2)


def test_mesh_must_be_1d(trained):
    """A multi-axis mesh is rejected loudly: the per-device ledger rows
    and shard byte math (pool/tp each) are only truthful on a 1-D head
    axis — dp means separate GenerationServer replicas, not a mesh
    axis here."""
    cfg, params = trained
    mesh2d = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("dp", "tp"))
    with pytest.raises(ValueError, match="must be 1-D"):
        _server(params, cfg, mesh=mesh2d)
    with pytest.raises(ValueError, match="must be 1-D"):
        kvc.PagedKVCache(2, 4, 8, 9, block_size=4, mesh=mesh2d)
    # a wrong axis NAME gets the same friendly treatment, not a bare
    # KeyError from mesh.shape[...]
    mesh_m = Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="not a mesh axis"):
        _server(params, cfg, mesh=mesh_m)
    with pytest.raises(ValueError, match="not a mesh axis"):
        kvc.PagedKVCache(2, 4, 8, 9, block_size=4, mesh=mesh_m)


def test_tp_subprocess_recipe(tp_subprocess):
    """The documented recipe — a FRESH process pinned to
    XLA_FLAGS=--xla_force_host_platform_device_count=2 — stands on its
    own: 2 devices come up, the head-sharded pool lands (N, H/tp, bs,
    D) per device, and the byte accounting halves per shard. Keeps the
    in-session suite honest: the 8-device conftest mesh is a superset,
    not a prerequisite."""
    code = """
import jax
import numpy as np
assert jax.device_count() == 2, jax.devices()
from jax.sharding import Mesh
from paddle_tpu.serving.kv_cache import PagedKVCache
mesh = Mesh(np.array(jax.devices()), ("tp",))
cache = PagedKVCache(2, 4, 8, 9, block_size=4, mesh=mesh)
kp = cache.pools[0]["kv"]
shard = kp.sharding.shard_shape(tuple(kp.shape))
assert shard == (9, 2, 4, 2 * 8), shard
assert cache.shard_pool_bytes() * 2 == cache.pool_bytes()
print("TP_RECIPE_OK")
"""
    res = tp_subprocess(code, devices=2)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "TP_RECIPE_OK" in res.stdout


@pytest.mark.quant
def test_tp2_int8_pools_bitwise_vs_tp1_int8(trained):
    """ISSUE 14: quantized pools compose with the mesh. Quantization is
    per (lane, column, head) row and the pools shard by HEAD, so each
    shard quantizes exactly the rows it owns — a tp=2 int8 server must
    reproduce the tp=1 int8 server's ids BITWISE on the acceptance
    stream (mid-stream cancel included), with the kernel engaged per
    shard, one fused signature, and the scale pools sharded beside the
    code pools."""
    cfg, params = trained
    ref_srv = _server(params, cfg, kv_dtype="int8")
    ref_ids = _drive_staggered_stream(ref_srv)
    assert ref_srv.get_stats()["kernel"]["engaged"] is True
    ref_srv.close()

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = _server(params, cfg, mesh=mesh, kv_dtype="int8")
    got_ids = _drive_staggered_stream(srv)
    assert got_ids == ref_ids
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1, st
    assert st["kernel"]["engaged"] is True, st["kernel"]
    assert st["blocks_free"] == st["blocks_total"]
    # scale pools shard with the code pools: (N, H/tp, bs) per device,
    # and the per-shard byte math (codes + scales) halves exactly
    ks = srv.cache.pools[0]["k_scale"]
    shard = ks.sharding.shard_shape(tuple(ks.shape))
    assert shard == (srv.cache.num_blocks, cfg.num_heads // 2,
                     srv.cache.block_size)
    assert srv.cache.shard_pool_bytes() * 2 == srv.cache.pool_bytes()
    assert st["kv_quant"]["kv_dtype"] == "int8"
    assert st["kv_quant"]["pool_bytes"] < \
        st["kv_quant"]["dense_equiv_bytes"]
    srv.close()


def test_tp2_gqa_bitwise_vs_tp1_gqa(trained):
    """ISSUE 16: grouped-query attention composes with the mesh. The
    pools shard on the KV head axis (H_kv, not the query heads), and
    the contiguous-group convention keeps each device's local q-head
    groups aligned with its local KV heads — so a tp=2 GQA server must
    reproduce the tp=1 GQA server's ids BITWISE on the acceptance
    stream, with (N, H_kv/tp, bs, D) pool shards and H_kv-true byte
    math."""
    cfg, params = trained
    kv = 2
    gqa_params = gpt.gqa_slice_kv_params(params, cfg, kv)
    gqa_cfg = gpt.GPTConfig(
        **{k: getattr(cfg, k)
           for k in ("vocab_size", "hidden_size", "num_layers",
                     "num_heads", "inner_size", "max_position",
                     "dropout")}, kv_heads=kv)

    ref_srv = GenerationServer(GPTServingModel(gqa_params, gqa_cfg),
                               num_slots=3, block_size=8,
                               max_context=64, chunk=4, start=False)
    ref_ids = _drive_staggered_stream(ref_srv)
    assert ref_srv.get_stats()["kernel"]["engaged"] is True
    ref_srv.close()

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    srv = GenerationServer(GPTServingModel(gqa_params, gqa_cfg),
                           num_slots=3, block_size=8, max_context=64,
                           chunk=4, start=False, mesh=mesh)
    got_ids = _drive_staggered_stream(srv)
    assert got_ids == ref_ids
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1, st
    assert st["kernel"]["engaged"] is True, st["kernel"]
    assert st["kernel"]["fallback_dispatches"] == 0
    assert st["blocks_free"] == st["blocks_total"]
    # the pool shards carry H_kv/tp heads — ONE KV head per device
    # here, while each device computes 2 query heads against it
    kp = srv.cache.pools[0]["kv"]
    shard = kp.sharding.shard_shape(tuple(kp.shape))
    assert shard == (srv.cache.num_blocks, kv // 2,
                     srv.cache.block_size, 2 * cfg.hidden_size
                     // cfg.num_heads)
    assert srv.cache.shard_pool_bytes() * 2 == srv.cache.pool_bytes()
    srv.close()

    # tp must divide H_kv, not just H: 4 devices over 2 KV heads is
    # rejected at construction with the kv-heads message
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("tp",))
    with pytest.raises(ValueError, match="divide kv_heads"):
        GenerationServer(GPTServingModel(gqa_params, gqa_cfg),
                         num_slots=3, block_size=8, max_context=64,
                         chunk=4, start=False, mesh=mesh4)
    with pytest.raises(ValueError, match="divide num_kv_heads"):
        kvc.PagedKVCache(2, 4, 8, 9, block_size=4, mesh=mesh4,
                         num_kv_heads=2)
