"""Pallas ragged paged attention kernel (ops/pallas/paged.py) vs the
pure-JAX reference (serving/kv_cache.paged_attention_reference).

Tiering: everything here is tier-1 (`pallas` marker; the kernel runs
under the Pallas interpreter on CPU, so these tests exercise the REAL
kernel code path, not a shadow implementation). The contract:

- f32 pools: kernel output is BITWISE-identical to the reference for
  chunked prefill (C>1), decode (C=1), ragged mixed-length batches,
  and NULL-padded tables — the kernel mirrors the reference's op
  sequence on its in-kernel gather, so partial sums are identical, not
  just close;
- bf16 pools: allclose within bf16 tolerance — the kernel accumulates
  scores/softmax in f32 where the reference rounds through bf16 (on
  the CPU backend XLA upcasts bf16 matmuls, so the observed diff here
  is usually 0; the tolerance is the documented contract for real-TPU
  runs where the two paths genuinely differ);
- the NULL block (block 0) is NEVER read: NaN-poisoning it must not
  reach the output, op-level and through a full GenerationServer
  stream;
- dispatch: PADDLE_TPU_PAGED_KERNEL=0 pins the reference, =1 raises on
  unsupported operands, auto falls back silently and counts it;
- the serving engine reports (and asserts) kernel engagement.
"""

import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged
from paddle_tpu.serving import kv_cache as kvc

pytestmark = pytest.mark.pallas


def make_case(dtype=jnp.float32, b=3, h=2, c=4, d=8, bs=8, m=6, seed=0,
              poison=False, idle_lane=False):
    """Ragged batch: every lane gets its own length (and so its own
    live-block count), tables are NULL-padded past the live blocks, and
    block assignment is shuffled so table order != pool order.
    idle_lane=True turns lane 0 into an engine-style masked lane: all
    positions 0, table all NULL. The pool is the fused one,
    `kvc.fuse_kv(k_pool, v_pool)`: K beside V in the minor dim."""
    rng = np.random.default_rng(seed)
    n = 1 + b * m
    k_pool = rng.standard_normal((n, h, bs, d)).astype(dtype)
    v_pool = rng.standard_normal((n, h, bs, d)).astype(dtype)
    fill = np.nan if poison else 0.0
    k_pool[kvc.NULL_BLOCK] = fill
    v_pool[kvc.NULL_BLOCK] = fill
    q = rng.standard_normal((b, h, c, d)).astype(dtype)
    tables = np.full((b, m), kvc.NULL_BLOCK, np.int32)
    q_pos = np.zeros((b, c), np.int32)
    free = list(range(1, n))
    rng.shuffle(free)
    for i in range(b):
        if idle_lane and i == 0:
            continue
        length = int(rng.integers(1, m * bs - c))
        for j in range(-(-(length + c) // bs)):
            tables[i, j] = free.pop()
        q_pos[i] = np.arange(length, length + c)
    return (jnp.asarray(q),
            kvc.fuse_kv(jnp.asarray(k_pool), jnp.asarray(v_pool)),
            jnp.asarray(tables), jnp.asarray(q_pos))


def _run_both(args):
    """Run both paths under jit — the production context (the engine's
    whole life is ONE jitted fused step). Eager op-by-op dispatch may
    compile the reference einsum standalone and diverge in the last
    ulp; the bitwise contract is pinned where it is used."""
    ref = jax.jit(kvc.paged_attention_reference)(*args)
    out = jax.jit(paged.ragged_paged_attention)(*args)
    return np.asarray(out), np.asarray(ref)


# ---------------------------------------------------------------------------
# bitwise pins (f32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(),                                      # chunked prefill C=4
    dict(c=1, seed=1),                           # decode C=1
    dict(b=5, h=3, c=3, d=5, bs=4, m=9, seed=7),  # odd, ragged
    dict(b=2, h=1, c=2, d=16, bs=16, m=3, seed=9),
    dict(idle_lane=True, seed=11),               # all-NULL masked lane
], ids=["prefill", "decode", "ragged_odd", "wide_block", "idle_lane"])
def test_kernel_bitwise_matches_reference_f32(case):
    out, ref = _run_both(make_case(**case))
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(out, ref)


def test_kernel_eager_allclose_f32():
    """Outside jit the bitwise pin does NOT hold (eager op-by-op
    dispatch compiles the reference einsum standalone and the two
    paths drift in the last ulp) — but the eager kernel must still be
    usable and numerically tight."""
    args = make_case(seed=3)
    out = np.asarray(paged.ragged_paged_attention(*args))
    ref = np.asarray(kvc.paged_attention_reference(*args))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# bf16: f32 accumulation, documented tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [4, 1], ids=["prefill", "decode"])
def test_kernel_bf16_allclose(c):
    out, ref = _run_both(make_case(dtype=jnp.bfloat16, c=c, seed=2))
    assert out.dtype == jnp.bfloat16
    # one-bf16-ulp headroom: the kernel's f32 score accumulation may
    # round differently from the reference's bf16 score math
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_output_dtype_follows_the_pool():
    argsf = make_case()
    assert paged.ragged_paged_attention(*argsf).dtype == jnp.float32
    argsb = make_case(dtype=jnp.bfloat16)
    assert paged.ragged_paged_attention(*argsb).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# NULL block is never read
# ---------------------------------------------------------------------------

def test_null_block_poison_stays_finite_op_level():
    """NaN in block 0 must not reach the kernel output (the reference,
    which gathers the dense view including NULL rows, does NOT have
    this property — that asymmetry is the proof the kernel skips the
    read instead of multiplying it by zero)."""
    args = make_case(seed=3, poison=True)
    out = np.asarray(paged.ragged_paged_attention(*args))
    assert np.isfinite(out).all()
    clean = make_case(seed=3, poison=False)
    np.testing.assert_array_equal(
        out, np.asarray(paged.ragged_paged_attention(*clean)))


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("half", ["k_lanes", "v_lanes"])
def test_null_block_poison_in_one_half_stays_finite(half, version):
    """K and V of the NULL block share one array now: NaN in its K
    lanes alone, or in its V lanes alone, reaches no output either, and
    changes no bit of it, under both kernels."""
    fn = jax.jit(paged.ragged_paged_attention if version == "v1"
                 else paged.ragged_paged_attention_v2)
    q, kv_pool, tables, pos = make_case(seed=3, idle_lane=True)
    d = q.shape[-1]
    lanes = slice(0, d) if half == "k_lanes" else slice(d, 2 * d)
    dirty = kv_pool.at[kvc.NULL_BLOCK, :, :, lanes].set(jnp.nan)
    out = np.asarray(fn(q, dirty, tables, pos))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(
        out, np.asarray(fn(q, kv_pool, tables, pos)))


def test_consts_mirror_kv_cache():
    """The kernel module duplicates NULL_BLOCK/NEG_INF (it must not
    import the serving layer); drift would silently break the bitwise
    pin or the NULL-skip guard."""
    assert paged.NULL_BLOCK == kvc.NULL_BLOCK
    assert paged.NEG_INF == kvc.NEG_INF


# ---------------------------------------------------------------------------
# gather pair (reference-path satellite)
# ---------------------------------------------------------------------------

def test_gather_block_kv_pair_matches_single_gathers():
    """The fused pool gathered once and split is the K pool and the V
    pool each gathered alone; fuse_kv and split_kv are inverses."""
    _q, kv_pool, tables, _pos = make_case(seed=5)
    k_pool, v_pool = kvc.split_kv(kv_pool)
    np.testing.assert_array_equal(
        np.asarray(kvc.fuse_kv(k_pool, v_pool)), np.asarray(kv_pool))
    gk, gv = kvc.gather_block_kv_pair(kv_pool, tables)
    np.testing.assert_array_equal(
        np.asarray(gk), np.asarray(kvc.gather_block_kv(k_pool, tables)))
    np.testing.assert_array_equal(
        np.asarray(gv), np.asarray(kvc.gather_block_kv(v_pool, tables)))


# ---------------------------------------------------------------------------
# dispatch + counters
# ---------------------------------------------------------------------------

def test_dispatch_auto_routes_to_kernel_and_counts(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    args = make_case(seed=6)
    k0 = kvc.KERNEL_DISPATCHES
    m0 = reg.counter("serving.kernel.traced").value()
    # fresh jit wrapper: dispatch happens at TRACE time, once
    out = jax.jit(lambda *a: kvc.paged_attention(*a))(*args)
    assert kvc.KERNEL_DISPATCHES == k0 + 1
    assert reg.counter("serving.kernel.traced").value() == m0 + 1
    assert reg.gauge("serving.kernel.interpret").value() == 1  # CPU
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.jit(kvc.paged_attention_reference)(*args)))


def test_dispatch_env_zero_pins_reference(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    from paddle_tpu.observability.metrics import global_registry
    args = make_case(seed=6)
    f0 = kvc.FALLBACK_DISPATCHES
    m0 = global_registry().counter("serving.kernel.fallback").value()
    kvc.paged_attention(*args)
    assert kvc.FALLBACK_DISPATCHES == f0 + 1
    assert global_registry().counter(
        "serving.kernel.fallback").value() == m0 + 1
    assert kvc.kernel_dispatch_stats()["mode"] == "off"


def test_dispatch_force_raises_on_unsupported(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    q, kv_pool, tables, pos = make_case(seed=6)
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables, pos)
    # a pool that is not (N, H, bs, 2 * head_dim) is no fused pool of
    # this q's, whatever its dtype: K alone, the pair's older shape
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kvc.split_kv(kv_pool)[0], tables, pos)


def test_dispatch_auto_falls_back_on_unsupported(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    q, kv_pool, tables, pos = make_case(seed=6)
    f0 = kvc.FALLBACK_DISPATCHES
    out = kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables,
                              pos)
    assert kvc.FALLBACK_DISPATCHES == f0 + 1
    assert out.dtype == jnp.float16


def test_bad_env_value_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "maybe")
    with pytest.raises(ValueError, match="PADDLE_TPU_PAGED_KERNEL"):
        kvc.paged_kernel_mode()


def test_dispatch_takes_the_shard_map_fact_as_an_argument(monkeypatch):
    """The dispatcher does not inspect tracers: whether it runs inside
    a shard_map is a fact its caller passes (`in_shard_map=`, set by
    GPTServingModel.build_fused_step's tensor-parallel branch). With
    it, force mode + non-qualifying operands falls back under the
    unsupported_under_shard_map label; without it the same call
    raises. A plain jit(vmap(...)) trace gets no special treatment —
    it takes the kernel like any other trace."""
    from paddle_tpu.observability.metrics import global_registry
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    q, kv_pool, tables, pos = make_case(b=2, c=1, m=3, seed=8)
    kv16 = kv_pool.astype(jnp.float16)
    reason = global_registry().counter(
        "serving.kernel.fallback").labels(
        reason="unsupported_under_shard_map")
    r0 = reason.value()
    with pytest.raises(ValueError, match="do not qualify"):
        kvc.paged_attention(q, kv16, tables, pos)
    out = kvc.paged_attention(q, kv16, tables, pos, in_shard_map=True)
    assert reason.value() == r0 + 1
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(kvc.paged_attention_reference(
            q, kv16, tables, pos)))
    k0, f0 = kvc.KERNEL_DISPATCHES, kvc.FALLBACK_DISPATCHES
    jax.jit(jax.vmap(lambda a: kvc.paged_attention(
        a, kv_pool, tables, pos)))(jnp.stack([q, q + 1]))
    assert (kvc.KERNEL_DISPATCHES, kvc.FALLBACK_DISPATCHES) == \
        (k0 + 1, f0)


def test_no_module_sniffs_jax_tracer_internals():
    """The jax 0.9 upgrade removed `jax.interpreters.batching.
    BatchTracer` and took every serving path down with it. Nothing
    under paddle_tpu/ may reach into jax.interpreters or the axis-env
    probes again."""
    import os
    import re
    root = os.path.dirname(os.path.abspath(kvc.__file__))
    root = os.path.dirname(root)
    pat = re.compile(r"jax\.interpreters|from jax import interpreters"
                     r"|nonempty_axis_env|get_axis_env")
    hits = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    if pat.search(fh.read()):
                        hits.append(os.path.relpath(path, root))
    assert not hits, hits


def test_dispatch_fallback_reason_labels(monkeypatch):
    """The other fallback reasons ride the same labeled series:
    pinned_off for PADDLE_TPU_PAGED_KERNEL=0, unsupported for
    non-qualifying operands in auto mode."""
    from paddle_tpu.observability.metrics import global_registry
    reg = global_registry()
    args = make_case(seed=12)
    off = reg.counter("serving.kernel.fallback").labels(
        reason="pinned_off")
    uns = reg.counter("serving.kernel.fallback").labels(
        reason="unsupported")
    o0, u0 = off.value(), uns.value()
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    kvc.paged_attention(*args)
    assert off.value() == o0 + 1 and uns.value() == u0
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    q, kv_pool, tables, pos = args
    kvc.paged_attention(q, kv_pool.astype(jnp.float16), tables, pos)
    assert uns.value() == u0 + 1
    # a deliberate pin DOMINATES: off mode under a vmap trace still
    # records pinned_off, never vmap_trace — a dashboard alerting on
    # non-pinned_off fallback reasons must not page on the pin
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    o1 = off.value()
    jax.vmap(lambda a: kvc.paged_attention(a, kv_pool, tables,
                                           pos))(jnp.stack([q, q]))
    assert off.value() == o1 + 1


def test_kernel_validates_shapes():
    q, kv_pool, tables, pos = make_case(seed=6)
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q, kv_pool, tables, pos[:1])
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q[:, :1], kv_pool, tables, pos)
    # the older pair's K pool alone: half a fused pool's minor dim
    with pytest.raises(ValueError, match="do not match"):
        paged.ragged_paged_attention(q, kvc.split_kv(kv_pool)[0], tables,
                                     pos)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
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
    from paddle_tpu.serving import GenerationServer, GPTServingModel
    kw.setdefault("num_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("start", False)
    return GenerationServer(GPTServingModel(params, cfg), **kw)


def test_engine_reports_kernel_engagement(tiny_gpt, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    assert srv.get_stats()["kernel"]["engaged"] is None
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    assert len(fut.result(timeout=5).token_ids) == 4
    st = srv.get_stats()
    assert st["fused_step_signatures"] == 1
    assert st["kernel"]["engaged"] is True
    assert st["kernel"]["kernel_dispatches"] == cfg.num_layers
    assert st["kernel"]["fallback_dispatches"] == 0
    # the layout, as a fact beside the kernel's: one block of a layer's
    # pool is (H_kv, block_size, 2 * head_dim), K beside V
    shape = [cfg.num_heads, 8, 2 * cfg.hidden_size // cfg.num_heads]
    assert st["kernel"]["pool_block_shape"] == shape
    assert list(srv.cache.pools[0]["kv"].shape[1:]) == shape


def test_engine_reference_mode_not_engaged(tiny_gpt, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "0")
    cfg, params = tiny_gpt
    srv = _server(params, cfg)
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    ids_ref = list(fut.result(timeout=5).token_ids)
    st = srv.get_stats()
    assert st["kernel"]["engaged"] is False
    assert st["kernel"]["fallback_dispatches"] == cfg.num_layers

    # kernel-mode server on the same params produces the same ids
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    srv2 = _server(params, cfg)
    fut2 = srv2.submit([5, 9, 11], max_new_tokens=4)
    srv2.run_until_idle()
    assert list(fut2.result(timeout=5).token_ids) == ids_ref
    assert srv2.get_stats()["kernel"]["engaged"] is True


def test_engine_bf16_pools_run_on_kernel(tiny_gpt, monkeypatch):
    """bf16 KV pools qualify for the kernel (f32 accumulation inside);
    a bf16 server must engage it and produce tokens end to end."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    from paddle_tpu.serving import GenerationServer, GPTServingModel
    cfg, params = tiny_gpt
    srv = GenerationServer(
        GPTServingModel(params, cfg, dtype=jnp.bfloat16), num_slots=2,
        block_size=8, max_context=64, chunk=4, start=False)
    assert srv.cache.dtype == jnp.bfloat16
    fut = srv.submit([5, 9, 11], max_new_tokens=4)
    srv.run_until_idle()
    res = fut.result(timeout=5)
    assert len(res.token_ids) == 4
    st = srv.get_stats()
    assert st["kernel"]["engaged"] is True
    assert st["fused_step_signatures"] == 1


def test_engine_null_block_poison_full_stream(tiny_gpt, monkeypatch):
    """The acceptance poison test: fill every layer's block 0 with NaN
    BEFORE serving, run a mixed-length stream on the kernel path —
    every output token id matches the clean run and every logprob is
    finite. Masked lanes and table padding contributed exactly
    nothing."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    cfg, params = tiny_gpt
    prompts = [np.array([5, 9, 11, 2, 7], np.int32),
               np.array([7] * 11, np.int32),
               np.array([3, 4], np.int32)]
    lens = [6, 4, 8]

    def run(poison):
        srv = _server(params, cfg)
        if poison:
            nanrow = jnp.full((cfg.num_heads, srv.block_size,
                               2 * cfg.hidden_size // cfg.num_heads),
                              jnp.nan, srv.cache.dtype)
            srv.cache.pools = [
                {"kv": p["kv"].at[kvc.NULL_BLOCK].set(nanrow)}
                for p in srv.cache.pools]
        futs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        srv.run_until_idle()
        res = [f.result(timeout=5) for f in futs]
        assert srv.get_stats()["kernel"]["engaged"] is True
        return res

    clean = run(poison=False)
    poisoned = run(poison=True)
    for c, p in zip(clean, poisoned):
        assert list(p.token_ids) == list(c.token_ids)
        assert np.isfinite(p.score)


# ---------------------------------------------------------------------------
# lazy export
# ---------------------------------------------------------------------------

def test_pallas_package_lazy_exports():
    import paddle_tpu.ops.pallas as pk
    assert pk.ragged_paged_attention is paged.ragged_paged_attention
    assert pk.paged is paged
    assert "flash_attention" in dir(pk)


def test_pallas_package_import_stays_cheap():
    """Importing the package must touch neither kernel module — CPU
    workloads that never hit attention pay no Pallas import."""
    code = ("import sys, paddle_tpu.ops.pallas; "
            "mods = [m for m in sys.modules if m.startswith("
            "'paddle_tpu.ops.pallas.')]; "
            "assert not mods, mods; print('lazy ok')")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "lazy ok" in out.stdout
