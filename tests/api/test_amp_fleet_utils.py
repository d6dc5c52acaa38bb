"""AMP / fleet / aux-subsystem tests (SURVEY.md §2.6, §2.9, §2.11)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, amp


def _net():
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    return loss


def _feed(seed=0):
    rs = np.random.RandomState(seed)
    xs = rs.rand(16, 8).astype(np.float32)
    return {"x": xs, "y": xs.sum(1, keepdims=True).astype(np.float32)}


# ---------------------------------------------------------------- AMP
def test_amp_decorate_trains_with_loss_scaling():
    loss = _net()
    opt = amp.decorate(fluid.optimizer.AdamOptimizer(1e-2),
                       init_loss_scaling=2.0 ** 10,
                       use_dynamic_loss_scaling=True)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    losses = [float(exe.run(feed=_feed(), fetch_list=[loss])[0])
              for _ in range(25)]
    assert losses[-1] < losses[0] * 0.1, losses[::6]


def test_amp_bf16_cast_tags_matmul_ops():
    loss = _net()
    main = fluid.default_main_program()
    amp.cast_model_to_bf16(main)
    tagged = [op.type for op in main.global_block().ops
              if op.attrs.get("__amp_dtype__") == "bfloat16"]
    assert "mul" in tagged
    # bf16 path still runs and produces finite loss
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    out, = exe.run(feed=_feed(), fetch_list=[loss])
    assert np.isfinite(out).all()


def test_amp_bf16_cast_tags_attention_ops_and_keeps_bias_f32(monkeypatch):
    """Both fused attention ops are on the white list, as Fluid's matmul
    pairs are: their q/k/v reach the attention bf16, while the additive
    attention Bias keeps float32."""
    from paddle_tpu.ops import attention_ops

    seen = []
    real = attention_ops.dot_product_attention

    def spy(q, k, v, bias=None, **kw):
        seen.append((q.dtype, k.dtype, v.dtype, bias.dtype))
        return real(q, k, v, bias=bias, **kw)

    monkeypatch.setattr(attention_ops, "dot_product_attention", spy)
    x = layers.data("x", shape=[6, 16], dtype="float32")
    mask = layers.data("mask", shape=[1, 1, 6], dtype="float32")
    h = layers.multi_head_attention(x, num_heads=2, attn_bias=mask)
    h = layers.scaled_dot_product_attention(h, h, h, num_heads=2,
                                            attn_bias=mask)
    loss = layers.mean(h)
    main = fluid.default_main_program()
    amp.cast_model_to_bf16(main)
    tagged = {op.type for op in main.global_block().ops
              if op.attrs.get("__amp_dtype__") == "bfloat16"}
    assert {"multihead_attention", "scaled_dot_product_attention"} <= tagged
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(0).rand(3, 6, 16).astype(np.float32),
            "mask": np.zeros((3, 1, 1, 6), np.float32)}
    out, = exe.run(feed=feed, fetch_list=[loss])
    assert np.isfinite(out).all()
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert seen == [(bf16, bf16, bf16, f32)] * 2


# ---------------------------------------------------------------- fleet
def test_fleet_facade_dp_training():
    from paddle_tpu.parallel import fleet as fleet_mod
    fleet = fleet_mod.fleet
    fleet.init(is_collective=True)
    assert fleet.worker_num() >= 1
    loss = _net()
    opt = fleet.distributed_optimizer(
        fluid.optimizer.SGDOptimizer(learning_rate=0.1))
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    prog = fleet.main_program if hasattr(fleet, "main_program") else \
        fluid.default_main_program()
    out, = exe.run(prog, feed=_feed(), fetch_list=[loss])
    assert np.isfinite(out).all()


# ---------------------------------------------------------------- utils
def test_model_stat_counts():
    from paddle_tpu.utils import model_stat
    _net()
    main = fluid.default_main_program()
    n, per_param = model_stat.count_params(main)
    assert n == 8 * 16 + 16 + 16 * 1 + 1
    assert per_param["fc_0.w_0"] == 128
    flops, per_op = model_stat.count_flops(main, batch_size=4)
    assert flops >= 2 * 4 * (8 * 16 + 16)
    assert per_op.get("mul", 0) > 0


def test_nan_check_guard_and_debugger():
    from paddle_tpu.utils import nan_check, debugger
    with pytest.raises(FloatingPointError):
        nan_check.guard_loss(float("nan"), step=3)
    assert nan_check.guard_loss(1.25) == 1.25
    _net()
    text = debugger.program_to_code(fluid.default_main_program()) \
        if hasattr(debugger, "program_to_code") else \
        debugger.dump_program(fluid.default_main_program())
    assert "mul" in text


def test_determinism_same_seed_same_init():
    from paddle_tpu.utils import determinism
    loss = _net()
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()

    def init_values():
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            return {p.name: np.asarray(scope.get(p.name))
                    for p in main.all_parameters()}

    a, b = init_values(), init_values()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_profiler_context_runs():
    import paddle_tpu.profiler as prof
    loss = _net()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    with prof.profiler(state="All"):
        exe.run(feed=_feed(), fetch_list=[loss])


def test_memory_stats():
    from paddle_tpu.utils import memory
    stats = memory.memory_usage() if hasattr(memory, "memory_usage") else \
        memory.device_memory_stats()
    assert isinstance(stats, dict)


# ---------------------------------------------------------------- decoding
def test_kv_cache_greedy_decode():
    import jax
    from paddle_tpu.inference import decoding

    V, D = 17, 8
    key = jax.random.PRNGKey(0)
    emb = jax.random.normal(key, (V, D)) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.5

    def step_fn(tok, cache, t):
        # toy "model": logits from current token embedding only
        h = emb[tok]
        return h @ w, cache

    bos = np.zeros((2,), np.int32)
    seqs, scores = decoding.greedy_decode(step_fn, {}, jnp.asarray(bos),
                                          max_len=6)
    seqs = np.asarray(seqs)
    assert seqs.shape == (2, 6)
    assert np.isfinite(np.asarray(scores)).all()
    # deterministic: both batch rows identical (same start token)
    np.testing.assert_array_equal(seqs[0], seqs[1])
