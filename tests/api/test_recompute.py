"""Remat (RecomputeOptimizer) tests.

Remat must be numerically invisible (identical losses — it only changes
WHAT is saved, not what is computed) and must actually shrink the step
executable's temporary memory when the policy discards activations.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import framework
from paddle_tpu.core.executor import Scope, scope_guard


DEPTH, WIDTH, BATCH = 6, 256, 32


def _build(recompute=None):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = fluid.data(name="x", shape=[-1, WIDTH], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        h = x
        for i in range(DEPTH):
            h = layers.fc(h, size=WIDTH, act="relu", name=f"blk{i}")
        p = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(p, y))
        inner = fluid.optimizer.AdamOptimizer(learning_rate=1e-3)
        if recompute is None:
            inner.minimize(loss)
        else:
            fluid.optimizer.RecomputeOptimizer(
                inner, policy=recompute).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((BATCH, WIDTH)).astype(np.float32),
            "y": rng.standard_normal((BATCH, 1)).astype(np.float32)}


def _train(recompute, steps=3):
    main, startup, loss = _build(recompute)
    losses = []
    with scope_guard(Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(steps):
            out, = exe.run(main, feed=_feed(), fetch_list=[loss])
            losses.append(float(np.asarray(out).reshape(-1)[0]))
        hlo = exe.last_compiled_text()
    return losses, hlo


def test_recompute_matches_plain_numerics():
    ref, _ = _train(None)
    for policy in ("dots", "nothing"):
        got, _ = _train(policy)
        np.testing.assert_allclose(ref, got, rtol=1e-6, atol=1e-7,
                                   err_msg=policy)


def test_recompute_rematerializes_forward():
    """The compiled step must actually recompute forward ops in the
    backward when a policy is set (rematted instructions in the optimized
    HLO), and must not when it isn't. Peak-memory benefit is a TPU
    runtime property (the CPU scheduler reuses buffers either way);
    no cell of the benchmark sets a recompute policy yet."""
    def remat_count(recompute):
        _, hlo = _train(recompute, steps=1)
        return hlo.count("rematted")

    assert remat_count(None) == 0
    assert remat_count("nothing") > 0
    assert remat_count("dots") > 0


def test_unknown_policy_rejected_eagerly():
    with pytest.raises(ValueError):
        fluid.optimizer.RecomputeOptimizer(
            fluid.optimizer.SGDOptimizer(learning_rate=0.1), policy="bogus")


def test_fleet_strategy_recompute_flag():
    from paddle_tpu.parallel import fleet as fleet_mod
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = fluid.data(name="x", shape=[-1, 8], dtype="float32")
        loss = layers.mean(layers.fc(x, size=1))
        flt = fleet_mod.Fleet()
        s = fleet_mod.DistributedStrategy()
        s.recompute = True
        flt.init(strategy=s)
        flt.distributed_optimizer(
            fluid.optimizer.SGDOptimizer(learning_rate=0.1)).minimize(loss)
    assert main._recompute == {"policy": "dots"}


def test_bf16_amp_conv_model_trains():
    """Regression: conv models must train under cast_model_to_bf16 (the
    conv transpose rule used to see mixed f32/bf16 dtypes and abort)."""
    from paddle_tpu import amp
    rng = np.random.default_rng(5)
    x = rng.standard_normal((4, 3, 16, 16)).astype(np.float32)
    y = rng.integers(0, 5, (4, 1)).astype(np.int64)

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        xv = fluid.data(name="x", shape=[4, 3, 16, 16], dtype="float32")
        yv = fluid.data(name="y", shape=[4, 1], dtype="int64")
        h = layers.conv2d(xv, num_filters=8, filter_size=3, padding=1,
                          act="relu")
        h = layers.pool2d(h, pool_size=2, pool_stride=2)
        logits = layers.fc(layers.reshape(h, shape=[4, -1]), size=5)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, yv))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.05, momentum=0.9).minimize(loss)
    amp.cast_model_to_bf16(main)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        losses = []
        for _ in range(12):
            out, = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss])
            losses.append(float(np.asarray(out).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
