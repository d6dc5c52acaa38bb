"""Framework surface on REAL TPU hardware: pieces whose CPU tests
cannot prove device behavior —

- the contrib basic_gru/basic_lstm scan kernels compile and run
  deterministically on the chip (the hoisted-projection scan is a
  different lowering on TPU: MXU matmuls inside a fused While),
- the int64 feed boundary behaves the same on device (accept + convert,
  loud overflow),
- GradientMergeOptimizer's gated update holds bit-exact off-steps on
  device (the snapshot/select must survive XLA:TPU fusion),
- a single-chip train step with donation still aliases buffers,
- a tiny GPT trains through the causal flash path and generates its
  memorized sequence.

Each test is small (seconds of chip time).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def test_contrib_rnn_kernels_on_tpu():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib import layers as contrib_layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard

    np.random.seed(0)
    b, t, d, h = 4, 16, 8, 32
    x = np.random.randn(b, t, d).astype("float32")
    lens = np.random.randint(2, t + 1, (b,)).astype("int32")

    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 5
    with framework.program_guard(main, startup):
        xv = layers.data("x", [b, t, d], append_batch_size=False)
        lv = layers.data("len", [b], dtype="int32",
                         append_batch_size=False)
        g_out, _ = contrib_layers.basic_gru(xv, None, h,
                                            bidirectional=True,
                                            sequence_length=lv)
        l_out, lh, _ = contrib_layers.basic_lstm(g_out, None, None, h)
    exe = fluid.Executor()
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        params = {k: np.asarray(v) for k, v in scope._vars.items()}
        got = exe.run(main, feed={"x": x, "len": lens},
                      fetch_list=[l_out, lh])
        tpu_out = [np.asarray(v) for v in got]
    assert all(np.isfinite(o).all() for o in tpu_out)
    # cross-check vs the same params on CPU in a subprocess-free way:
    # the suite's CPU goldens already pin the math; here assert the
    # TPU lowering agrees with itself deterministically
    with scope_guard(scope):
        scope._vars.clear()
        scope._vars.update({k: v for k, v in params.items()})
        got2 = exe.run(main, feed={"x": x, "len": lens},
                       fetch_list=[l_out, lh])
    for a, b_ in zip(tpu_out, got2):
        np.testing.assert_allclose(a, np.asarray(b_), rtol=1e-5,
                                   atol=1e-6)


def test_int64_policy_on_tpu():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        ids = layers.data("ids", [4, 3], dtype="int64",
                          append_batch_size=False)
        emb = layers.embedding(ids, size=(50, 8))
        out = layers.reduce_sum(emb)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        got = exe.run(main, feed={"ids": np.ones((4, 3), np.int64) * 7},
                      fetch_list=[out])
        assert np.isfinite(np.asarray(got[0])).all()
        bad = np.ones((4, 3), np.int64)
        bad[0, 0] = 2 ** 31
        with pytest.raises(OverflowError, match="MIGRATION.md"):
            exe.run(main, feed={"ids": bad}, fetch_list=[out])


def test_gradient_merge_off_steps_exact_on_tpu():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard

    K = 3
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", [4, 6], append_batch_size=False)
        y = layers.data("y", [4, 1], append_batch_size=False)
        loss = layers.mean(layers.square_error_cost(
            layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="w")),
            y))
        fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.AdamOptimizer(1e-2), K).minimize(loss)
    exe = fluid.Executor()
    scope = Scope()
    rng = np.random.default_rng(0)
    with scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.get("w")).copy()
        for i in range(K - 1):
            exe.run(main, feed={
                "x": rng.standard_normal((4, 6)).astype("float32"),
                "y": rng.standard_normal((4, 1)).astype("float32")},
                fetch_list=[loss])
            np.testing.assert_array_equal(np.asarray(scope.get("w")), w0)
        exe.run(main, feed={
            "x": rng.standard_normal((4, 6)).astype("float32"),
            "y": rng.standard_normal((4, 1)).astype("float32")},
            fetch_list=[loss])
        assert not np.array_equal(np.asarray(scope.get("w")), w0)


def test_single_chip_step_donation_aliases():
    import re
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", [8, 16], append_batch_size=False)
        y = layers.data("y", [8, 1], dtype="int64",
                        append_batch_size=False)
        h = layers.fc(x, size=32, act="relu")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=4), y))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    exe = fluid.Executor()
    rng = np.random.default_rng(1)
    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(main, feed={
            "x": rng.standard_normal((8, 16)).astype("float32"),
            "y": rng.integers(0, 4, (8, 1)).astype(np.int64)},
            fetch_list=[loss])
    header = exe.last_compiled_text().splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
    assert m and re.findall(r"\{\d+\}:", m.group(1)), (
        "no donated-buffer aliasing in the single-chip TPU step")


def test_gpt_train_and_generate_on_tpu():
    """Decoder-only flagship on the chip: causal flash path trains a
    tiny LM and the KV-cache generate matches the memorized sequence."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt

    cfg = gpt.gpt_tiny()
    rng = np.random.RandomState(2)
    toks = rng.randint(3, cfg.vocab_size, (1, 12)).astype("int64")
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with framework.program_guard(main, startup):
        _t, loss, _l = gpt.build_lm_net(cfg, seq_len=12)
        fluid.optimizer.AdamOptimizer(3e-3).minimize(loss)
    exe = fluid.Executor()
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(120):
            out = exe.run(main, feed={"tokens": toks}, fetch_list=[loss])
    assert float(np.asarray(out[0]).reshape(-1)[0]) < 0.05
    ids, _ = gpt.generate(scope, cfg, toks[:1, 0], max_len=11)
    np.testing.assert_array_equal(np.asarray(ids)[0], toks[0, 1:])
