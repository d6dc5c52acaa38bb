"""The flash kernel under an executor-activated mesh. GSPMD cannot
partition a Mosaic kernel (on a four-chip host the data-parallel train
step died with "Mosaic kernels cannot be automatically partitioned.
Please wrap the call in a shard_map"), so `dot_product_attention` wraps
the call itself: batch over 'dp', heads over 'tp'. On CPU the kernel is
interpreted and GSPMD would cope either way — what is pinned here is
that the wrap computes the same attention, forward and backward, with
every optional operand."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash

pytestmark = pytest.mark.pallas


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("extra", ["key_bias", "low_rank_bias",
                                   "segments"])
def test_flash_on_mesh_matches_single_device(monkeypatch, extra):
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    b, h, t, d = 4, 4, 32, 16
    q, k, v = (_rand((b, h, t, d), s) for s in (0, 1, 2))
    kw = {}
    if extra == "key_bias":
        m = np.zeros((b, 1, 1, t), np.float32)
        m[1, :, :, t // 2:] = -1e9
        kw["bias"] = jnp.asarray(m)
    elif extra == "low_rank_bias":
        kw["bias"] = _rand((t, t), 3)           # (Tq, Tk): rank 2
    elif extra == "segments":
        kw["segment_ids"] = jnp.asarray(
            np.repeat([[1, 1, 2, 2]], b, 0).repeat(t // 4, 1))

    def loss(q, k, v):
        o = attention_ops.dot_product_attention(q, k, v, causal=True,
                                                **kw)
        return jnp.sum(jnp.sin(o)), o

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True))
    (_, want), gwant = grad(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    traces0 = flash.TRACE_COUNT
    with mesh:
        (_, got), ggot = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert flash.TRACE_COUNT > traces0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for a, w in zip(ggot, gwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_flash_inside_a_callers_shard_map_is_not_wrapped_again(monkeypatch):
    """The pipeline forward (parallel/pipeline.py) is a full-mesh
    shard_map under the executor's `with mesh:`; attention traced inside
    it sees Manual axes and must run the kernel on its local shard. A
    second shard_map over the same mesh raises "The context mesh ...
    should match the mesh passed to shard_map"."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    b, h, t, d = 4, 4, 32, 16
    q, k, v = (_rand((b, h, t, d), s) for s in (0, 1, 2))

    def loss(q, k, v):
        o = attention_ops.dot_product_attention(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o)), o

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    (_, want), gwant = jax.jit(grad)(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "pp"))
    spec = P("dp")

    def inner(q, k, v):
        assert attention_ops._active_mesh() is None
        (_, o), g = grad(q, k, v)
        return o, g

    traces0 = flash.TRACE_COUNT
    with mesh:
        assert attention_ops._active_mesh() is mesh
        got, ggot = jax.jit(shard_map(
            inner, mesh=mesh, in_specs=(spec,) * 3,
            out_specs=(spec, (spec,) * 3), check_vma=False))(q, k, v)
    assert flash.TRACE_COUNT > traces0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for a, w in zip(ggot, gwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_pipelined_program_with_attention_matches_sequential(monkeypatch):
    """The same thing through the framework: a PipelineOptimizer program
    whose first stage holds attention, run on a pp=2 mesh with the flash
    kernel forced, trains to the sequential Executor's losses."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.parallel import pipeline as pp_mod
    from paddle_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    rs = np.random.RandomState(0)
    feed = {"x": rs.randn(8, 16, 32).astype(np.float32),
            "label": rs.randn(8, 16, 4).astype(np.float32)}

    def run(pipelined):
        main, startup = framework.Program(), framework.Program()
        with framework.program_guard(main, startup):
            x = layers.data("x", shape=[16, 32], dtype="float32")
            label = layers.data("label", shape=[16, 4], dtype="float32")
            h = layers.multi_head_attention(
                x, num_heads=2, causal=True,
                param_attr=fluid.ParamAttr(name="ppa"))
            cut = layers.assign(h)
            y = layers.fc(cut, size=4, num_flatten_dims=2,
                          param_attr=fluid.ParamAttr(name="ppa_fc"))
            loss = layers.mean(layers.square_error_cost(y, label))
            sgd = fluid.optimizer.SGDOptimizer(learning_rate=0.1)
            if pipelined:
                pp_mod.PipelineOptimizer(
                    sgd, cut_list=[[cut]],
                    num_microbatches=4).minimize(loss)
            else:
                sgd.minimize(loss)
        losses = []
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            prog = main
            if pipelined:
                prog = fluid.CompiledProgram(main).with_mesh(
                    make_mesh(pp=2, devices=jax.devices()[:2]))
            for _ in range(3):
                out, = exe.run(prog, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(out).reshape(-1)[0]))
        return losses

    want = run(False)
    traces0 = flash.TRACE_COUNT
    got = run(True)
    assert flash.TRACE_COUNT > traces0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
