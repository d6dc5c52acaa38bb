"""End-to-end verify drive (the .claude/skills/verify recipe, runnable):
static train to acc 1.0 -> clone(for_test) eval -> EMA bare-call
apply/restore round-trip -> save/load_inference_model equality ->
dygraph convergence. CPU-only (JAX_PLATFORMS=cpu): the chip run is
chip_smoke.py's job.

    python tools/verify_drive.py        # prints VERIFY OK
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import jax  # noqa: E402

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

img = layers.data("img", shape=[784], dtype="float32")
label = layers.data("label", shape=[1], dtype="int64")
h = layers.fc(img, size=128, act="relu")
logits = layers.fc(h, size=10)
loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
acc = layers.accuracy(layers.softmax(logits), label)
test_prog = fluid.default_main_program().clone(for_test=True)
opt = fluid.optimizer.AdamOptimizer(1e-3)
opt.minimize(loss)
ema = fluid.optimizer.ExponentialMovingAverage(0.999)
ema.update()

exe = fluid.Executor(fluid.TPUPlace(0))
exe.run(fluid.default_startup_program())

import paddle_tpu.dataset as dataset  # noqa: E402
import paddle_tpu.reader as reader  # noqa: E402

feeder = fluid.DataFeeder(["img", "label"])
last_batch = None
for batch in reader.batch(dataset.mnist.train(), 64)():
    l, a = exe.run(feed=feeder.feed(batch), fetch_list=[loss, acc])
    last_batch = batch
acc_val = np.asarray(a).reshape(-1)[0].item()
print("train acc", acc_val)
assert acc_val >= 0.95, "synthetic mnist should hit ~1.0"

# eval on the cloned test program
l_eval, a_eval = exe.run(test_prog, feed=feeder.feed(last_batch),
                         fetch_list=[loss, acc])
print("eval acc", np.asarray(a_eval).reshape(-1)[0].item())

# EMA fluid-style eval flow (the change under test this commit)
from paddle_tpu.core.executor import global_scope  # noqa: E402

w_train = {p.name: np.asarray(global_scope().get(p.name))
           for p in fluid.default_main_program().all_parameters()}
ema.apply(exe, need_restore=False)
ema.restore(exe)
for name, val in w_train.items():
    np.testing.assert_allclose(
        np.asarray(global_scope().get(name)), val, rtol=1e-6)
print("ema apply/restore round-trip ok")

# save/load inference model round-trip
import tempfile  # noqa: E402

d = tempfile.mkdtemp()
fluid.io.save_inference_model(d, ["img"], [logits], exe,
                              main_program=test_prog)
[prog2, feeds2, fetches2] = fluid.io.load_inference_model(d, exe)
x_in = np.asarray([b[0] for b in last_batch], np.float32)
ref = exe.run(test_prog, feed={"img": x_in}, fetch_list=[logits])[0]
got = exe.run(prog2, feed={feeds2[0]: x_in}, fetch_list=fetches2)[0]
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)
print("inference round-trip ok")

# dygraph loop
with fluid.dygraph.guard():
    fcl = fluid.dygraph.Linear(4, 1)
    sgd = fluid.optimizer.SGDOptimizer(0.1)
    xs = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    ys = (xs @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32))
    first = None
    for i in range(30):
        x = fluid.dygraph.to_variable(xs)
        y = fluid.dygraph.to_variable(ys)
        pred = fcl(x)
        mse = layers.mean(layers.square_error_cost(pred, y))
        mse.backward()
        sgd.minimize(mse)
        fcl.clear_gradients()
        v = float(np.asarray(mse.numpy()))
        first = v if first is None else first
    print("dygraph mse", first, "->", v)
    assert v < first * 0.1
print("VERIFY OK")
