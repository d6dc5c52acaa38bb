"""Op-level microbenchmarks: time the hot kernels on the current backend
and print achieved TFLOP/s (and % of peak when known).

Run on a real TPU:

    python tools/opbench.py                 # all suites
    python tools/opbench.py --ops matmul,flash --dtype bfloat16

Suites: matmul (MXU), conv (ResNet shapes), flash (Pallas attention),
layernorm+softmax (VPU/fusion), embedding (gather). The numbers bound
what bench.py's end-to-end MFU can reach — if matmul sits at 60% of peak
and the model bench at 20%, the gap is scheduling/input, not kernels.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# chip peak table shared with the end-to-end bench
from bench import _peak_flops  # noqa: E402


def _peak(kind):
    if "cpu" in kind.lower():
        return None      # no meaningful MXU peak, even with the env var
                         # still exported from an earlier TPU session
    return _peak_flops(kind)    # an unknown chip is an error there


def _time(fn, *args, steps=20):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="matmul,conv,flash,norm,embedding,rnn")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes: CI/CPU smoke of every suite "
                         "(full shapes would grind for minutes off-TPU)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    dtype = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev))
    peak = _peak(kind)
    print(f"device: {kind}  dtype: {dtype}  "
          f"peak: {peak / 1e12 if peak else '?'} TFLOP/s (bf16 table — "
          f"the % column is only meaningful for --dtype bfloat16)")
    key = jax.random.PRNGKey(0)

    def report(name, seconds, flops):
        tf = flops / seconds / 1e12
        pct = f"{flops / seconds / peak:6.1%}" if peak else "   n/a"
        print(f"{name:<28} {seconds * 1e3:9.3f} ms  {tf:8.2f} TF/s  {pct}")

    suites = set(args.ops.split(","))

    if "matmul" in suites:
        mm_shapes = [(128, 128, 128)] if args.tiny else \
            [(1024, 1024, 1024), (4096, 4096, 4096), (8192, 8192, 8192)]
        for m, n, k in mm_shapes:
            a = jax.random.normal(key, (m, k), dtype)
            b = jax.random.normal(key, (k, n), dtype)
            f = jax.jit(lambda a, b: a @ b)
            dt = _time(f, a, b, steps=args.steps)
            report(f"matmul {m}x{k}x{n}", dt, 2 * m * n * k)

    if "conv" in suites:
        from jax import lax
        conv_shapes = [(2, 3, 8, 32, 3, 1)] if args.tiny else [
            (32, 3, 64, 224, 7, 2), (32, 256, 256, 14, 3, 1)]
        for b, c_in, c_out, hw, khw, stride in conv_shapes:
            x = jax.random.normal(key, (b, c_in, hw, hw), dtype)
            w = jax.random.normal(key, (c_out, c_in, khw, khw), dtype)
            f = jax.jit(lambda x, w: lax.conv_general_dilated(
                x, w, (stride, stride), "SAME"))
            dt = _time(f, x, w, steps=args.steps)
            out_hw = hw // stride
            flops = 2 * b * c_out * out_hw * out_hw * c_in * khw * khw
            report(f"conv {c_in}->{c_out} {hw}px k{khw}", dt, flops)

    if "flash" in suites:
        from paddle_tpu.ops.pallas import flash
        fl_shapes = [(1, 2, 64, 16)] if args.tiny else \
            [(8, 12, 512, 64), (1, 12, 4096, 64)]
        for b, h, t, d in fl_shapes:
            q = jax.random.normal(key, (b, h, t, d), dtype)
            f = jax.jit(lambda q: flash.flash_attention(q, q, q,
                                                        causal=True))
            try:
                dt = _time(f, q, steps=max(2, args.steps // 2))
            except Exception as e:
                print(f"flash b{b} t{t}: FAILED {e}", file=sys.stderr)
                continue
            flops = 2 * 2 * b * h * t * t * d // 2   # causal half
            report(f"flash b{b} h{h} t{t}", dt, flops)

    if "rnn" in suites:
        # the contrib basic_gru/basic_lstm scan kernels: hoisted input
        # projection (one big MXU matmul) + (H, kH) recurrent matmuls
        # inside one XLA While — reported as recurrent-matmul TFLOP/s
        from paddle_tpu.ops import _REGISTRY as _ops

        class _RCtx:
            def __init__(self, ins, attrs):
                self._i, self._a = ins, attrs
                self.is_test = True

            def in_(self, s, d=None):
                return self._i.get(s, d)

            def has_in(self, s):
                return s in self._i

            def attr(self, n, d=None):
                return self._a.get(n, d)

        b, t, d, h = (2, 32, 32, 64) if args.tiny else (32, 512, 512, 1024)
        x = jax.random.normal(key, (b, t, d), jnp.float32)
        gw = jax.random.normal(key, (d + h, 2 * h), jnp.float32) * 0.05
        cw = jax.random.normal(key, (d + h, h), jnp.float32) * 0.05
        lw = jax.random.normal(key, (d + h, 4 * h), jnp.float32) * 0.05
        gru = jax.jit(lambda x: _ops["basic_gru"](_RCtx(
            {"Input": x, "GateW": gw, "GateB": jnp.zeros(2 * h),
             "CandW": cw, "CandB": jnp.zeros(h)}, {}))["Hidden"])
        dt = _time(gru, x, steps=args.steps)
        flops = 2 * b * t * ((d + h) * 3 * h)
        report(f"basic_gru b{b} t{t} h{h}", dt, flops)
        lstm = jax.jit(lambda x: _ops["basic_lstm"](_RCtx(
            {"Input": x, "Weight": lw, "Bias": jnp.zeros(4 * h)},
            {}))["Hidden"])
        dt = _time(lstm, x, steps=args.steps)
        report(f"basic_lstm b{b} t{t} h{h}", dt,
               2 * b * t * ((d + h) * 4 * h))

    if "norm" in suites:
        nrm = (256, 64) if args.tiny else (8192, 1024)
        x = jax.random.normal(key, nrm, jnp.float32)
        f = jax.jit(lambda x: jax.nn.softmax(
            (x - x.mean(-1, keepdims=True)) / (x.std(-1, keepdims=True)
                                               + 1e-5)))
        dt = _time(f, x, steps=args.steps)
        report(f"layernorm+softmax {nrm[0]}x{nrm[1]}", dt, 10 * x.size)

    if "embedding" in suites:
        tn, td = (1000, 64) if args.tiny else (50_000, 768)
        tbl = jax.random.normal(key, (tn, td), dtype)
        ids = jax.random.randint(key, (64,) if args.tiny else (8 * 512,),
                                 0, tn)
        f = jax.jit(lambda tbl, ids: tbl[ids])
        dt = _time(f, tbl, ids, steps=args.steps)
        gb = (ids.size * td * tbl.dtype.itemsize) / 2**30
        print(f"{f'embedding gather {ids.size}x{td}':<28} {dt * 1e3:9.3f} ms  "
              f"{gb / dt:8.2f} GB/s")

    return 0


if __name__ == "__main__":
    sys.exit(main())
