"""Flash-attention block-size tuner: sweep (block_q, block_k) on the
current backend and print the fastest config.

Run on a real TPU (one process holds the chip):

    python tools/tune_flash.py --seq 512 --batch 8 --heads 12 --dim 64

The winner is persisted to perf/flash_tuned.json, which
ops/pallas/flash.py default_blocks() reads in every later process —
the end-of-round bench picks up the tuned blocks with no env plumbing.
PADDLE_TPU_FLASH_BLOCK_Q / _K env vars still override both.
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _log(msg):
    print(f"tune_flash: [{time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--blocks", default="128,256,512",
                    help="comma list swept for BOTH block_q and block_k")
    ap.add_argument("--backward", action="store_true",
                    help="time fwd+bwd instead of fwd only")
    ap.add_argument("--dtype", default="bfloat16",
                    help="bfloat16 on TPU; float32 for CPU smoke runs "
                         "(bf16 through the interpreter is glacial)")
    args = ap.parse_args()

    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()
    _log(f"device: {getattr(devs[0], 'device_kind', devs[0])} "
         f"x{len(devs)} ({jax.default_backend()})")
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash

    dtype = jnp.dtype(args.dtype)
    key = jax.random.PRNGKey(0)
    shape = (args.batch, args.heads, args.seq, args.dim)
    q = jax.random.normal(key, shape, dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), shape, dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape, dtype)

    blocks = [int(b) for b in args.blocks.split(",")]
    results = []
    for bq, bk in itertools.product(blocks, blocks):
        if bq > args.seq or bk > args.seq:
            continue
        if args.backward:
            def loss(q, k, v, bq=bq, bk=bk):
                return flash.flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk
                ).astype(jnp.float32).sum()
            fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        else:
            fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash.flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk))
        _log(f"compile+run bq={bq} bk={bk}")
        try:
            out = fn(q, k, v)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / args.steps
        except Exception as e:
            # stdout TOO: the archived artifact must show which configs
            # failed and why (the r4 artifact was empty because failures
            # went only to stderr)
            short = str(e).strip().splitlines()[0][:200] if str(e).strip() \
                else repr(e)[:200]
            print(f"bq={bq:4d} bk={bk:4d}  FAILED: {short}", flush=True)
            print(f"bq={bq:4d} bk={bk:4d}  FAILED: {e}", file=sys.stderr)
            continue
        results.append((dt, bq, bk))
        print(f"bq={bq:4d} bk={bk:4d}  {dt * 1e3:8.3f} ms/step", flush=True)

    if not results:
        # parseable failure record in the artifact (never a 0-byte file)
        print(json.dumps({"failed": True, "error": "no config ran",
                          "swept": blocks, "backward": bool(args.backward)}),
              flush=True)
        print("no config ran", file=sys.stderr)
        return 1
    dt, bq, bk = min(results)
    print(f"\nbest: PADDLE_TPU_FLASH_BLOCK_Q={bq} "
          f"PADDLE_TPU_FLASH_BLOCK_K={bk}  ({dt * 1e3:.3f} ms/step)")
    # persist only results measured on real hardware — a CPU smoke run
    # must not steer TPU block sizes
    backend = jax.default_backend()
    if backend == "tpu":
        # the reader's own path helper: writer and reader cannot diverge
        path = flash.tuned_blocks_path()
        with open(path, "w") as f:
            json.dump({"block_q": bq, "block_k": bk,
                       "ms_per_step": round(dt * 1e3, 3),
                       "backend": backend,
                       "device_kind": jax.devices()[0].device_kind,
                       "seq": args.seq, "batch": args.batch,
                       "heads": args.heads, "dim": args.dim,
                       "backward": bool(args.backward)}, f, indent=1)
        print(f"persisted -> {os.path.normpath(path)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
