"""Host-runtime microbenchmarks: the native C++ pieces vs their
pure-Python baselines, measured on this machine's CPU (no TPU needed).

Writes perf/hostbench.json — committed evidence that the native runtime
(SURVEY §1 "C++ for host-side runtime pieces") buys real throughput,
independent of the accelerator:

  ring        csrc/prefetch.cc push+pop GB/s (copying, bounded-memory
              backpressure — a capacity number; a queue.Queue moves
              references, so a "speedup vs Queue" would be fiction)
  loader      csrc/loader_pool.cc shuffled-batch assembly batches/s
              (capacity; its contract is determinism + off-GIL
              assembly, not beating an inline numpy slice)
  multislot   csrc/dataset_feed.cc parse MB/s vs the Python parser
              (identical work both sides -> honest speedup)
  serve_queue csrc/serve_queue.cc submit->batch latency overhead

Usage: JAX_PLATFORMS=cpu python tools/hostbench.py
"""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "perf", "hostbench.json")


def bench_ring(mb=256, slot_kb=1024):
    from paddle_tpu.reader import native

    payload = b"x" * (slot_kb * 1024)
    n = mb * 1024 // slot_kb  # slots pushed

    ring = native.NativeRing(slots=8, slot_bytes=len(payload) + 64)

    def produce():
        for _ in range(n):
            ring.push(payload)
        ring.close()

    t0 = time.perf_counter()
    th = threading.Thread(target=produce)
    th.start()
    got = 0
    while True:
        b = ring.pop()
        if b is None:
            break
        got += len(b)
    th.join()
    dt = time.perf_counter() - t0
    native_gbs = got / dt / 2**30
    return {"slot_kb": slot_kb, "moved_mb": mb,
            "native_gb_per_s": round(native_gbs, 2)}


def bench_loader(rows=100_000, feat=64, batch=256, epochs=2):
    """Capacity of the deterministic-shuffle off-GIL batch assembler.
    No "speedup" claim: an inline numpy slice is (by design) about as
    fast — the pool exists for determinism across worker counts,
    bounded memory, and keeping assembly off the training thread."""
    from paddle_tpu.reader import native

    xs = np.random.RandomState(0).randn(rows, feat).astype(np.float32)
    ys = np.random.RandomState(1).randint(0, 10, (rows, 1)).astype(np.int32)

    t0 = time.perf_counter()
    pool = native.NativeLoaderPool([xs, ys], batch_size=batch,
                                   epochs=epochs, shuffle_seed=7)
    n_batches = 0
    for b in pool:
        n_batches += 1
    dt = time.perf_counter() - t0
    mbps = n_batches * batch * (feat + 1) * 4 / dt / 2**20
    return {"batch": batch, "feat": feat,
            "batches_per_s": round(n_batches / dt, 1),
            "assembled_mb_per_s": round(mbps, 1)}


def bench_multislot(lines=100_000):
    from paddle_tpu.io import dataset as ds

    # CTR-style MultiSlot line: two sparse slots + one dense slot
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(lines):
        ids1 = " ".join(str(x) for x in rs.randint(0, 1 << 20, 8))
        ids2 = " ".join(str(x) for x in rs.randint(0, 1 << 20, 4))
        dense = " ".join(f"{v:.3f}" for v in rs.rand(13))
        rows.append(f"8 {ids1} 4 {ids2} 13 {dense}\n")
    blob = "".join(rows)
    with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                     delete=False) as f:
        f.write(blob)
        path = f.name
    mb = len(blob) / 2**20
    slots = [{"name": "slot1", "type": "uint64", "is_dense": True},
             {"name": "slot2", "type": "uint64", "is_dense": True},
             {"name": "dense", "type": "float", "is_dense": True}]
    try:
        t0 = time.perf_counter()
        nat, _ = ds._parse_files_native(slots, [path], "cat", False,
                                        False, 4)
        dt_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        py, _ = ds._parse_files_python(slots, [path], "cat", False, False)
        dt_py = time.perf_counter() - t0
        assert len(nat) == len(py)
    finally:
        os.unlink(path)
    return {"file_mb": round(mb, 1),
            "native_mb_per_s": round(mb / dt_native, 1),
            "python_mb_per_s": round(mb / dt_py, 1),
            "speedup": round(dt_py / dt_native, 2)}


def bench_serve_queue(n=20_000):
    from paddle_tpu.inference import serving

    lib = serving.load_library()
    import ctypes

    q = lib.sq_create(64, 500)
    ids = (ctypes.c_int64 * 64)()
    got = []

    def drain():
        while True:
            k = lib.sq_next_batch(q, ids, 64, 200_000)
            if k < 0:
                return
            got.extend(ids[:k])

    th = threading.Thread(target=drain)
    th.start()
    t0 = time.perf_counter()
    for i in range(n):
        lib.sq_submit(q, i)
    lib.sq_close(q)
    th.join()
    dt = time.perf_counter() - t0
    assert len(got) == n
    return {"requests": n,
            "requests_per_s": round(n / dt),
            "us_per_request": round(dt / n * 1e6, 2)}


def main():
    results = {}
    for name, fn in (("ring", bench_ring), ("loader", bench_loader),
                     ("multislot", bench_multislot),
                     ("serve_queue", bench_serve_queue)):
        t0 = time.perf_counter()
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 — record, keep benching
            results[name] = {"failed": True, "error": repr(e)}
        print(f"hostbench {name}: {results[name]} "
              f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
    results["note"] = ("CPU host-runtime microbenchmarks; hardware-"
                      "independent evidence for the native (C++) pieces")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
