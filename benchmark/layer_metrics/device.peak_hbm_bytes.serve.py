"""Peak bytes in use on the chip, `memory_stats()["peak_bytes_in_use"]`
after the window: the weights, the KV pools as the fused step's
arguments and once more as its outputs (they are not donated), and the
small arrays of an iteration. The backend leaves a step's temporaries
out of this reading (0.77 GB by the compile-time figure in PERF.md
section 4); the engine's jitted step is not the benchmark's to ask for
its `memory_analysis()`."""

META = {"layer": "device", "unit": "bytes", "better": "lower",
        "source": "program_counter", "moves": "output_tokens_per_s"}


def read(run):
    return run.memory_peak_bytes or None
