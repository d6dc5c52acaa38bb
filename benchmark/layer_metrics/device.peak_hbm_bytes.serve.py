"""Peak bytes in use on the chip, `memory_stats()["peak_bytes_in_use"]`
after the window. In `gpt2-xl.closed-16` it reads 9.49 GB, and that is
the START-UP's peak, not serving's: the program's startup scope holds
the parameters in float32 (6.2 GB) beside their bf16 copies (3.16 GB)
before it is dropped. Serving itself holds 8.2 GB: the bf16 weights
(3.16 GB) and ONE set of KV pools (5.04 GB), one fused array a layer,
`bf16[1025, 25, 16, 128]` with K beside V (PR 29), which every fused
step is handed, donates and gets back aliased to its outputs (PR 26), so
no second set is ever alive. The backend leaves a step's temporaries out
of this reading; the engine's jitted step is not the benchmark's to ask
for its `memory_analysis()`."""

META = {"layer": "device", "unit": "bytes", "better": "lower",
        "source": "program_counter", "moves": "output_tokens_per_s"}


def read(run):
    return run.memory_peak_bytes or None
