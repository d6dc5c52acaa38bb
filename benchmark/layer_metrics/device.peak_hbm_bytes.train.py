"""Bytes the compiled train step holds on the chip at its peak, by the
compiler's own account (`memory_analysis()` of the step, through
`Executor.explain`): arguments + outputs - aliased (the donated state
counts once) + temporaries (activations kept for the backward pass and
scratch). The backend's `peak_bytes_in_use`, which `device` reports as
`memory_peak_bytes`, leaves a step's temporaries out: it read 4.56 GB
where the step holds 14.64 (PERF.md section 4)."""

META = {"layer": "device", "unit": "bytes", "better": "lower",
        "source": "program_counter", "moves": "train_tokens_per_s"}


def read(run):
    m = run.samples.get("step_memory")
    if not m or run.ctx.peaks is None:      # a rehearsal has no chip
        return None
    return (m["argument_size_in_bytes"] + m["output_size_in_bytes"]
            - m["alias_size_in_bytes"] + m["temp_size_in_bytes"])
