"""Median device time of one execution of the fused step: the compiled
program with most device time in the traced window (the serving loop
runs nothing else there), from the `XLA Modules` line of the chip's
plane."""

META = {"layer": "fused step", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.heaviest_module_ms_p50() if dev is not None else None
