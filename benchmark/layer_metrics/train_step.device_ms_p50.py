"""Median device time of one execution of the train step: the compiled
program with most device time in the traced window, from the
`XLA Modules` line of the chip's plane."""

META = {"layer": "training step", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "train_tokens_per_s"}


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.heaviest_module_ms_p50() if dev is not None else None
