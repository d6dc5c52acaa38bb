"""Median, over the device's gaps between train steps in the traced
window, of the time inside a gap that the program's `executor.run` span
covers: the host's share of what the chip waits for between steps. A gap
runs from the end of one execution of the train step on the chip to the
start of the next; the span is read from the `/host:` planes of the
profiler's trace (`benchmark/host_spans.py`)."""

from benchmark import host_spans

META = {"layer": "training step", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "train_tokens_per_s"}


def read(run):
    return host_spans.executor_run_ms(run)
