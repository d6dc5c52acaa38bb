"""Median, over the device's idle gaps in the traced window, of the time
inside a gap covered by `serving.feed`, `serving.dispatch` and the head
of `serving.fetch` up to the start of the device's step (uploads, the
launch of the executable, the latency until the device starts). A gap
runs from the end of one execution of the fused step on the chip to the
start of the next; the spans are the program's own, read from the
`/host:` planes of the profiler's trace (`benchmark/host_spans.py`)."""

from benchmark import host_spans

META = {"layer": "serving engine", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "itl_p95_ms"}


def read(run):
    return host_spans.serving_idle_ms(run, "launch")
