"""Median, over the device's idle gaps in the traced window, of the time
inside a gap covered by no leaf span of the engine thread: the gap less
the union of plan, launch, fetch, commit and account (the `_serve` loop,
hand-offs of the interpreter lock to client threads). A gap runs from
the end of one execution of the fused step on the chip to the start of
the next; the spans are the program's own, read from the `/host:` planes
of the profiler's trace (`benchmark/host_spans.py`)."""

from benchmark import host_spans

META = {"layer": "serving engine", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "itl_p95_ms"}


def read(run):
    return host_spans.serving_idle_ms(run, "unattributed")
