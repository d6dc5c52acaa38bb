"""Median host time of one engine iteration inside the traced window:
the program's own `serving.iteration` span (`engine.py:step`, fused step
dispatched -> ids fetched to the host; the fetch synchronises, so it is
a sound wall time of the device step plus transfer, not device time).
The `serving.step_ms` histogram covers the same interval plus commit,
but its buckets (25, 50, 100 ms) cannot resolve a median."""

from benchmark import stats

META = {"layer": "serving engine", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "itl_p95_ms"}


def read(run):
    if run.traced is None:
        return None
    durs = [e["dur"] / 1e3 for e in run.traced.spans
            if e.get("name") == "serving.iteration" and e.get("ph") == "X"]
    return stats.median(durs) if durs else None
