"""Share of the engine's iterations, over the traced window, whose fused
step was dispatched while the step before it was not yet fetched: the
host's plan, feed, dispatch, fetch and commit of that iteration ran
under the device's step. Each of the program's `serving.iteration` spans
carries `overlapped`, the 1 or 0 its iteration added to the counter
`serving.overlapped_iterations` (`engine.py:step`); a program without
that counter writes no such arg, and this reads nothing."""

META = {"layer": "serving engine", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "output_tokens_per_s"}


def read(run):
    if run.traced is None:
        return None
    marks = [(e.get("args") or {}).get("overlapped")
             for e in run.traced.spans
             if e.get("name") == "serving.iteration"]
    marks = [m for m in marks if m is not None]
    return 100.0 * sum(marks) / len(marks) if marks else None
