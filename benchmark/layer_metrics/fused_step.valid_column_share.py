"""Share of the fused step's (slots, chunk) grid that carried a token,
over the traced window: `valid_columns` over `valid_columns +
padded_columns`, summed over the program's `serving.iteration` spans
(each carries its iteration's two counts). The step computes every
column; a padded one is thrown away."""

META = {"layer": "fused step", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "itl_p95_ms"}


def read(run):
    if run.traced is None:
        return None
    valid = padded = 0
    for e in run.traced.spans:
        args = e.get("args") or {}
        if e.get("name") == "serving.iteration" and "valid_columns" in args:
            valid += args["valid_columns"]
            padded += args["padded_columns"]
    return 100.0 * valid / (valid + padded) if valid + padded else None
