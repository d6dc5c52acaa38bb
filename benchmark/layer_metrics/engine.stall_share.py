"""The share of the WHOLE measured window (not of the traced seconds) in
which token deliveries stood still: the time in intervals with no token
to any stream that lasted over 3 x the usual one, the usual one being
the interval that the window's median instant lies in
(`stats.no_token_gaps`, `stats.stall_share`, over the runner's own
request log). 0.0 where every iteration took its usual time. It is the
witness for a run whose `output_tokens_per_s` read low with its
`itl_p95_ms` unmoved: a pause of seconds hides from a p95 of 32,000
gaps, and shows here."""

META = {"layer": "serving engine", "unit": "%", "better": "lower",
        "source": "host_clock", "moves": "output_tokens_per_s"}


def read(run):
    share = run.facts.get("stall_share")
    return None if share is None else 100.0 * share
