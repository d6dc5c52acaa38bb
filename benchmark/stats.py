"""The arithmetic that turns the runner's own clock readings into the
end-to-end metrics. Pure functions, no JAX, no program import."""


def percentile(values, q):
    """q in [0, 100], linear interpolation between closest ranks (the
    definition numpy's default uses). Raises on an empty sample: a
    metric with nothing behind it is left out, not reported as 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    if len(s) == 1:
        return float(s[0])
    pos = (len(s) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def median(values):
    return percentile(values, 50.0)


def rate_per_s(count, t0, t1):
    """Work per second over ALL the work and ALL the time of a window."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    return count / (t1 - t0)


def in_window(stamps, t0, t1):
    """How many of the stamps fall inside [t0, t1]."""
    return sum(1 for t in stamps if t0 <= t <= t1)


def gaps_ms(stamps, t0, t1):
    """Gaps between consecutive stamps of ONE stream, in ms, counted
    where the later stamp falls inside [t0, t1]."""
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])
            if t0 <= b <= t1]


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median — the spread the bounds are set from (the contract's
    definition: statistics.quantiles(values, n=4))."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
