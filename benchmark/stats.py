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


def trimmed_range_share(values):
    """(max - min) over the median of a set of runs, after leaving out
    the run farthest from the median where that narrows the range: the
    spread the driver's check reckons with (PERF_LEDGER.jsonl: "a spread
    leaves out the run farthest from its median where that narrows
    it"). One far-off run in a set does no harm, two do. The median is
    that of the whole set."""
    if len(values) < 3:
        raise ValueError("a trimmed range wants three runs or more")
    mid = median(values)
    rest = list(values)
    rest.remove(max(values, key=lambda v: abs(v - mid)))
    return (max(rest) - min(rest)) / mid


def no_token_gaps(streams, t0, t1):
    """The intervals inside [t0, t1] in which NO stream received a
    token: every stream's stamps merged, the window's two ends added,
    consecutive differences taken. `streams` is a list of lists of
    stamps; returns [(start, end)] in the stamps' own seconds. Tokens
    that one iteration hands to several streams arrive microseconds
    apart; those intervals are in the list too, and weigh what they
    last, which is nothing (see `weighted_median_s`)."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    merged = sorted(t for s in streams for t in s if t0 <= t <= t1)
    points = [t0] + merged + [t1]
    return [(a, b) for a, b in zip(points, points[1:]) if b > a]


def weighted_median_s(intervals):
    """The length of the interval that the median INSTANT of the covered
    time lies in: half of the time is spent in intervals no longer than
    it. For the gaps between token deliveries that is the length of an
    ordinary iteration, however many lanes an iteration serves."""
    lengths = sorted(b - a for a, b in intervals)
    if not lengths:
        raise ValueError("median of no intervals")
    half, run = sum(lengths) / 2.0, 0.0
    for length in lengths:
        run += length
        if run >= half:
            return length
    return lengths[-1]


def stalls(intervals, factor=3.0):
    """The no-token intervals longer than `factor` x their
    (time-weighted) median: where deliveries stood still."""
    limit = factor * weighted_median_s(intervals)
    return [(a, b) for a, b in intervals if b - a > limit]


def stall_share(intervals, t0, t1, factor=3.0):
    """The share of the window [t0, t1] spent in `stalls`: 0.0 where
    every iteration took its usual time, 0.02 where deliveries stopped
    for 2% of the window."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    return sum(b - a for a, b in stalls(intervals, factor)) / (t1 - t0)


def by_tenth(events, t0, t1):
    """`events` is [(time, weight)]: the weights summed over each tenth
    of the window [t0, t1], ten numbers. An event outside the window is
    left out; one at t1 counts in the last tenth."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    out = [0] * 10
    for t, w in events:
        if t0 <= t <= t1:
            out[min(9, int(10 * (t - t0) / (t1 - t0)))] += w
    return out
