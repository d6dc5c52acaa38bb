#!/usr/bin/env python3
"""benchmark/spread.py — the spread of sets of runs, and the bound the
rule gives for each end-to-end metric.

    python3 benchmark/spread.py setA.jsonl setB.jsonl [...]

Each file is one set: the result lines (`run.py`'s last line of standard
output, `--trace 0`) of runs of ONE cell on one tree, a line a run, in
the order they ran, every run with another seed. For each end-to-end
metric of `BENCHMARK.json` that the lines carry it prints each set's
median, `stats.iqr_share` (the contract's spread) and
`stats.trimmed_range_share` (the driver's: max - min over the median,
the run farthest from the median left out where that narrows it), then
the bound by the rule and the bound `BENCHMARK.json` holds:

    bound = max(0.01, 2.5 x the larger of the sets' trimmed range shares)
            rounded up to the next 0.005, never over 0.1

so that each set's spread is at most 40% of the bound (the driver refuses
a bound where the mean of its two sets' spreads is over 50% of it). Two
sets of at least six runs decide. `setup_s` keeps its bound (0.1, judged
by its median alone); its line leaves out each set's first run, which
compiles. A bound is "loose" where it is over 8 x the IQR share of all
the runs together and over 0.01: widened to cover a far-off run, where
the cause should have been found. Exits 1 where a bound in
`BENCHMARK.json` is not the rule's, a set's spread is over 40% of it, a
run is not `correct`, or the second set's median differs from the
first's by more than the bound.
"""

import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402

FLOOR, CEILING, FACTOR, STEP, SHARE = 0.01, 0.1, 2.5, 0.005, 0.4


def rule(trimmed_range_shares):
    """The bound for a metric whose sets spread by these shares."""
    raw = max(FLOOR, FACTOR * max(trimmed_range_shares))
    return min(CEILING, math.ceil(round(raw / STEP, 9)) * STEP)


def read_set(path):
    """The result lines of one file, in order."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def values(runs, metric):
    return [r["metrics"][metric]["value"] for r in runs
            if metric in r.get("metrics", {})]


def report(sets, bench, out=print):
    """Prints the table; returns the list of complaints."""
    wrong = []
    for i, runs in enumerate(sets):
        bad = [r for r in runs if not r.get("correct") or r.get("failed")]
        if bad:
            wrong.append(f"set {i + 1}: {len(bad)} run(s) not correct or "
                         f"with failed requests")
    for m in bench["end_to_end"]:
        name = m["name"]
        skip = 1 if name == "setup_s" else 0
        per_set = [values(runs, name)[skip:] for runs in sets]
        if not all(len(v) >= 3 for v in per_set):
            continue
        out(f"{name} ({m['unit']}, better {m['better']})")
        for i, v in enumerate(per_set):
            out(f"  set {i + 1}: n {len(v)}  median {stats.median(v):.6g}  "
                f"iqr share {stats.iqr_share(v):.5f}  trimmed range share "
                f"{stats.trimmed_range_share(v):.5f}  min {min(v):.6g}  "
                f"max {max(v):.6g}")
        if name == "setup_s":
            out(f"  bound {m['bound']} kept (judged by the median alone; "
                f"each set's first run, which compiles, left out)")
            continue
        shares = [stats.trimmed_range_share(v) for v in per_set]
        bound = rule(shares)
        everything = [x for v in per_set for x in v]
        loose = 8 * stats.iqr_share(everything)
        out(f"  rule: bound {bound:.3f}  (largest trimmed range share "
            f"{max(shares):.5f} = {max(shares) / bound:.0%} of it; 8 x the "
            f"iqr share of all runs {loose:.4f}"
            f"{', LOOSE' if bound > max(loose, FLOOR) else ''})   "
            f"BENCHMARK.json: {m['bound']}")
        if abs(m["bound"] - bound) > 1e-9:
            wrong.append(f"{name}: BENCHMARK.json holds {m['bound']}, the "
                         f"rule gives {bound:.3f}")
        if max(shares) > SHARE * m["bound"]:
            wrong.append(f"{name}: a set spreads by {max(shares):.5f}, "
                         f"over 40% of the bound {m['bound']}")
        meds = [stats.median(v) for v in per_set]
        for later in meds[1:]:
            if abs(later - meds[0]) / meds[0] > m["bound"]:
                wrong.append(f"{name}: medians {meds[0]:.6g} and "
                             f"{later:.6g} differ by more than the bound")
    return wrong


def main(argv=None):
    paths = (argv if argv is not None else sys.argv[1:])
    if not paths:
        print(__doc__)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sets = [read_set(p) for p in paths]
    for p, runs in zip(paths, sets):
        print(f"{p}: {len(runs)} run(s)")
    wrong = report(sets, bench)
    for w in wrong:
        print("NOT MET: " + w)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
