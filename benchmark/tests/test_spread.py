"""The rule that sets a bound from two sets of runs."""

import json

import pytest

from benchmark import spread


@pytest.mark.parametrize("shares, bound", [
    ((0.001, 0.002), 0.01),        # never under 1%
    ((0.004, 0.004), 0.01),        # 2.5 x 0.4% is the floor itself
    ((0.0041, 0.002), 0.015),      # rounded UP to the next 0.005
    ((0.0166, 0.0088), 0.045),     # the larger of the two sets decides
    ((0.01, 0.01), 0.025),         # 2.5 x 1%, no rounding error upward
    ((0.2, 0.01), 0.1),            # never over the contract's 0.1
])
def test_rule(shares, bound):
    assert spread.rule(shares) == pytest.approx(bound)
    if bound < 0.1:
        assert max(shares) <= spread.SHARE * bound + 1e-12


def _line(tokens, setup=35.0, correct=True):
    return {"correct": correct, "attempted": 230, "failed": 0,
            "metrics": {"output_tokens_per_s": {"value": tokens,
                                                "unit": "tokens/s"},
                        "setup_s": {"value": setup, "unit": "s"}}}


BENCH = {"end_to_end": [
    {"name": "output_tokens_per_s", "unit": "tokens/s",
     "better": "higher", "bound": 0.01},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1}]}


def test_report_holds_the_file_to_the_rule(tmp_path):
    quiet = [_line(640.0 + 0.3 * i, 90.0 if i == 0 else 35.0) for i in range(6)]
    sets = []
    for name in ("a.jsonl", "b.jsonl"):
        path = tmp_path / name
        path.write_text("".join(json.dumps(r) + "\n" for r in quiet))
        sets.append(spread.read_set(str(path)))
    lines = []
    assert spread.report(sets, BENCH, out=lines.append) == []
    text = "\n".join(lines)
    assert "rule: bound 0.010" in text and "setup_s" in text
    # the first run's set-up, which compiles, is left out
    assert "n 5  median 35" in text
    # two far runs in a set: the spread is over 40% of the bound, and
    # the rule asks for another bound than the file holds
    noisy = [sets[0], quiet[:4] + [_line(610.0), _line(612.0)]]
    wrong = spread.report(noisy, BENCH, out=lines.append)
    assert any("over 40%" in w for w in wrong)
    assert any("the rule gives" in w for w in wrong)
    # a run that is not correct is named
    bad = [sets[0], quiet[:5] + [_line(641.0, correct=False)]]
    assert any("not correct" in w
               for w in spread.report(bad, BENCH, out=lines.append))
