"""Gap attribution on synthetic interval lists."""

import types

import pytest

from benchmark import host_spans as hs
from benchmark.trace import Event


def ev(name, start, dur, **stats):
    return Event(name, start, dur, stats)


def iteration(t, it, plan=2, feed=1, dispatch=3, fetch=104, commit=4,
              account=2, with_parent=True):
    """One engine iteration's spans starting at t, the way step() lays
    them: plan, [iteration: feed, dispatch, fetch], commit, account."""
    out, names = [], ("serving.plan", "serving.feed", "serving.dispatch",
                      "serving.fetch", "serving.commit", "serving.account")
    at = t
    for name, dur in zip(names, (plan, feed, dispatch, fetch, commit,
                                 account)):
        out.append(ev(name, at, dur, iteration=it))
        at += dur
    if with_parent:
        out.append(ev("serving.iteration", t + plan,
                      feed + dispatch + fetch, iteration=it))
    return out


def steps(n, period=116, lead=8, dur=100):
    """n executions of jit_fused: each starts `lead` after its
    iteration's plan began and runs `dur`."""
    return [ev(f"jit_fused({i})", i * period + lead, dur)
            for i in range(n)] + [ev("jit_other(9)", 3, 1)]


def test_gaps_lie_between_executions_of_the_named_module():
    gaps = hs.module_gaps(steps(3), "jit_fused")
    assert gaps == [(108, 124), (224, 240)]
    assert hs.module_gaps(steps(1), "jit_fused") == []


def test_parts_sum_to_the_gap_and_the_parent_is_not_counted():
    spans = [s for i in range(3) for s in iteration(i * 116, i)]
    gap = hs.module_gaps(steps(3), "jit_fused")[0]       # (108, 124)
    got = hs.attribute(gap, spans, hs.SERVING_PARTS)
    # iteration 0: fetch until 110, commit until 114, account until 116;
    # iteration 1: plan until 118, feed 119, dispatch 122, fetch's head
    # until the device starts at 124
    assert got == {"fetch": 2, "commit": 4, "account": 2, "plan": 2,
                   "launch": 1 + 3 + 2, hs.UNATTRIBUTED: 0}
    assert sum(got.values()) == gap[1] - gap[0]
    # serving.iteration covers feed..fetch of every iteration and is in
    # no part: with only the parent there, everything is unattributed
    only_parent = [s for s in spans if s.name == "serving.iteration"]
    got = hs.attribute(gap, only_parent, hs.SERVING_PARTS)
    assert got[hs.UNATTRIBUTED] == 16
    assert all(v == 0 for k, v in got.items() if k != hs.UNATTRIBUTED)


def test_time_under_no_leaf_span_is_unattributed():
    # device: [0, 100) and [120, 220); the host leaves 106..109 (between
    # commit and plan) under no span
    modules = [ev("jit_fused(1)", 0, 100), ev("jit_fused(2)", 120, 100)]
    spans = [ev("serving.fetch", -10, 113), ev("serving.commit", 103, 3),
             ev("serving.plan", 109, 4), ev("serving.feed", 113, 1),
             ev("serving.dispatch", 114, 3), ev("serving.fetch", 117, 110)]
    (gap,) = hs.module_gaps(modules, "jit_fused")
    got = hs.attribute(gap, spans, hs.SERVING_PARTS)
    assert got == {"fetch": 3, "commit": 3, "account": 0, "plan": 4,
                   "launch": 1 + 3 + 3, hs.UNATTRIBUTED: 3}
    assert sum(got.values()) == gap[1] - gap[0] == 20


def test_a_span_straddling_an_edge_is_clipped_to_the_gap():
    gap = (100, 120)
    spans = [ev("serving.commit", 90, 15),      # 100..105 inside
             ev("serving.plan", 118, 50)]       # 118..120 inside
    got = hs.attribute(gap, spans, hs.SERVING_PARTS)
    assert got["commit"] == 5 and got["plan"] == 2
    assert got[hs.UNATTRIBUTED] == 20 - 7
    # fetch: the one that runs past the gap's end is the head (launch),
    # the one that began before the gap and ends in it is the tail
    spans = [ev("serving.fetch", 50, 55), ev("serving.fetch", 115, 90)]
    got = hs.attribute(gap, spans, hs.SERVING_PARTS)
    assert got["fetch"] == 5 and got["launch"] == 5


def test_medians_over_the_gaps_the_spans_saw():
    modules = steps(6)
    spans = [s for i in range(1, 5) for s in iteration(i * 116, i)]
    gaps = hs.module_gaps(modules, "jit_fused")
    assert len(gaps) == 5
    # spans run from 116 (plan of 1) to 5 * 116: the gaps before the
    # first and after the last span are not the spans' to explain
    seen = hs.seen_gaps(gaps, spans)
    assert seen == gaps[1:4]
    med = hs.part_medians_ms(gaps, spans, hs.SERVING_PARTS)
    assert med["gap"] == pytest.approx(16e-6)
    assert med["commit"] == pytest.approx(4e-6)
    assert set(med) == set(hs.SERVING_PARTS) | {hs.UNATTRIBUTED, "gap"}
    assert hs.part_medians_ms(gaps, [], hs.SERVING_PARTS) == {}
    assert hs.part_medians_ms([], spans, hs.SERVING_PARTS) == {}


def test_device_step_inside_the_hosts_feed_to_fetch():
    modules = steps(4)
    spans = [s for i in range(4) for s in iteration(i * 116, i)]
    assert hs.contained_share(modules, "jit_fused", spans,
                              "serving.feed", "serving.fetch") == 1.0
    # a host clock 60 ns off the device's: no step is inside any more
    late = [ev(s.name, s.start_ns + 60, s.dur_ns, **s.detail)
            for s in spans]
    assert hs.contained_share(modules, "jit_fused", late,
                              "serving.feed", "serving.fetch") == 0.0
    assert hs.contained_share(modules, "jit_fused", [], "a", "b") is None


def test_readers_return_none_where_there_is_nothing_to_read():
    """An untraced run, and a run of a program that writes no such span
    (the parent of the PR that added them): None, never an error."""
    run = types.SimpleNamespace(traced=None)
    assert hs.serving_idle_ms(run, "plan") is None
    assert hs.executor_run_ms(run) is None
    dev = types.SimpleNamespace(
        planes={"/device:TPU:0": {"XLA Modules": steps(3)}})
    traced = types.SimpleNamespace(device=dev, t1=1.0, dir="/nonexistent")
    run = types.SimpleNamespace(traced=traced)
    assert hs.serving_idle_ms(run, "plan") is None
    assert hs.executor_run_ms(run) is None
