"""Operations and bytes of the power-retention kernel on hand-counted
cases, the family's count of a token's operations, and the readers
that use them."""

import json
import os
import types

import pytest

from benchmark import flops_retention as fr
from benchmark import peaks
from benchmark.run import _load_reader as _reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D = 128 * 129 // 2                          # 8,256 pairs


def test_a_lane_call_reads_and_writes_its_state_and_normaliser_once():
    # one lane, one layer, a chunk of 16 valid columns, 40 query heads
    # on 8 KV heads of 128, float32 state, bf16 streams
    f, b = fr.retention_chunk_work(1, 16, 40, 8, 128, D, 4, 2)
    state = 8 * D * (128 + 1) * 4           # 34,080,768 bytes
    assert b == 2 * state + 16 * (40 + 16) * 128 * 2
    per_column = (2 * 40 * D * 129 + 2 * 8 * D * 128 + 48 * D)
    assert f == 16 * per_column + 2 * 40 * 16 * 17 * 128
    # a decode lane's one column moves the same state
    f1, b1 = fr.retention_chunk_work(1, 1, 40, 8, 128, D, 4, 2)
    assert b1 == 2 * state + 56 * 128 * 2
    assert f1 == per_column + 2 * 40 * 1 * 2 * 128
    # counts arrive summed over lanes and layers: 8 layers x 16 lanes,
    # every lane decoding
    f8, b8 = fr.retention_chunk_work(128, 128, 40, 8, 128, D, 4, 2)
    assert (f8, b8) == (128 * f1, 128 * b1)
    assert fr.retention_chunk_work(0, 0, 40, 8, 128, D, 4, 2) == (0, 0)
    # the bound is memory at every chunk size: 34 MB against < 2 GFLOP
    t_c, t_m = f / 197e12, b / 819e9
    assert t_m > 5 * t_c


def test_the_intra_chunk_terms_are_never_counted_above_need():
    """Two lane calls of 1 and 15 columns need 2 x 40 x (1 x 2 + 15 x
    16) x 128; the counts give their mean, 8, which counts less."""
    f, _ = fr.retention_chunk_work(2, 16, 40, 8, 128, D, 4, 2)
    per_column = (2 * 40 * D * 129 + 2 * 8 * D * 128 + 48 * D)
    intra = f - 16 * per_column
    assert intra == 2 * 2 * 40 * 8 * 9 * 128
    assert intra <= 2 * 40 * (1 * 2 + 15 * 16) * 128


def test_retention_counts_sum_the_iteration_spans():
    spans = [
        {"name": "serving.iteration", "args": {
            "retention_lane_calls": 128, "retention_columns": 2048,
            "state_resets": 1}},
        {"name": "serving.plan", "args": {"retention_lane_calls": 7}},
        {"name": "serving.iteration", "args": {
            "retention_lane_calls": 8, "retention_columns": 8,
            "state_resets": 0}}]
    assert fr.retention_counts(spans) == (136, 2056)
    # the KDA family's counts are not these
    assert fr.retention_counts([{"name": "serving.iteration", "args": {
        "kda_lane_calls": 3, "kda_columns": 3}}]) is None


def test_serving_flops_counts_the_projections_the_mlp_and_the_state():
    from benchmark.models import brumby
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-pp5.json")) as f:
        c = json.load(f)
    got = brumby.serving_flops(c)
    h, inner = 5120, 17408
    proj = (h * 5120 + h * 2048 + h * 8 + 5120 * h + 3 * h * inner)
    retention = 2 * 40 * D * 129 + 2 * 8 * D * 128
    assert got["body_matmul_flops_per_token"] == 8 * (2 * proj + retention)
    # the head once, untied
    assert got["head_matmul_flops_per_token"] == 2 * h * 151936
    assert {k: got[k] for k in got if k.startswith("retention_")} == {
        "retention_layers": 8, "retention_heads": 40,
        "retention_kv_heads": 8, "retention_head_dim": 128,
        "retention_state_rows": D, "retention_state_itemsize": 4}
    # a layer's matrices are 330.35 M parameters: 2 x that a token
    assert proj == pytest.approx(330.3e6, rel=1e-3)


def _run_with(spans, facts, kernel_s):
    dev = types.SimpleNamespace(
        kernel_s=lambda needles: kernel_s if needles == (
            "power_retention",) else 0.0,
        kernel_share_pct=lambda needles: 48.0 if kernel_s else None)
    traced = types.SimpleNamespace(device=dev, spans=spans, t0=0.0, t1=4.0)
    return types.SimpleNamespace(
        traced=traced, facts=dict(facts), requests=[],
        ctx=types.SimpleNamespace(peaks=peaks.peaks_for("TPU v5 lite")))


NEW = ("retention.device_share", "retention_roofline")
FACTS = {"retention_heads": 40, "retention_kv_heads": 8,
         "retention_head_dim": 128, "retention_state_rows": D,
         "retention_state_itemsize": 4, "kv_itemsize": 2}


def test_new_readers_return_none_where_the_program_says_nothing():
    """On a program without the kernel, the counts or the family's
    facts (the parent commit, every other cell), each new reader
    returns None and never raises."""
    run = _run_with([{"name": "serving.iteration", "args": {}}],
                    {"chunk": 16, "num_heads": 25, "kv_itemsize": 2}, 0.0)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run = _run_with([{"name": "serving.iteration", "args": {}}], FACTS,
                    1e-3)
    assert _reader("retention_roofline").read(run) is None
    run.traced = None
    for name in NEW:
        assert _reader(name).read(run) is None, name


def test_retention_readers_read_the_counts():
    # one step of 16 decoding lanes over 8 layers, 1.5 ms a layer
    spans = [{"name": "serving.iteration", "ph": "X", "args": {
        "retention_lane_calls": 128, "retention_columns": 128}}]
    run = _run_with(spans, FACTS, 8 * 1.5e-3)
    got = _reader("retention_roofline").read(run)
    _f, nbytes = fr.retention_chunk_work(128, 128, 40, 8, 128, D, 4, 2)
    assert got == pytest.approx(100 * nbytes / 819e9 / 12e-3)
    # 8.73 GB at 819 GB/s: 10.7 ms of a 12 ms kernel
    assert 88 < got < 90
    assert run.facts["retention_bound"] == "memory"
    assert _reader("retention.device_share").read(run) == 48.0
