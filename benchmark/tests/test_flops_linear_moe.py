"""Operations and bytes of the linear-attention / mixture-of-experts
family's kernels on hand-counted cases, and the readers that use
them."""

import types

import pytest

from benchmark import peaks
from benchmark.run import _load_reader as _reader


def test_kda_chunk_work_reads_and_writes_a_state_a_lane_call():
    from benchmark import flops_linear_moe as lm
    # one lane, one layer, a chunk of 16 valid columns, 64 heads of
    # 128 x 128, float32 state, bf16 streams
    f, b = lm.kda_chunk_work(1, 16, 64, 128, 128, 4, 2)
    assert f == 16 * 64 * 2 * 4 * 128 * 128
    state = 64 * 128 * 128 * 4              # 4,194,304 bytes
    # q, k, g (128 each), v, o (128 each), beta (1): 641 values a head
    assert b == 2 * state + 16 * 64 * 641 * 2
    # a decode lane's one column moves the same state
    f1, b1 = lm.kda_chunk_work(1, 1, 64, 128, 128, 4, 2)
    assert f1 == f // 16 and b1 == 2 * state + 64 * 641 * 2
    # counts arrive summed over lanes and layers: 9 layers x 16 lanes
    f9, b9 = lm.kda_chunk_work(144, 9 * 176, 64, 128, 128, 4, 2)
    assert b9 == 144 * 2 * state + 9 * 176 * 64 * 641 * 2
    assert f9 == 9 * 176 * 64 * 131072
    assert lm.kda_chunk_work(0, 0, 64, 128, 128, 4, 2) == (0, 0)


def test_gqa_attention_work_reads_k_and_v_once_a_kv_head():
    from benchmark import flops, flops_linear_moe as lm
    # a chunk of 16 queries at context 4096, 64 query heads on 8 KV
    # heads of 128, bf16
    f, b = lm.gqa_attention_work([(16, 4096)], 64, 8, 128, 2)
    assert f == 4 * 16 * 4096 * 64 * 128
    assert b == (2 * 4096 * 8 * 128 + 2 * 16 * 64 * 128) * 2
    # the same operations as multi-head attention, an eighth of its
    # key and value bytes
    f_mha, b_mha = flops.paged_attention_work([(16, 4096)], 64, 128, 2)
    assert f == f_mha
    assert b_mha - b == 2 * 4096 * (64 - 8) * 128 * 2
    f2, _ = lm.gqa_attention_work([(16, 4096), (1, 100)], 64, 8, 128, 2)
    assert f2 == f + 4 * 100 * 64 * 128
    assert lm.gqa_attention_work([], 64, 8, 128, 2) == (0, 0)


def test_state_counts_sum_the_iteration_spans():
    from benchmark import flops_linear_moe as lm
    spans = [
        {"name": "serving.iteration", "args": {
            "kda_lane_calls": 144, "kda_columns": 1584,
            "state_resets": 1}},
        {"name": "serving.plan", "args": {"kda_lane_calls": 7}},
        {"name": "serving.iteration", "args": {
            "kda_lane_calls": 9, "kda_columns": 9, "state_resets": 0}}]
    assert lm.state_counts(spans) == (153, 1593)
    # a program without state layers says nothing: the readers return
    # None and the line leaves the metric out
    assert lm.state_counts([{"name": "serving.iteration",
                             "args": {"valid_columns": 3}}]) is None


def _run_with(spans, facts, kernel_s, requests=()):
    dev = types.SimpleNamespace(
        kernel_s=lambda needles: kernel_s,
        kernel_share_pct=lambda needles: 12.5 if kernel_s else None)
    traced = types.SimpleNamespace(device=dev, spans=spans, t0=0.0, t1=4.0)
    return types.SimpleNamespace(
        traced=traced, facts=dict(facts), requests=list(requests),
        ctx=types.SimpleNamespace(peaks=peaks.peaks_for("TPU v5 lite")))


NEW = ("kda_scan.device_share", "kda_scan_roofline",
       "paged_attention_gqa_roofline")


def test_new_readers_return_none_where_the_program_says_nothing():
    """On a program without the kernel, the counts or the family's
    facts (the parent commit, gpt2-xl, JoyAI), each new reader returns
    None and never raises."""
    run = _run_with([{"name": "serving.iteration", "args": {}}],
                    {"chunk": 16, "num_heads": 25, "num_layers": 48,
                     "kv_itemsize": 2}, 0.0)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run.traced = None
    for name in NEW:
        assert _reader(name).read(run) is None, name


def test_kda_readers_read_the_counts():
    spans = [{"name": "serving.iteration", "ph": "X", "args": {
        "kda_lane_calls": 144, "kda_columns": 1584}}]
    facts = {"kda_heads": 64, "kda_key_dim": 128, "kda_value_dim": 128,
             "kda_state_itemsize": 4, "kv_itemsize": 2}
    run = _run_with(spans, facts, 4e-3)
    got = _reader("kda_scan_roofline").read(run)
    nbytes = 144 * 2 * 64 * 128 * 128 * 4 + 1584 * 64 * 641 * 2
    # 1.34 GB at 819 GB/s: 1.63 ms of a 4 ms kernel
    assert got == pytest.approx(100 * nbytes / 819e9 / 4e-3)
    assert 40 < got < 42
    assert run.facts["kda_scan_bound"] == "memory"
    assert _reader("kda_scan.device_share").read(run) == 12.5


def test_gqa_roofline_reads_the_request_log_over_the_gqa_layers():
    req = types.SimpleNamespace(prompt=list(range(4096)), t_submit=0.5,
                                stamps=[1.0, 1.5, 2.0])
    facts = {"chunk": 16, "gqa_layers": 3, "gqa_heads": 64,
             "gqa_kv_heads": 8, "gqa_head_dim": 128, "kv_itemsize": 2}
    run = _run_with([], facts, 2e-3, [req])
    got = _reader("paged_attention_gqa_roofline").read(run)
    from benchmark import flops, flops_linear_moe as lm
    calls = flops.lane_calls([req], 16, 0.0, 4.0)
    assert len(calls) == 256 + 2
    ops, nbytes = lm.gqa_attention_work(calls, 64, 8, 128, 2)
    least, bound = flops.least_time_s(3 * ops, 3 * nbytes,
                                      peaks.peaks_for("TPU v5 lite"))
    assert got == pytest.approx(100 * least / 2e-3)
    assert run.facts["paged_attention_gqa_bound"] == bound
