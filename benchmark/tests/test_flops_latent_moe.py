"""Operations and bytes of the latent-attention / mixture-of-experts
family's two kernels, and the readers that use them."""

import types

import pytest

from benchmark import peaks
from benchmark.run import _load_reader as _reader


def test_latent_attention_work_reads_a_row_once_for_all_heads():
    from benchmark import flops_latent_moe as lm
    # a chunk of 16 queries at context 2048, 32 heads, rows of 576
    # values of which 512 are the value, bf16
    f, b = lm.latent_attention_work([(16, 2048)], 32, 576, 512, 2)
    assert f == 2 * 16 * 2048 * 32 * (576 + 512)
    assert b == (2048 * 576 + 16 * 32 * (576 + 512)) * 2
    f1, b1 = lm.latent_attention_work([(16, 2048), (1, 100)], 32, 576,
                                      512, 2)
    assert f1 == f + 2 * 100 * 32 * 1088 and b1 > b
    assert lm.latent_attention_work([], 32, 576, 512, 2) == (0, 0)


def test_held_experts_work_counts_touched_weights_and_assignments():
    from benchmark import flops_latent_moe as lm
    f, b = lm.held_experts_work(8, 6, 2048, 768, 2)
    assert f == 8 * 3 * 2 * 2048 * 768
    assert b == (6 * 3 * 2048 * 768 + 8 * 2 * 2048) * 2
    assert lm.held_experts_work(0, 0, 2048, 768, 2) == (0, 0)


def test_routing_counts_sum_the_iteration_spans():
    from benchmark import flops_latent_moe as lm
    spans = [
        {"name": "serving.iteration", "args": {
            "moe_assignments": 800, "moe_assignments_held": 50,
            "moe_expert_tokens_max": 4, "moe_experts_touched": 30}},
        {"name": "serving.plan", "args": {"moe_assignments_held": 9}},
        {"name": "serving.iteration", "args": {
            "moe_assignments": 80, "moe_assignments_held": 5,
            "moe_expert_tokens_max": 1, "moe_experts_touched": 5}}]
    assert lm.routing_counts(spans) == (880, 55, 35)
    # a program without expert layers says nothing: the readers return
    # None and the line leaves the metric out
    assert lm.routing_counts([{"name": "serving.iteration",
                               "args": {"valid_columns": 3}}]) is None


def _run_with(spans, facts, kernel_s):
    dev = types.SimpleNamespace(
        kernel_s=lambda needles: kernel_s,
        kernel_share_pct=lambda needles: 12.5 if kernel_s else None)
    traced = types.SimpleNamespace(device=dev, spans=spans, t0=0.0, t1=4.0)
    return types.SimpleNamespace(
        traced=traced, facts=dict(facts), requests=[],
        ctx=types.SimpleNamespace(peaks=peaks.peaks_for("TPU v5 lite")))


def test_new_readers_return_none_where_the_program_says_nothing():
    """On a program without the kernels or the counts (the parent
    commit, or gpt2-xl), each new reader returns None and never
    raises."""
    run = _run_with([{"name": "serving.iteration", "args": {}}],
                    {"chunk": 16, "num_heads": 25, "num_layers": 48,
                     "kv_itemsize": 2}, 0.0)
    for name in ("latent_attention.device_share",
                 "latent_attention_roofline", "moe_experts.device_share",
                 "moe_experts_roofline",
                 "moe.expert_tokens_max_over_mean"):
        assert _reader(name).read(run) is None, name
    run.traced = None
    for name in ("latent_attention_roofline", "moe_experts_roofline",
                 "moe.expert_tokens_max_over_mean"):
        assert _reader(name).read(run) is None, name


def test_moe_readers_read_the_counts():
    spans = [{"name": "serving.iteration", "ph": "X", "args": {
        "moe_assignments": 800, "moe_assignments_held": 64,
        "moe_expert_tokens_max": 8, "moe_experts_touched": 16}}]
    facts = {"expert_hidden": 2048, "expert_inner": 768,
             "kv_itemsize": 2}
    run = _run_with(spans, facts, 1e-3)
    # 16 experts' weights at 819 GB/s: 184 us of a 1 ms kernel
    got = _reader("moe_experts_roofline").read(run)
    nbytes = (16 * 3 * 2048 * 768 + 64 * 2 * 2048) * 2
    assert got == pytest.approx(100 * nbytes / 819e9 / 1e-3)
    assert run.facts["moe_experts_bound"] == "memory"
    assert _reader("moe.expert_tokens_max_over_mean").read(run) == 2.0
    assert _reader("moe_experts.device_share").read(run) == 12.5
