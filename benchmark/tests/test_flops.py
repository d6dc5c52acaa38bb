"""Operations and bytes from shapes, and the readers that use them."""

import types

import pytest

from benchmark import flops, peaks
from benchmark.run import _load_reader as _reader


def test_peaks_table_knows_v5e_and_refuses_the_rest():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_least_time_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = flops.least_time_s(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_time_s(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")


def test_paged_attention_work_from_contexts():
    # one decode token at context 100, 25 heads x 64, bf16
    f, b = flops.paged_attention_work([(1, 100)], 25, 64, 2)
    assert f == 4 * 1 * 100 * 1600
    assert b == 2 * 100 * 1600 * 2 + 2 * 1 * 1600 * 2
    f2, b2 = flops.paged_attention_work([(1, 100), (16, 48)], 25, 64, 2)
    assert f2 == f + 4 * 16 * 48 * 1600 and b2 > b


def test_flash_work_per_kernel():
    f, b = flops.flash_work("flash_fwd", 16, 16, 512, 64, 4)
    assert f == 2 * 2 * 16 * 16 * 512 * 512 * 64
    assert b == 4 * 16 * 16 * 512 * 64 * 4
    assert flops.flash_work("flash_dkv", 16, 16, 512, 64, 4)[0] == 2 * f
    assert flops.flash_work("flash_fwd", 1, 1, 8, 8, 4, causal=True)[0] \
        == flops.flash_work("flash_fwd", 1, 1, 8, 8, 4)[0] // 2


def _request():
    return types.SimpleNamespace(
        prompt=list(range(40)), t_submit=0.0,
        stamps=[3.0, 4.0, 5.0])       # 3 chunks of 16, then 2 decodes


def test_lane_calls_from_a_request_log():
    calls = flops.lane_calls([_request()], 16, 0.0, 10.0)
    assert calls == [(16, 16), (16, 32), (8, 40), (1, 41), (1, 42)]
    # only what falls in the window: the last chunk and the first decode
    assert flops.lane_calls([_request()], 16, 2.5, 4.5) == [(8, 40),
                                                            (1, 41)]


def test_decoder_step_flops_and_the_serving_mfu_reader():
    calls = flops.lane_calls([_request()], 16, 0.0, 10.0)
    ops = flops.decoder_step_flops(calls, 3, 1000, 50, layers=2, heads=4,
                                   head_dim=8)
    # 42 fed tokens, 3 sampled, attention 4 x c x ctx x 32 a layer
    attention = 4 * 32 * (16 * 16 + 16 * 32 + 8 * 40 + 41 + 42)
    assert ops == 42 * 1000 + 3 * 50 + 2 * attention
    mfu = _reader("fused_step.mfu")
    run = types.SimpleNamespace(
        requests=[_request()], t0=0.0, t1=10.0,
        facts={"chunk": 16, "window_tokens": 3, "num_layers": 2,
               "num_heads": 4, "head_dim": 8,
               "body_matmul_flops_per_token": 1000,
               "head_matmul_flops_per_token": 50},
        ctx=types.SimpleNamespace(chips=1, peaks={"flops_per_s": 1e6}))
    assert mfu.read(run) == pytest.approx(100.0 * ops / (10.0 * 1e6))
    del run.facts["body_matmul_flops_per_token"]
    assert mfu.read(run) is None


def test_mfu_reader_arithmetic():
    mfu = _reader("train_step.mfu")
    run = types.SimpleNamespace(
        e2e={"train_tokens_per_s": 24625.0},
        facts={"forward_matmul_flops_per_token": 2.0e9 / 3},
        ctx=types.SimpleNamespace(
            chips=1, peaks=peaks.peaks_for("TPU v5 lite")))
    assert mfu.read(run) == pytest.approx(25.0)
    run.ctx.peaks = None
    assert mfu.read(run) is None
