"""Operations and bytes of the window walk on hand-counted cases, the
program's count of the keys a walk needed against the yardstick's,
and the readers that use them."""

import types

import pytest

from benchmark import flops, peaks
from benchmark import flops_window_moe as wm
from benchmark.run import _load_reader as _reader


def test_window_attention_work_reads_k_and_v_once_a_kv_head():
    # one decode lane in one window layer at a context past the window:
    # 4,096 keys, 28 query heads on 4 KV heads of 128, bf16
    f, b = wm.window_attention_work([(1, 10000)], 4096, 28, 4, 128, 2)
    assert f == 4 * 28 * 128 * 4096
    assert b == (4096 * 2 * 4 * 128 + 2 * 28 * 128) * 2
    # K and V of a key are read once for the 7 query heads of its KV
    # head: a seventh of what a key read a query head would cost
    per_head_reads = 4096 * 2 * 28 * 128 * 2
    assert (b - 2 * 28 * 128 * 2) * 7 == per_head_reads
    # calls add up: 144 such calls cost 144 times one
    f9, b9 = wm.window_attention_work([(1, 10000)] * 144, 4096, 28, 4,
                                      128, 2)
    assert f9 == 144 * f and b9 == 144 * b
    assert wm.window_attention_work([], 4096, 28, 4, 128, 2) == (0, 0)


def test_window_attention_work_counts_each_querys_own_window():
    """A chunk of 4 queries at positions 6-9 under a window of 8: the
    queries see 7, 8, 8, 8 keys (31 pairs), and the chunk needs keys
    0-9 (10 = min(10, 8 + 4 - 1)); inside the first window, 3 queries at
    0-2 see 1 + 2 + 3 keys and need 3."""
    f, b = wm.window_attention_work([(4, 10)], 8, 1, 1, 1, 1)
    assert (f, b) == (4 * 31, 2 * 10 + 2 * 4)
    f, b = wm.window_attention_work([(3, 3)], 8, 1, 1, 1, 1)
    assert (f, b) == (4 * 6, 2 * 3 + 2 * 3)


@pytest.mark.parametrize("first,columns,want", [
    (10000, 1, 4096),           # decode far past the window: clipped
    (10000, 16, 4096 + 15),     # a chunk: the first query's window on
    (100, 16, 116),             # inside the first window: all of it
    (4090, 16, 4106),           # a chunk straddling the edge: from 0
    (0, 5, 5)])
def test_the_programs_key_count_is_the_yardsticks(first, columns, want):
    """The program counts, a lane call, the keys from its first query's
    position less the window (never below 0) to its last query (the
    `window_key_positions` of an iteration span); the yardstick counts
    min(context, W + C - 1) of a lane call from the request log. Both
    are the context clipped at W for a decode lane, W + C - 1 for a
    chunk past the window."""
    import jax.numpy as jnp
    from paddle_tpu.serving import blocks
    spec = blocks.StepSpec(layers=(), positions="none", tied_head=False,
                           heads=1, kv_heads=1, head_dim=1, norm_eps=0,
                           dims={"window": 4096})
    pos = jnp.arange(first, first + 16, dtype=jnp.int32)[None]
    ctx = blocks.StepContext(spec, 1, 16, jnp.float32, pos, None, None,
                             None, None, None, False, None, None,
                             jnp.asarray([columns], jnp.int32), None)
    calls, keys = (int(x) for x in blocks.window_counts(ctx, 1))
    assert (calls, keys) == (1, want)
    _f, nbytes = wm.window_attention_work([(columns, first + columns)],
                                          4096, 1, 1, 1, 1)
    assert nbytes == 2 * want + 2 * columns


def _run_with(facts, kernel_s, requests=()):
    dev = types.SimpleNamespace(
        kernel_s=lambda needles: kernel_s,
        kernel_share_pct=lambda needles: 7.5 if kernel_s else None)
    traced = types.SimpleNamespace(device=dev, spans=[], t0=0.0, t1=4.0)
    return types.SimpleNamespace(
        traced=traced, facts=dict(facts), requests=list(requests),
        ctx=types.SimpleNamespace(peaks=peaks.peaks_for("TPU v5 lite")))


NEW = ("window_attention.device_share", "window_attention_roofline")

FACTS = {"chunk": 16, "window_layers": 9, "sliding_window": 4096,
         "window_heads": 28, "window_kv_heads": 4, "window_head_dim": 128,
         "kv_itemsize": 2}


def test_new_readers_return_none_where_the_program_says_nothing():
    """On a program without the window walk or the family's facts (the
    parent commit, the other cells), each new reader returns None and
    never raises."""
    run = _run_with({"chunk": 16, "num_heads": 28, "kv_itemsize": 2}, 0.0)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run = _run_with(FACTS, 0.0)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run.traced = None
    for name in NEW:
        assert _reader(name).read(run) is None, name


def test_window_roofline_reads_the_request_log_over_the_window_layers():
    """A prompt of 8,192 in 512 chunks and two streamed tokens, over the
    nine window layers: every chunk past the first window needs 4,111
    keys, a decode token 4,096, whatever the context."""
    req = types.SimpleNamespace(prompt=list(range(8192)), t_submit=0.5,
                                stamps=[1.0, 1.5, 2.0])
    run = _run_with(FACTS, 2e-3, [req])
    got = _reader("window_attention_roofline").read(run)
    calls = flops.lane_calls([req], 16, 0.0, 4.0)
    assert len(calls) == 512 + 2
    ops, nbytes = wm.window_attention_work(calls, 4096, 28, 4, 128, 2)
    keys = sum(min(ctx, 4096 + c - 1) for c, ctx in calls)
    assert keys == 16 * 256 * 257 // 2 + 256 * 4111 + 2 * 4096
    assert nbytes == (keys * 2 * 4 + 2 * 28 * (512 * 16 + 2)) * 128 * 2
    least, bound = flops.least_time_s(9 * ops, 9 * nbytes,
                                      peaks.peaks_for("TPU v5 lite"))
    assert got == pytest.approx(100 * least / 2e-3)
    assert run.facts["window_attention_bound"] == bound
    assert _reader("window_attention.device_share").read(run) == 7.5
