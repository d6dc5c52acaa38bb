"""The trace reduction on synthetic interval lists."""

import pytest

from benchmark import trace
from benchmark.trace import Event


def ev(name, start, dur, detail=""):
    return Event(name, start, dur, detail)


def test_union_counts_overlap_once():
    events = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5),
              ev("d", 31, 2)]
    assert trace.union_ns(events) == 15 + 5
    assert trace.union_ns([]) == 0


def test_clip_cuts_and_drops():
    events = [ev("a", 0, 10), ev("b", 20, 10), ev("c", 50, 5)]
    got = trace.clip(events, 5, 25)
    assert [(e.name, e.start_ns, e.dur_ns) for e in got] == \
        [("a", 5, 5), ("b", 20, 5)]


def test_instruction_text_splits_into_name_and_operands():
    text = ("%paged_attention_v1.61 = bf16[16,25,16,64]{3,2,1,0} "
            "custom-call(s32[16,64]{1,0} %copy-done.3), "
            "custom_call_target=\"tpu_custom_call\"")
    name, rest = trace.split_instruction(text)
    assert name == "paged_attention_v1.61"
    assert rest.startswith("bf16[16,25,16,64]")
    assert trace.split_instruction("jit_fused(123)") == ("jit_fused(123)", "")


def test_matching_reads_the_name_never_the_operands():
    events = [ev("fusion.9", 0, 4, "bf16[4] fusion(%paged_attention_v1.3)"),
              ev("paged_attention_v2.7", 5, 2), ev("fusion.1", 9, 1)]
    got = trace.matching(events, ("paged_attention_v",))
    assert [e.name for e in got] == ["paged_attention_v2.7"]
    assert trace.matching(events, ("flash_fwd",)) == []


def test_leaf_events_drop_parents():
    # a while loop that wraps two bodies, and one op beside it
    events = [ev("while.1", 0, 100), ev("fusion.1", 0, 40),
              ev("fusion.2", 50, 50), ev("copy.1", 120, 10)]
    leaves = trace.leaf_events(events)
    assert [e.name for e in leaves] == ["fusion.1", "fusion.2", "copy.1"]
    assert trace.union_ns(leaves) == 100


def test_time_by_name_strips_instruction_numbers():
    events = [ev("fusion.1", 0, 3), ev("fusion.22", 5, 4),
              ev("paged_attention_v1.3", 10, 9), ev("copy", 20, 1)]
    assert trace.time_by_name(events) == [
        ("paged_attention_v1", 9), ("fusion", 7), ("copy", 1)]


def test_module_executions_in_time_order():
    mods = [ev("jit_fused(123)", 100, 7), ev("jit_other(5)", 50, 1),
            ev("jit_fused(123)", 0, 9)]
    assert trace.module_executions(mods, "jit_fused") == [9, 7]
    assert trace.heaviest_module(mods) == "jit_fused"
    assert trace.heaviest_module([]) is None


def test_longest_gaps():
    events = [ev("a", 10, 10), ev("b", 40, 10), ev("c", 55, 5)]
    gaps = trace.longest_gaps(events, 0, 100, top=2)
    assert gaps == [(60, 40), (20, 20)]


def test_device_trace_busy_window_and_kernels():
    plane = {trace.OPS_LINE: [ev("fusion.1", 0, 50),
                              ev("flash_fwd.2", 60, 20),
                              ev("flash_dq.3", 80, 20)],
             trace.MODULES_LINE: [ev("jit_step(1)", 0, 100)]}
    dev = trace.DeviceTrace({"/device:TPU:0": plane})
    assert dev.window_s() == pytest.approx(100e-9)
    assert dev.busy_s() == pytest.approx(90e-9)
    assert dev.kernel_s(("flash_fwd", "flash_dq")) == pytest.approx(40e-9)
    assert dev.kernel_calls(("flash_dq",)) == 1
    assert dev.kernel_share_pct(("flash_fwd",)) == pytest.approx(100 * 20 / 90)
    assert dev.kernel_share_pct(("paged_attention_v",)) is None
    assert dev.heaviest_module_ms_p50() == pytest.approx(100e-6)
    top = dev.breakdown()["device_ops"]
    assert top[0][0] == "fusion" and len(top) <= 10
    assert len(dev.breakdown()["idle_gaps"]) <= 10


def test_busy_counts_parents_and_sums_count_leaves():
    plane = {trace.OPS_LINE: [ev("while.1", 0, 100), ev("fusion.1", 0, 40),
                              ev("flash_fwd.2", 50, 40)]}
    dev = trace.DeviceTrace({"/device:TPU:0": plane})
    assert dev.busy_s() == pytest.approx(100e-9)
    assert dev.kernel_share_pct(("flash_fwd",)) == pytest.approx(40.0)
    assert [n for n, _ in dev.breakdown()["device_ops"]] == \
        ["fusion", "flash_fwd"]


def test_a_kernel_that_holds_a_dma_done_event_still_counts():
    plane = {trace.OPS_LINE: [ev("flash_dq.1", 0, 100),
                              ev("copy-done.7", 40, 1),
                              ev("flash_dq.2", 100, 100)]}
    dev = trace.DeviceTrace({"/device:TPU:0": plane})
    assert dev.kernel_calls(("flash_dq",)) == 2
    assert dev.kernel_s(("flash_dq",)) == pytest.approx(200e-9)
    assert dev.kernel_share_pct(("flash_dq",)) == pytest.approx(100.0)


def test_device_trace_refuses_an_empty_trace():
    with pytest.raises(ValueError):
        trace.DeviceTrace({"/device:TPU:0": {trace.OPS_LINE: []}})


def test_result_itemsize_reads_the_type_a_kernel_returns():
    assert trace.result_itemsize(
        "(f32[256,512,64]{2,1,0:T(8,128)}, f32[256,512,64]{2,1,0}) "
        "custom-call(f32[256,512,64]{2,1,0} %bitcast.4363)") == 4
    assert trace.result_itemsize(
        "bf16[16,25,16,64]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call("
        "s32[16,64]{1,0} %copy-done.3)") == 2
    assert trace.result_itemsize("f8e4m3fn[8,128]{1,0} fusion()") == 1
    assert trace.result_itemsize("c64[8]{0} fft()") is None
    assert trace.result_itemsize("") is None
