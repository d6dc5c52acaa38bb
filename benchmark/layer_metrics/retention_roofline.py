"""Power retention's share of its roofline in the traced window: the
least time the chip could take for the state reads and updates the
window's steps needed, over the time the operations named
`power_retention*` took.

The work comes from the program's counts on its `serving.iteration`
spans (`retention_lane_calls`, the (lane, layer) pairs that read and
wrote a state; `retention_columns`, the valid columns x layers) and
`flops_retention.retention_chunk_work`. A state is 34 MB a lane a layer
against 16 columns of some 200 KB: the bound is memory, the states
themselves."""

from benchmark import flops, flops_retention

META = {"layer": "linear attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("power_retention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None or "retention_heads" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    counts = flops_retention.retention_counts(run.traced.spans)
    if kernel_s <= 0 or counts is None:
        return None
    calls, columns = counts
    ops, nbytes = flops_retention.retention_chunk_work(
        calls, columns, f["retention_heads"], f["retention_kv_heads"],
        f["retention_head_dim"], f["retention_state_rows"],
        f["retention_state_itemsize"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops, nbytes, run.ctx.peaks)
    run.facts["retention_bound"] = bound
    return 100.0 * least / kernel_s
