"""The chunked delta rule's share of its roofline in the traced window:
the least time the chip could take for the state updates the window's
steps needed, over the time the kernel `kda_chunk` took.

The work comes from the program's counts on its `serving.iteration`
spans (`kda_lane_calls`, the (lane, layer) pairs that read and wrote a
state; `kda_columns`, the valid columns x layers) and
`flops_linear_moe.kda_chunk_work`. A state is 4.2 MB a lane a layer
against 16 columns of some 80 KB: the bound is memory, the states
themselves."""

from benchmark import flops, flops_linear_moe

META = {"layer": "linear attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("kda_chunk",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None or "kda_heads" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    counts = flops_linear_moe.state_counts(run.traced.spans)
    if kernel_s <= 0 or counts is None:
        return None
    calls, columns = counts
    ops, nbytes = flops_linear_moe.kda_chunk_work(
        calls, columns, f["kda_heads"], f["kda_key_dim"],
        f["kda_value_dim"], f["kda_state_itemsize"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops, nbytes, run.ctx.peaks)
    run.facts["kda_scan_bound"] = bound
    return 100.0 * least / kernel_s
