"""The paged-attention kernels' share of their roofline in the traced
window: the least time the chip could take for the attention the window
needed, over the time the kernels took.

The work comes from the runner's own request log, not from the program
(`flops.lane_calls`); bytes and operations per call are
`benchmark/flops.py`'s. At these shapes (1 to 16 queries per lane
against hundreds of keys, bf16) the bound that applies is memory: about
one operation per byte against the chip's 240."""

from benchmark import flops

META = {"layer": "paged attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_attention_v",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    if dev is None or run.ctx.peaks is None:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    f = run.facts
    calls = flops.lane_calls(run.requests, f["chunk"], run.traced.t0,
                             run.traced.t1)
    ops, nbytes = flops.paged_attention_work(
        calls, f["num_heads"], f["head_dim"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops * f["num_layers"],
                                      nbytes * f["num_layers"],
                                      run.ctx.peaks)
    run.facts["paged_attention_bound"] = bound
    return 100.0 * least / kernel_s
