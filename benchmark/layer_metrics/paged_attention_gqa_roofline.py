"""The paged-attention kernels' share of their roofline in the traced
window, for a model whose paged layers are GROUPED-QUERY and are some
of its layers: the least time the chip could take for the attention
the window needed, over the time the kernels took.

The work comes from the runner's own request log (`flops.lane_calls`)
and `flops_linear_moe.gqa_attention_work`, over the family's
`gqa_layers` layers: a token's K and V are read once a KV head, where
`paged_attention_roofline` counts a read a query head over every
layer."""

from benchmark import flops, flops_linear_moe

META = {"layer": "paged attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_attention_v",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None or "gqa_kv_heads" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    calls = flops.lane_calls(run.requests, f["chunk"], run.traced.t0,
                             run.traced.t1)
    ops, nbytes = flops_linear_moe.gqa_attention_work(
        calls, f["gqa_heads"], f["gqa_kv_heads"], f["gqa_head_dim"],
        f["kv_itemsize"])
    least, bound = flops.least_time_s(ops * f["gqa_layers"],
                                      nbytes * f["gqa_layers"],
                                      run.ctx.peaks)
    run.facts["paged_attention_gqa_bound"] = bound
    return 100.0 * least / kernel_s
