"""The window walk's share of its roofline in the traced window: the
least time the chip could take for the window attention the window's
steps needed, over the time the kernel `paged_window_attention` took.

The work comes from the runner's own request log (`flops.lane_calls`)
and `flops_window_moe.window_attention_work`, over the family's
`window_layers` layers: a lane call reads the keys of its queries'
windows, min(context, window + columns - 1), K and V once a KV head.
With a window of 4,096 the bound is memory, the keys themselves."""

from benchmark import flops, flops_window_moe

META = {"layer": "paged attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_window_attention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None or "window_kv_heads" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    calls = flops.lane_calls(run.requests, f["chunk"], run.traced.t0,
                             run.traced.t1)
    ops, nbytes = flops_window_moe.window_attention_work(
        calls, f["sliding_window"], f["window_heads"],
        f["window_kv_heads"], f["window_head_dim"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops * f["window_layers"],
                                      nbytes * f["window_layers"],
                                      run.ctx.peaks)
    run.facts["window_attention_bound"] = bound
    return 100.0 * least / kernel_s
