"""The latent paged-attention kernel's share of its roofline in the
traced window: the least time the chip could take for the attention the
window needed, over the time the kernel took.

The work comes from the runner's own request log (`flops.lane_calls`);
operations and bytes per call are `flops_latent_moe.py`'s: a token's
one cached row is read once for all heads, so a chunk of 16 queries x
32 heads against it is hundreds of operations a byte, and which bound
applies depends on the mix of chunks and decode tokens."""

from benchmark import flops, flops_latent_moe

META = {"layer": "paged attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_latent_attention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None \
            or "latent_row_values" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    calls = flops.lane_calls(run.requests, f["chunk"], run.traced.t0,
                             run.traced.t1)
    ops, nbytes = flops_latent_moe.latent_attention_work(
        calls, f["num_heads"], f["latent_row_values"],
        f["latent_value_width"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops * f["num_layers"],
                                      nbytes * f["num_layers"],
                                      run.ctx.peaks)
    run.facts["latent_attention_bound"] = bound
    return 100.0 * least / kernel_s
