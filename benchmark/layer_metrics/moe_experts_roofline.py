"""The held experts' kernel's share of its roofline in the traced
window: the least time the chip could take for the expert products the
window's routing needed, over the time the kernel `moe_experts` took.

The work comes from the program's routing counts on its
`serving.iteration` spans (`moe_assignments_held`, the tokens the held
experts computed; `moe_experts_touched`, how many (layer, expert)
weights a step had to read) and `flops_latent_moe.held_experts_work`.
With half a token an expert a decode step, the bound is memory: the
touched experts' weights."""

from benchmark import flops, flops_latent_moe

META = {"layer": "expert layer", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("moe_experts",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    f = run.facts
    if dev is None or run.ctx.peaks is None or "expert_inner" not in f:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    counts = flops_latent_moe.routing_counts(run.traced.spans)
    if kernel_s <= 0 or counts is None:
        return None
    _all, held, touched = counts
    ops, nbytes = flops_latent_moe.held_experts_work(
        held, touched, f["expert_hidden"], f["expert_inner"],
        f["kv_itemsize"])
    least, bound = flops.least_time_s(ops, nbytes, run.ctx.peaks)
    run.facts["moe_experts_bound"] = bound
    return 100.0 * least / kernel_s
