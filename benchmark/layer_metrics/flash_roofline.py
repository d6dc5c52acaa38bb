"""The flash-attention kernels' share of their roofline in the traced
window: for every kernel call seen in the trace, the least time the
chip could take at the cell's (rows, heads, seq, head_dim), over the
time the calls took. Operations and bytes per call are
`benchmark/flops.py`'s; the bytes per element are those of the type the
kernel returns, read from its instruction in the trace. Today the
program runs attention in float32 (`amp.cast_model_to_bf16` leaves the
fused attention op alone): 4 bytes, which at seq 512 and head_dim 64
puts the forward call at 128 operations per byte, under the chip's 240,
so the memory bound applies to it by the published peaks."""

from benchmark import flops

META = {"layer": "flash attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "train_tokens_per_s"}

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(run):
    dev = run.traced.device if run.traced is not None else None
    if dev is None or run.ctx.peaks is None:
        return None
    f = run.facts
    least = took = 0.0
    bounds = {}
    for kernel in KERNELS:
        calls = dev.kernel_calls((kernel,))
        itemsize = dev.kernel_itemsize((kernel,))
        if not calls or itemsize is None:
            continue
        ops, nbytes = flops.flash_work(
            kernel, f["rows"], f["num_heads"], f["seq_len"],
            f["head_dim"], itemsize)
        t, bounds[kernel] = flops.least_time_s(ops, nbytes, run.ctx.peaks)
        least += t * calls
        took += dev.kernel_s((kernel,))
    run.facts["flash_bounds"] = str(bounds)
    run.facts["flash_itemsize"] = itemsize
    return 100.0 * least / took if took > 0 else None
