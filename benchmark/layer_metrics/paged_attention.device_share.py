"""Share of the device's busy time that the paged-attention kernels
take: summed device time of the operations named `paged_attention_v*`
over the union of all operations, in the traced window."""

META = {"layer": "paged attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_attention_v",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
