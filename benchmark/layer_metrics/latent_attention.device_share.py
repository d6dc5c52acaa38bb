"""Share of the device's busy time that the latent paged-attention
kernel takes: summed device time of the operations named
`paged_latent_attention*` over the union of all operations, in the
traced window."""

META = {"layer": "paged attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_latent_attention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
