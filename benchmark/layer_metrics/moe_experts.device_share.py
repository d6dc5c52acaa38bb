"""Share of the device's busy time that the held experts' kernel takes:
summed device time of the operations named `moe_experts*` over the
union of all operations, in the traced window."""

META = {"layer": "expert layer", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("moe_experts",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
