"""Share of the device's busy time that power retention takes: summed
device time of the operations named `power_retention*` over the union
of all operations, in the traced window."""

META = {"layer": "linear attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("power_retention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
