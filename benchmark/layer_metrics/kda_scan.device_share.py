"""Share of the device's busy time that the chunked delta rule takes:
summed device time of the operations named `kda_chunk*` over the union
of all operations, in the traced window."""

META = {"layer": "linear attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("kda_chunk",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
