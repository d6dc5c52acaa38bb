"""Share of the device's busy time that the window walk takes: summed
device time of the operations named `paged_window_attention*` over the
union of all operations, in the traced window. The full layers' walks
are `paged_attention.device_share`'s."""

META = {"layer": "paged attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_window_attention",)


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
