"""Share of the device's busy time that the flash-attention kernels take
(`flash_fwd`, `flash_dq`, `flash_dkv`, any suffix), in the traced
window."""

META = {"layer": "flash attention", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "train_tokens_per_s"}

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(run):
    dev = run.traced.device if run.traced is not None else None
    return dev.kernel_share_pct(KERNELS) if dev is not None else None
