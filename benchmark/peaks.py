"""Published peaks of the chips the benchmark runs on, keyed by
`jax.devices()[0].device_kind`. One table; an unknown kind is an error,
never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row with its source to benchmark/peaks.py") from None
