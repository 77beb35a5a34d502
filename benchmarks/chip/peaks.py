"""Published peaks of one chip, keyed by `jax.devices()[0].device_kind`.

A device that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on file for device_kind {device_kind!r}; add it to "
            "benchmarks/chip/peaks.py with its source")
    return PEAKS[device_kind]
