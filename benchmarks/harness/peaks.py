"""Peaks of one chip, keyed by the exact ``device_kind`` JAX reports. A device
that is not in the table is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; the table has "
            f"{sorted(PEAKS)}. Add the kind with its published peaks and "
            "their source; there is no default.")
    return PEAKS[device_kind]
