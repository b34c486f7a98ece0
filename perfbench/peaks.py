"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, not a
default."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to perfbench/peaks.py with its source"
        ) from None
