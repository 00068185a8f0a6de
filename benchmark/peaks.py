"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip. A device
that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            "no published peaks for device kind {!r}; add it to benchmark/peaks.py "
            "with its source".format(device_kind)
        )
    return PEAKS[device_kind]
