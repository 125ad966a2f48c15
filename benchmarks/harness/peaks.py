"""Published peaks, by `device_kind` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 394 TOP/s
int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
A device that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it, "
            "with its source, in a new table — there is no default"
        ) from None
