"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as jax reports it.  A kind that is not here is an error:
there is no default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s
and 1,600 Gbit/s of chip-to-chip interconnect.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            "benchmark/peaks.py; add the chip with its source") from None
