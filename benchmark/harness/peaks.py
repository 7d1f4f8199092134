"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.
A device that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def of(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add a row with its source to benchmark/harness/"
                       "peaks.py")
    return PEAKS[device_kind]
