"""Published peaks of one chip, keyed by the `device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (System architecture): 197
TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s per chip. JAX names the chip
"TPU v5 lite". A device that is not in the table is an error, not a default.
The bf16 peak is the one used although the cells' operands are float32: the
MXU has no faster float32 mode, so bf16 is the ceiling any precision can
reach, and a float32 program reads low against it by design.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def lookup(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a row with its source to harness/peaks.py"
        )
