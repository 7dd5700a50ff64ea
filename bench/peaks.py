"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``. A kind that is not in the table is an error,
never a default: a roofline or utilization against a guessed peak is not a
measurement.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB
of HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} has no entry in bench/peaks.py; "
            f"known: {sorted(PEAKS)}") from None
