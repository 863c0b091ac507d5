"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a utilization against a made-up peak is not a number.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
HBM2e at 819 GB/s per chip, 1,600 Gbit/s of inter-chip interconnect.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, TPU v5e: 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


class UnknownDevice(LookupError):
    """The device_kind has no published peak in this table."""


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} has no entry in "
            f"benchmark/lib/peaks.py (known: {sorted(PEAKS)}); add its "
            f"published peaks with their source before measuring on it"
        ) from None
