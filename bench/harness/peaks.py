"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device kind missing here is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # JAX's device_kind of a v5e chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12},
}


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    """The peaks of one chip of `device_kind`; raises `UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to bench/harness/peaks.py with their source") from None
