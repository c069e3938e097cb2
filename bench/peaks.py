"""Peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A device kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; raises UnknownDevice."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak rates for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
