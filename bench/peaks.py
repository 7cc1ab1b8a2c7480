"""Peak rates of each accelerator the benchmark may run on, by ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share or an MFU is only meaningful against the chip it ran on.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add its published peaks to bench/peaks.py"
        ) from None
