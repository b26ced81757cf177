"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A device that is not in the table is
an error: a roofline share against a guessed peak is no measurement.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "int8_ops_s": 393e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation, per-chip peaks",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
