"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, and 1,600
Gbit/s of inter-chip interconnect (ICI). A device that is not in the
table is an error: a roofline share against a guessed peak is no
measurement.
"""
from __future__ import annotations

SOURCE = ('Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
          '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip')

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "bf16_flops_per_s": 197e12,
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_of(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises
    :class:`UnknownDevice` for a device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
