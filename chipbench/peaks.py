"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a roofline share against a guessed peak is not
a measurement.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600
Gbit/s of chip-to-chip interconnect, per chip.
"""

from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": SOURCE,
}

#: device_kind (as ``jax.devices()[0].device_kind`` reports it) → peaks
PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peak table of one chip of ``device_kind``; raises
    ``KeyError`` for a device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; the table holds "
                       f"{sorted(PEAKS)}") from None


def ops_peak(device_kind: str, bits: int) -> float:
    """Peak operations per second of one chip for operands of ``bits``
    bits: the int8 peak up to 8 bits.  Both families are quantized to at
    most 8 bits; a wider one would add its peak here."""
    if bits > 8:
        raise ValueError(f"no peak held for {bits}-bit operands")
    return peaks(device_kind)["int8_ops_per_s"]
