"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Every utilisation and roofline share the benchmark prints divides by a
number from this table.  A device that is not in it is an error, never
a default: a share of a guessed peak is worse than none.
"""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2e at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.  JAX
# reports that chip as device_kind "TPU v5 lite".
PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise LookupError(
            f"no peaks recorded for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS_BY_DEVICE_KIND)}); add it to "
            "benchmark/peaks.py with its source") from None
