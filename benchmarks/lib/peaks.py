"""The ONE table of device peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect per chip. A device that is not in the table is
an error, never a default: a share of an unknown peak means nothing.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float | str]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud docs, 'TPU v5e' (v5litepod), per chip",
    },
}


def peaks_for(device_kind: str) -> dict[str, float | str]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a sourced "
            "row to benchmarks/lib/peaks.py (known: " + ", ".join(sorted(PEAKS)) + ")"
        ) from None
