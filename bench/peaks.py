"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float       # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float  # HBM bandwidth of one chip
    hbm_bytes: float        # HBM capacity of one chip
    source: str


PEAKS: dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops_bf16=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM2 at 819 GB/s per chip",
    ),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
