"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
             hbm_bytes=16e9, source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None
