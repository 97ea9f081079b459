"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per-chip specifications):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. A device kind
missing from ``PEAKS`` is an error, never a default: a share taken
against another chip's peaks is a wrong number, not an approximate one.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float             # FLOP/s per chip
    ops_int8: float               # int8 op/s per chip
    hbm_bw: float                 # bytes/s per chip
    hbm_bytes: float              # bytes per chip


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, ops_int8=393e12,
                             hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; KeyError if unlisted."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
