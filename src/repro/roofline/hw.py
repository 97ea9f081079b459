"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per-chip specifications):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect. A device kind missing from ``PEAKS`` is an
error, never a default: a roofline share taken against another chip's
peaks is a wrong number, not an approximate one.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float             # FLOP/s per chip
    ops_int8: float               # int8 op/s per chip — the integer
    # compute paths' MACs; XNOR word ops are charged at this rate too
    # after the 32-bits-per-word conversion in
    # roofline.analysis.integer_dense_ops
    hbm_bw: float                 # bytes/s per chip
    hbm_bytes: float              # bytes per chip
    ici_bw: float                 # bytes/s per link


V5E = "TPU v5 lite"               # jax's device_kind for a v5e chip

PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(
        flops_bf16=197e12,
        ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16e9,
        ici_bw=1600e9 / 8 / 4,    # 1,600 Gbit/s over the chip's 4 links
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; KeyError if unlisted."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add its row, with its source, "
            f"to repro/roofline/hw.py"
        ) from None
