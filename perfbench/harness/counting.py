"""Operations and bytes of a tiled layer, counted from shapes.

One function, ``tiled_linear``, counts a tiled layer call; every family's
step count and kernel count (``perfbench/families/<family>.py``), and so
every roofline and every MFU reading of the benchmark, goes through it. A
tiled layer's kernel computes only the ``r = n_out / p`` unique rows and
replicates them with alpha outside the kernel, so a call of ``m`` rows
needs ``2 * m * r * n_in`` operations, not ``2 * m * n_out * n_in``. Its
bytes are the packed tile, the alphas, the bf16 activations in and the
f32 kernel output. Padding that a kernel adds to its blocks is not needed
work and is not counted.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

ACT_BYTES = 2          # bf16 activations
OUT_BYTES = 4          # the kernels' f32 output


def tiled_linear(m: int, r: int, n_in: int, p: int) -> Tuple[float, float]:
    """(operations, bytes) of one tiled-layer call on ``m`` rows."""
    flops = 2.0 * m * r * n_in
    nbytes = (r * -(-n_in // 32) * 4 + p * 4 + m * n_in * ACT_BYTES
              + m * r * OUT_BYTES)
    return flops, float(nbytes)


def closest(shapes: Iterable[Tuple[int, int]], r_pad: int,
            k_pad: int) -> Tuple[int, int]:
    """The (r, n_in) among a configuration's tiled layers that a kernel
    call on a padded ``(r_pad, k_pad)`` tile serves: the closest one that
    fits."""
    unique: Dict[Tuple[int, int], None] = dict.fromkeys(shapes)
    fits = [(r_pad - r + k_pad - k, (r, k)) for r, k in unique
            if r <= r_pad and k <= k_pad]
    if not fits:
        raise ValueError(f"no tiled layer fits a ({r_pad}, {k_pad}) call")
    return min(fits)[1]
