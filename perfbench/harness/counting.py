"""Operations and bytes that the tiled model needs, counted from shapes.

One function, ``tiled_linear``, counts a tiled layer; every roofline and
every MFU reading of the benchmark goes through it. A tiled layer's
kernel computes only the ``r = n_out / p`` unique rows and replicates
them with alpha outside the kernel, so a call of ``m`` rows needs
``2 * m * r * n_in`` operations, not ``2 * m * n_out * n_in``. Its bytes
are the packed tile, the alphas, the bf16 activations in and the f32
kernel output. Padding that a kernel adds to its blocks is not needed
work and is not counted.

Attention and the untiled parts count at their own shapes: ``4 * heads *
head_dim`` operations per (query, key) pair (scores and the weighted sum
of values); norms and the embedding lookup do no matrix work and are left
out.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from harness import weights

ACT_BYTES = 2          # bf16 activations
OUT_BYTES = 4          # the kernels' f32 output


def tiled_linear(m: int, r: int, n_in: int, p: int) -> Tuple[float, float]:
    """(operations, bytes) of one tiled-layer call on ``m`` rows."""
    flops = 2.0 * m * r * n_in
    nbytes = (r * -(-n_in // 32) * 4 + p * 4 + m * n_in * ACT_BYTES
              + m * r * OUT_BYTES)
    return flops, float(nbytes)


def layer_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(r, n_in) of every tiled linear of one layer."""
    return [(n_out // cfg["tbn_p"], n_in)
            for n_out, n_in, _ in weights.linears(cfg).values()]


def head_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["vocab_size"] // cfg["tbn_p"], cfg["hidden_size"]


def step_flops(cfg: dict, rows: int, key_pairs: int, logit_rows: int) -> float:
    """Operations one model step needs: ``rows`` token rows through every
    layer, ``key_pairs`` (query, key) pairs of causal attention summed
    over layers' queries, and the head on ``logit_rows`` rows."""
    p = cfg["tbn_p"]
    per_layer = sum(tiled_linear(rows, r, n_in, p)[0]
                    for r, n_in in layer_shapes(cfg))
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * key_pairs
    r, n_in = head_shape(cfg)
    return (cfg["num_hidden_layers"] * (per_layer + attn)
            + tiled_linear(logit_rows, r, n_in, p)[0])


def match_linear(cfg: dict, r_pad: int, k_pad: int) -> Tuple[int, int]:
    """The configuration's (r, n_in) that a kernel call on a padded
    ``(r_pad, k_pad)`` tile serves: the closest one that fits."""
    shapes: Dict[Tuple[int, int], None] = dict.fromkeys(
        layer_shapes(cfg) + [head_shape(cfg)])
    fits = [(r_pad - r + k_pad - k, (r, k)) for r, k in shapes
            if r <= r_pad and k <= k_pad]
    if not fits:
        raise ValueError(f"no tiled layer fits a ({r_pad}, {k_pad}) call")
    return min(fits)[1]
