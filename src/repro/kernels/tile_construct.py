"""Pallas TPU kernel: fused training-time tile construction.

Training forward needs (t, alpha) from the master weight every step. The
naive path materializes the binarized full tensor B_hat (N elements) in HBM;
this kernel fuses reshape -> column-sum over the p replicas -> sign ->
bit-pack (+ per-tile |.|_1 for alpha) in one pass over W, so only q bits +
p floats ever leave the core. Beyond-paper training-memory optimization
(DESIGN.md §2).

Layout: the wrapper passes W already reshaped (p, q). Grid over q blocks;
each step loads a (p, bq) strip of W (and optionally of the alpha source A),
reduces over the replica axis, packs bq/32 int32 words, and accumulates the
per-tile |.|_1 partial sums into a (1, p) accumulator output.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE_BITS = 32


def _construct_kernel(w_ref, a_ref, packed_ref, alpha_ref, *, bq: int):
    qi = pl.program_id(0)

    w = w_ref[...]  # (p, bq)
    s = jnp.sum(w.astype(jnp.float32), axis=0)  # (bq,)
    bits = (s > 0).astype(jnp.uint32)
    words = bits.reshape(bq // LANE_BITS, LANE_BITS)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, words.shape, 1)
    packed = jnp.sum(words << shifts, axis=1, dtype=jnp.uint32)
    packed_ref[0, :] = packed.astype(jnp.int32)

    @pl.when(qi == 0)
    def _init():
        alpha_ref[...] = jnp.zeros_like(alpha_ref)

    partial_l1 = jnp.sum(jnp.abs(a_ref[...].astype(jnp.float32)), axis=1)  # (p,)
    alpha_ref[0, :] += partial_l1


def tile_construct_pallas(
    w2d: jax.Array,
    a2d: Optional[jax.Array] = None,
    *,
    block_q: int = 4096,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(p, q) -> (packed int32 (q/32,), per-tile alpha (p,)).

    ``a2d`` is the alpha source strip (defaults to ``w2d`` — Eq. 7 family);
    q must be a multiple of 32 and of block_q (wrapper pads).
    """
    p, q = w2d.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, q)
    assert q % LANE_BITS == 0 and q % block_q == 0 and block_q % LANE_BITS == 0
    if a2d is None:
        a2d = w2d

    kernel = functools.partial(_construct_kernel, bq=block_q)
    packed, alpha_acc = pl.pallas_call(
        kernel,
        grid=(q // block_q,),
        in_specs=[
            pl.BlockSpec((p, block_q), lambda qi: (0, qi)),
            pl.BlockSpec((p, block_q), lambda qi: (0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q // LANE_BITS), lambda qi: (0, qi)),
            pl.BlockSpec((1, p), lambda qi: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, q // LANE_BITS), jnp.int32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(w2d, a2d)
    return packed[0], alpha_acc[0] / q
