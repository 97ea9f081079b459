"""Pallas TPU kernel: fully-connected forward with a reused bit-packed tile.

TPU adaptation of the paper's Triton inference kernel (Section 5.2). The
weight never exists densely: HBM holds one bit-packed tile
``packed (r, K/32) int32`` (r = n_out / p unique weight rows). Per grid step
the kernel pulls an (bm, bk) activation block and a (br, bk/32) packed block
into VMEM, unpacks the bits to ±1 in-register (shift/and on the VPU), and
feeds the MXU:

    u = x @ T^T          -- p-fold fewer FLOPs than the dense layer
    y = kron(alpha, u)   -- broadcast-scale applied by the wrapper (ops.py)

Weight HBM traffic is 32*p smaller than fp32 (p smaller than 1-bit BWNN).

Block shapes follow the Mosaic rule for the last two dims of every block
(a multiple of (8, 128), or the whole array dim): the packed-word axis is
blocked as the whole padded row when it has at most ``WORD_BLOCK`` words,
else in ``WORD_BLOCK``-word steps over a row padded to a multiple (ops.py
pads). The defaults (bm, br, bk) = (128, 128, 4096) keep the per-step
working set -- activation block, packed block and the (br, bk) unpack
temporaries -- inside the 16 MiB default scoped VMEM of a v5e core
(tests/test_tpu_compile.py compiles them at granite-8b widths).

Grid: (M/bm, r/br, K/bk), k innermost (sequential accumulation); m/r are
parallel. The f32 accumulator lives in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_BITS = 32
# Packed words per k step once a row outgrows one block: 128 words (4096
# weight bits) is one full lane tile, the smallest legal partial block.
WORD_BLOCK = 128


def _unpack_block(words: jax.Array, br: int, bk: int, dtype) -> jax.Array:
    """(br, bk/32) int32 words -> (br, bk) ±1 values of ``dtype``.

    Column c of the output reads bit (c % 32) of word (c // 32): broadcast
    each word over 32 lanes, shift by the lane's bit index, mask, map to ±1.
    """
    nw = bk // LANE_BITS
    u32 = words.astype(jnp.uint32)
    rep = jnp.broadcast_to(u32[:, :, None], (br, nw, LANE_BITS)).reshape(br, bk)
    shift = jax.lax.broadcasted_iota(jnp.uint32, (br, bk), 1) % LANE_BITS
    bits = (rep >> shift) & jnp.uint32(1)
    # a select, not ``bits * 2 - 1``: Mosaic has no integer multiply
    return jnp.where(bits == 1, 1.0, -1.0).astype(dtype)


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, compute_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = x_ref.shape
    br = w_ref.shape[0]
    w = _unpack_block(w_ref[...], br, bk, compute_dtype)
    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_matmul_unique(
    x: jax.Array,
    packed: jax.Array,
    *,
    r: int,
    block_m: int = 128,
    block_r: int = 128,
    block_k: int = WORD_BLOCK * LANE_BITS,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """u = x @ T^T for a row-packed tile.

    x: (M, K). packed: (r, K/32) int32 (row-major bit order, see
    repro.core.packing). Returns (M, r) in ``out_dtype``.

    Shapes must be pre-padded to block multiples (ops.py handles padding).
    """
    m, k = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert k % LANE_BITS == 0, "K must be a multiple of 32 (packed lanes)"
    assert packed.shape == (r, k // LANE_BITS), (packed.shape, (r, k // LANE_BITS))
    block_m = min(block_m, m)
    block_r = min(block_r, r)
    block_k = min(block_k, k)
    assert m % block_m == 0 and r % block_r == 0 and k % block_k == 0
    assert block_k % LANE_BITS == 0
    nk = k // block_k
    compute_dtype = x.dtype if x.dtype in (jnp.bfloat16, jnp.float32) else jnp.float32

    kernel = functools.partial(_matmul_kernel, nk=nk, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, r // block_r, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ri, ki: (mi, ki)),
            pl.BlockSpec(
                (block_r, block_k // LANE_BITS), lambda mi, ri, ki: (ri, ki)
            ),
        ],
        out_specs=pl.BlockSpec((block_m, block_r), lambda mi, ri, ki: (mi, ri)),
        out_shape=jax.ShapeDtypeStruct((m, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed)
