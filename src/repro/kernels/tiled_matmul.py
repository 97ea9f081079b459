"""Pallas TPU kernel: fully-connected forward with a reused bit-packed tile.

TPU adaptation of the paper's Triton inference kernel (Section 5.2). The
weight never exists densely: HBM holds one bit-packed tile
``packed (r, K/32) int32`` (r = n_out / p unique weight rows). Per grid step
the kernel pulls an (bm, bk) activation block and a (br, bk/32) packed block
into VMEM, unpacks the bits to ±1 in-register (shift/compare/select on the
VPU), and feeds the MXU:

    u = x @ T^T          -- p-fold fewer FLOPs than the dense layer
    y = kron(alpha, u)   -- broadcast-scale applied by the wrapper (ops.py)

Weight HBM traffic is 32*p smaller than fp32 (p smaller than 1-bit BWNN).

Unpack order. Word j of a block row holds the weights of block columns
32j..32j+31, bit b for column 32j + b. Where the block's word axis fills
whole lane tiles (``nw = bk/32`` a multiple of ``WORD_BLOCK``), the kernel
unpacks it plane by plane: plane b is bit b of every word, a (br, nw) ±1
array made by one shift, compare and select over the words as they lie,
so no value moves across lanes. Plane b holds the weights of columns
32j + b, so the wrapper hands the kernel the activations with each k
block's columns in the same planar order (``planar_columns``: column
b·nw + j of a block is input column 32j + b) and the block's product is
the sum over b of the plane's dot with its nw activation columns. A
narrower whole-row block (nw < WORD_BLOCK, or not a multiple) keeps the
interleaved unpack ``_unpack_block``: each word broadcast over 32 lanes,
a relayout per block, cheap only because such rows are short. The conv
and int8 kernels unpack interleaved too.

Block shapes follow the Mosaic rule for the last two dims of every block
(a multiple of (8, 128), or the whole array dim): the packed-word axis is
blocked as the whole padded row when it has at most ``WORD_BLOCK`` words,
else in ``WORD_BLOCK``-word steps over a row padded to a multiple (ops.py
pads). The defaults (bm, br, bk) = (128, 128, 4096) keep the per-step
working set -- activation block, packed block and the unpack temporaries
-- inside the 16 MiB default scoped VMEM of a v5e core
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


def is_planar(block_k: int) -> bool:
    """Whether a k block of ``block_k`` bits unpacks plane by plane: its
    word axis must fill whole lane tiles."""
    return (block_k // LANE_BITS) % WORD_BLOCK == 0


def planar_columns(x: jax.Array, block_k: int) -> jax.Array:
    """(m, K) -> (m, K) with each ``block_k`` block's columns in planar
    order: column b·nw + j of a block is its input column 32j + b."""
    m, k = x.shape
    nw = block_k // LANE_BITS
    return x.reshape(m, k // block_k, nw, LANE_BITS).swapaxes(-1, -2) \
        .reshape(m, k)


def _plane(words: jax.Array, b: int, dtype) -> jax.Array:
    """Bit ``b`` of every word as ±1 of ``dtype``: shifted up to the sign
    bit, so the compare reads it in place."""
    return jnp.where(words << (LANE_BITS - 1 - b) < 0, 1.0, -1.0).astype(dtype)


def _block_dot(x_ref, w_ref, dtype) -> jax.Array:
    """x_blk @ W_blk^T (f32) for an activation block (bm, bk) and its
    packed weight block (br, bk/32); planar activations where
    ``is_planar(bk)`` (module docstring)."""
    bk = x_ref.shape[1]
    br, nw = w_ref.shape
    nt = (((1,), (1,)), ((), ()))
    if not is_planar(bk):
        w = _unpack_block(w_ref[...], br, bk, dtype)
        return jax.lax.dot_general(x_ref[...].astype(dtype), w, nt,
                                   preferred_element_type=jnp.float32)
    words = w_ref[...]
    acc = None
    for b in range(LANE_BITS):
        part = jax.lax.dot_general(
            x_ref[:, b * nw:(b + 1) * nw].astype(dtype),
            _plane(words, b, dtype), nt, preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, compute_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_dot(x_ref, w_ref, compute_dtype)

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

    x: (M, K) in natural column order. packed: (r, K/32) int32
    (row-major bit order, see repro.core.packing). Returns (M, r) in
    ``out_dtype``.

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

    if is_planar(block_k):
        x = planar_columns(x, block_k)

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
