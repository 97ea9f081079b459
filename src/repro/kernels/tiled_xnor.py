"""Pallas TPU kernels: integer-domain decode matvec on packed tile words.

The float kernels (tiled_matmul / tiled_matvec) ship sub-bit *weights*
but still unpack every packed word to ±1 floats and burn MXU float MACs
— at decode sizes the matvec kernel is unpack-bound, not MXU-bound. The
BNN lineage ("Bitwise Neural Networks", Kim & Smaragdis 2016; XNOR-Net)
gets its speed by never leaving the integer domain: quantize the
activations too and accumulate directly against the packed
``(r, ceil(n_in/32))`` tile words. Two compute paths live here, both
decode-oriented (m <= MATVEC_MAX_M after flattening lead dims — the
``(n_slots, 1)`` tick batch):

* ``xnor`` — sign-binarize activations, bit-pack them with the SAME
  little-endian word layout as the weights (repro.core.packing), and
  compute the integer dot product per output as

      acc[i, j] = n_in - 2 * sum_w popcount(xq[i, w] XOR wq[j, w])

  No unpack, no MAC of any kind: each packed word contributes one
  32-lane XOR + one SWAR popcount on the VPU. Padding needs no masks —
  pad bits of BOTH operands pack to 0, so their XOR is 0 and popcount
  ignores them (disagreements can only occur in valid bits).

* ``int8`` — the accuracy-preserving middle step: per-row symmetric
  int8 activations against {0, 1} weight bits through the MXU's integer
  ``dot_general`` (preferred_element_type=int32), folded to the ±1 dot
  with ``acc = 2 * (q @ bits^T) - rowsum(q)``. The weight words are
  expanded to a 0/1 *select mask* (shift/and, one byte per bit) — never
  to ±1 floats — and every MAC is int8 x int8 -> int32.

Both kernels return the raw int32 accumulator; the wrapper (ops.py)
applies the activation scale ``u = scale * acc`` and the usual alpha
replica broadcast. The accumulators are BIT-IDENTICAL to the pure-JAX
oracles (``kernels.ref.tiled_xnor_matvec_ref`` — which uses
``jax.lax.population_count``, an implementation independent of the SWAR
popcount here — and ``tiled_int8_matvec_ref``), so the parity wall
asserts exact integer equality, not allclose.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import pack_bits
from repro.kernels.tiled_matmul import LANE_BITS, WORD_BLOCK
from repro.kernels.tiled_matvec import sublane_rounded

# Dispatchable compute paths for the tiled dense serve apply. "float" is
# the byte-parity reference (the existing unpack + MXU float kernels);
# the integer paths engage only at decode m (ops.py falls back to float
# for prefill-sized batches).
COMPUTE_PATHS = ("float", "int8", "xnor")

# Decode blocking. The xnor kernel blocks over packed WORDS (one word = 32
# weight bits) on the lane axis of the activation block, so a partial row
# steps in WORD_BLOCK words (the Mosaic minimum, tiled_matmul.py) — the
# same k footprint as the float matvec's DECODE_BLOCK_K.
XNOR_BLOCK_R = 256
XNOR_BLOCK_W = WORD_BLOCK
INT8_BLOCK_R = 128
INT8_BLOCK_K = WORD_BLOCK * LANE_BITS


# --------------------------------------------------------------------------
# Activation quantization (pure jnp — shared by wrapper, oracle and tests)
# --------------------------------------------------------------------------
def quantize_sign(x: jax.Array, n_in: int) -> Tuple[jax.Array, jax.Array]:
    """Sign-binarize activation rows for the pure-XNOR path.

    x: (m, k >= n_in) — columns past n_in are ignored. Returns
    (packed (m, ceil(n_in/32)) int32, scale (m, 1) f32) where
    ``scale = mean|x_row|`` (XNOR-Net's per-row activation scale) and
    bit j of word w encodes ``sign(x[:, w*32+j]) > 0`` in the same
    little-endian layout as the weight tiles, pad bits 0.
    """
    xv = x[:, :n_in].astype(jnp.float32)
    scale = jnp.mean(jnp.abs(xv), axis=1, keepdims=True)
    return pack_bits(xv > 0), scale


def quantize_int8(x: jax.Array, n_in: int) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization (the accuracy-preserving step).

    x: (m, k >= n_in). Returns (q (m, n_in) int8 in [-127, 127],
    scale (m, 1) f32) with ``x ~= q * scale``; an all-zero row gets
    scale 1 so the dequant stays finite.
    """
    xv = x[:, :n_in].astype(jnp.float32)
    amax = jnp.max(jnp.abs(xv), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xv / scale), -127, 127).astype(jnp.int8)
    return q, scale


def popcount32(v: jax.Array) -> jax.Array:
    """SWAR popcount of each int32/uint32 lane -> int32 counts.

    Shift/and/add only (no multiply, no lookup) so it lowers to plain
    VPU vector ops inside a Pallas kernel; the oracle deliberately uses
    ``jax.lax.population_count`` instead so the two implementations
    check each other.
    """
    v = v.astype(jnp.uint32)
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    v = (v + (v >> 8) + (v >> 16) + (v >> 24)) & jnp.uint32(0x3F)
    return v.astype(jnp.int32)


# --------------------------------------------------------------------------
# XNOR + popcount kernel (packed words x packed words)
# --------------------------------------------------------------------------
def _xnor_kernel(x_ref, w_ref, o_ref, acc_ref, *, nw_steps: int, n_in: int):
    """One (r block, word block) step: acc += popcount(x XOR w) per word.

    x_ref (bm, bw) int32 packed activation words; w_ref (bw, br) int32
    packed weight words TRANSPOSED so each word index is a row — the
    (bm, 1) x (1, br) XOR broadcast stays 2D for the VPU. The word loop
    is a static unroll (bw is a compile-time block size).
    """
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bw = x_ref.shape[1]
    pop = acc_ref[...]
    for j in range(bw):
        xw = x_ref[:, j : j + 1]            # (bm, 1) int32
        ww = w_ref[j : j + 1, :]            # (1, br) int32
        pop += popcount32(jnp.bitwise_xor(xw, ww))
    acc_ref[...] = pop

    @pl.when(ki == nw_steps - 1)
    def _store():
        # integer ±1 dot: matches = n - pop, acc = matches - pop
        o_ref[...] = jnp.int32(n_in) - 2 * acc_ref[...]


def tiled_xnor_matvec_unique(
    packed_x: jax.Array,
    packed_rows: jax.Array,
    *,
    n_in: int,
    block_r: int = XNOR_BLOCK_R,
    block_w: int = XNOR_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """acc = sign(x) . T^T in the INTEGER domain, from packed words only.

    packed_x: (M, W) int32 sign-packed activations (quantize_sign);
    packed_rows: (r, W) int32 row-packed tile. Both pre-padded: M to the
    int32 sublane multiple, W to block_w multiples, r to block_r
    multiples — pad words are 0 on both sides so they cannot contribute
    (XOR of equal pad bits is 0). Returns (M, r) int32, the exact ±1 dot
    over the first n_in bit positions.
    """
    m, w_words = packed_x.shape
    r = packed_rows.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert packed_rows.shape[1] == w_words, (packed_rows.shape, w_words)
    block_r = min(block_r, r)
    block_w = min(block_w, w_words)
    assert r % block_r == 0 and w_words % block_w == 0
    nw_steps = w_words // block_w
    # word-index-major layout so the kernel's per-word broadcast is 2D
    wq_t = packed_rows.T  # (W, r)

    kernel = functools.partial(_xnor_kernel, nw_steps=nw_steps, n_in=n_in)
    return pl.pallas_call(
        kernel,
        grid=(r // block_r, nw_steps),
        in_specs=[
            pl.BlockSpec((m, block_w), lambda ri, ki: (0, ki)),
            pl.BlockSpec((block_w, block_r), lambda ri, ki: (ki, ri)),
        ],
        out_specs=pl.BlockSpec((m, block_r), lambda ri, ki: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((m, r), jnp.int32),
        scratch_shapes=[pltpu.VMEM((m, block_r), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(packed_x, wq_t)


# --------------------------------------------------------------------------
# int8 x binary kernel (integer MXU dot against a 0/1 select mask)
# --------------------------------------------------------------------------
def _unpack_bits01(words: jax.Array, br: int, bk: int) -> jax.Array:
    """(br, bk/32) int32 words -> (br, bk) {0, 1} int8 select mask.

    Same shift/and expansion as the float kernels' ``_unpack_block`` but
    the bits stay a 0/1 integer mask — the ±1 map happens arithmetically
    in the accumulator fold, never as a float."""
    nw = bk // LANE_BITS
    u32 = words.astype(jnp.uint32)
    rep = jnp.broadcast_to(u32[:, :, None], (br, nw, LANE_BITS)).reshape(br, bk)
    shift = jax.lax.broadcasted_iota(jnp.uint32, (br, bk), 1) % LANE_BITS
    return ((rep >> shift) & jnp.uint32(1)).astype(jnp.int8)


def _int8_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = x_ref.shape
    br = w_ref.shape[0]
    bits = _unpack_bits01(w_ref[...], br, bk)
    q = x_ref[...]
    # s1 = q @ bits^T over the +1 positions; the ±1 dot is 2*s1 - sum(q)
    # (pad columns hold q = 0, so both terms ignore them)
    s1 = jax.lax.dot_general(
        q, bits, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )
    rowsum = jnp.sum(q.astype(jnp.int32), axis=1, keepdims=True)
    acc_ref[...] += 2 * s1 - rowsum

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...]


def tiled_int8_matvec_unique(
    q: jax.Array,
    packed_rows: jax.Array,
    *,
    r: int,
    block_r: int = INT8_BLOCK_R,
    block_k: int = INT8_BLOCK_K,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """acc = q . T^T with int8 activations and binary weights, int32 MACs.

    q: (M, K) int8, M pre-padded to the int8 sublane multiple (32) and K
    to block_k multiples with ZERO pad columns; packed_rows:
    (r, K/32) int32. Returns (M, r) int32 — the exact integer dot of q
    against the ±1 rows.
    """
    m, k = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert q.dtype == jnp.int8, q.dtype
    assert k % LANE_BITS == 0, "K must be a multiple of 32 (packed lanes)"
    assert packed_rows.shape == (r, k // LANE_BITS), (
        packed_rows.shape, (r, k // LANE_BITS))
    block_r = min(block_r, r)
    block_k = min(block_k, k)
    assert r % block_r == 0 and k % block_k == 0
    assert block_k % LANE_BITS == 0
    nk = k // block_k

    kernel = functools.partial(_int8_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(r // block_r, nk),
        in_specs=[
            pl.BlockSpec((m, block_k), lambda ri, ki: (0, ki)),
            pl.BlockSpec(
                (block_r, block_k // LANE_BITS), lambda ri, ki: (ri, ki)
            ),
        ],
        out_specs=pl.BlockSpec((m, block_r), lambda ri, ki: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((m, r), jnp.int32),
        scratch_shapes=[pltpu.VMEM((m, block_r), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, packed_rows)


# --------------------------------------------------------------------------
# Structured (pure-jnp) integer paths — the non-Pallas backends
# --------------------------------------------------------------------------
def xnor_matvec_words(
    packed_x: jax.Array, packed_rows: jax.Array, *, n_in: int
) -> jax.Array:
    """Pure-jnp twin of the xnor kernel (SWAR popcount, same word math).

    This is what ``ops.tiled_dense_infer`` runs with use_pallas=False —
    CPU/GPU serving stays in the packed-word domain too (32x fewer loads
    than the unpack + float einsum reference). Bit-identical to the
    kernel AND to the independent ``ref.tiled_xnor_matvec_ref`` oracle.
    """
    xo = jnp.bitwise_xor(packed_x[:, None, :], packed_rows[None, :, :])
    return jnp.int32(n_in) - 2 * popcount32(xo).sum(axis=-1)


def int8_matvec_packed(
    q: jax.Array, packed_rows: jax.Array, *, n_in: int
) -> jax.Array:
    """Pure-jnp twin of the int8 kernel: 0/1 mask + integer dot."""
    words = packed_rows.shape[1]
    r = packed_rows.shape[0]
    bits = _unpack_bits01(packed_rows, r, words * LANE_BITS)[:, :n_in]
    s1 = jax.lax.dot_general(
        q[:, :n_in], bits, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return 2 * s1 - jnp.sum(q[:, :n_in].astype(jnp.int32), axis=1,
                            keepdims=True)


def int_sublane_dtype(compute_path: str):
    """The activation dtype whose sublane rule pads m for each path."""
    return jnp.int32 if compute_path == "xnor" else jnp.int8


__all__ = [
    "COMPUTE_PATHS",
    "XNOR_BLOCK_R",
    "XNOR_BLOCK_W",
    "INT8_BLOCK_R",
    "INT8_BLOCK_K",
    "quantize_sign",
    "quantize_int8",
    "popcount32",
    "tiled_xnor_matvec_unique",
    "tiled_int8_matvec_unique",
    "xnor_matvec_words",
    "int8_matvec_packed",
    "int_sublane_dtype",
    "sublane_rounded",
]
