"""Pallas TPU kernel: decode-time tiled mat*vec* with a reused packed tile.

Small-m specialization of ``tiled_matmul_unique``. A continuous-batching
decode tick is an ``(n_slots, 1)`` batch — at the matmul kernel's default
``block_m=128`` the activation block is ~97% zero padding for the default
4 slots, and every MXU pass wastes the m dimension on rows that do not
exist. Here the whole sublane-rounded batch IS the m block (no m grid
axis, no m padding beyond the hardware sublane).

Grid: (r/br, K/bk), k innermost (sequential accumulation), r parallel.
The unpack is the matmul kernel's (``tiled_matmul._block_dot``): plane by
plane, with the activations' columns in planar order (``planar_columns``),
where the k block's words fill whole lane tiles — every call of a
granite-8b decode tick, since bk = 4096 bits is 128 words — and the
interleaved unpack for a narrower whole-row block. VMEM per step: m·bk
activations + br·bk/32 packed words + m·br f32 accumulator + the unpack
temporaries (br·bk/32 per plane, br·bk interleaved) — at the decode
defaults (m<=32, br=128, bk=4096) inside the 16 MiB default scoped VMEM
of a v5e core.

Dispatch lives in ``ops.tiled_dense_infer``: any matmul with
m <= MATVEC_MAX_M (after flattening lead dims; per-shard m under the
tensor-parallel shard_map wrapper) routes here instead of the matmul
kernel. Oracle: ``kernels.ref.tiled_matvec_unique_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiled_matmul import (
    LANE_BITS,
    WORD_BLOCK,
    _block_dot,
    is_planar,
    planar_columns,
)

# Dispatch threshold: batches at or under this m take the decode path.
# 32 covers any realistic slot count while staying well inside the regime
# where the matmul kernel's 128-row m blocks are mostly padding.
MATVEC_MAX_M = 32
# Decode blocking: one WORD_BLOCK of packed words (the Mosaic minimum
# for a partial row, tiled_matmul.py) per k step. br=256 at that bk
# overran the 16 MiB default scoped VMEM with the interleaved unpack's
# (br, bk) temporaries, so r blocks like the matmul kernel's.
DECODE_BLOCK_R = 128
DECODE_BLOCK_K = WORD_BLOCK * LANE_BITS


# Smallest second-to-last dim a TPU tile supports, per dtype: 4-byte
# dtypes tile at (8, 128), 2-byte at (16, 128), 1-byte at (32, 128).
# An EXPLICIT table — the old `8 if f32 else 16` silently mis-rounded
# int8 (which needs 32 sublanes) and any other non-f32 dtype.
_SUBLANE_MULT = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16, "int16": 16, "uint16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}


def sublane_rounded(m: int, dtype) -> int:
    """Round a decode batch up to the dtype's TPU sublane multiple.

    Raises a loud ValueError for dtypes without an entry rather than
    guessing — a wrong sublane multiple produces a mis-shaped m block
    that Mosaic rejects (or worse, pads wastefully) far from here.
    """
    name = jnp.dtype(dtype).name
    mult = _SUBLANE_MULT.get(name)
    if mult is None:
        raise ValueError(
            f"no TPU sublane rule for dtype {name!r} — known dtypes: "
            f"{sorted(_SUBLANE_MULT)}. Add an explicit entry to "
            f"_SUBLANE_MULT (tiled_matvec.py) for the new dtype's tile "
            f"shape instead of letting callers guess."
        )
    return -(-m // mult) * mult


def _matvec_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, compute_dtype):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_dot(x_ref, w_ref, compute_dtype)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_matvec_unique(
    x: jax.Array,
    packed: jax.Array,
    *,
    r: int,
    block_r: int = DECODE_BLOCK_R,
    block_k: int = DECODE_BLOCK_K,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """u = x @ T^T for a row-packed tile at decode-sized m.

    x: (M, K) in natural column order, M sublane-rounded (ops.py pads);
    packed: (r, K/32) int32. Returns (M, r) in ``out_dtype``. M is one
    block — there is no m grid axis; shapes must be pre-padded to block
    multiples on r/K.
    """
    m, k = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert k % LANE_BITS == 0, "K must be a multiple of 32 (packed lanes)"
    assert packed.shape == (r, k // LANE_BITS), (packed.shape, (r, k // LANE_BITS))
    block_r = min(block_r, r)
    block_k = min(block_k, k)
    assert r % block_r == 0 and k % block_k == 0
    assert block_k % LANE_BITS == 0
    nk = k // block_k
    compute_dtype = x.dtype if x.dtype in (jnp.bfloat16, jnp.float32) else jnp.float32

    if is_planar(block_k):
        x = planar_columns(x, block_k)

    kernel = functools.partial(_matvec_kernel, nk=nk, compute_dtype=compute_dtype)
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
        out_shape=jax.ShapeDtypeStruct((m, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed)
