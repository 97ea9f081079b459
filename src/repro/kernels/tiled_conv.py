"""Pallas TPU kernel: fused im2col conv forward with a reused bit-packed tile.

Conv analogue of ``tiled_matmul`` (DESIGN.md §4): the dense OIHW conv weight
never exists — HBM holds one bit-packed tile in "conv layout",
``packed (kh*kw, r, c_in/32) int32`` (r = c_out / p unique filters), and the
kernel contracts the conv as a sum over kernel positions of strided 1x1
matmuls against the unpacked tile cross-section:

    u[n, oh, ow, :] = sum_{i,j} x[n, oh*sh + i, ow*sw + j, :] @ T[i,j]^T
    y = kron(alpha, u)   -- broadcast over the p tile replicas (ops.py)

Patch extraction (im2col) is fused: per grid step the kernel pulls ONE
padded input row and the kw packed (br, C/32) cross-sections of one kernel
row into VMEM, reads each kernel column's (OW, C) patch block straight off
the row ref, unpacks the bits to ±1 on the VPU, and feeds the MXU. The
wrapper splits the row's columns by stride phase, ``(sw, Wp/sw, C)`` with
phase ``w % sw`` first, so every patch is a contiguous static slice of one
phase (Mosaic has no strided load for 16-bit data). Neither the im2col
matrix nor the dense weight is ever materialized in HBM — weight traffic
is 32*p smaller than fp32.

Grid: (N*OH, r/br, kh); the kernel-row axis is innermost and sequential
(accumulates into VMEM scratch), the row and filter axes are parallel.
VMEM working set per step: Wp*C + kw*br*C/32 + 2*OW*br elements. The
wrapper (ops.tiled_conv_infer) handles SAME/VALID padding, channel
padding to whole 32-bit lanes, and filter padding to br multiples.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiled_matmul import _unpack_block

LANE_BITS = 32


def _conv_kernel(
    x_ref, w_ref, o_ref, acc_ref, *, kw: int, sw: int, ow: int, nk: int,
    compute_dtype,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = x_ref.shape[-1]
    br = w_ref.shape[1]
    for j in range(kw):
        # Fused patch gather: output column o reads input column o*sw + j,
        # i.e. column o + j//sw of stride phase j % sw — the (ow, c) im2col
        # block for kernel position (ki, j).
        start = j // sw
        patch = x_ref[0, j % sw, start:start + ow, :].astype(compute_dtype)
        t = _unpack_block(w_ref[j], br, c, compute_dtype)  # (br, c) in ±1
        acc_ref[...] += jax.lax.dot_general(
            patch, t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def tiled_conv_unique(
    x: jax.Array,
    packed: jax.Array,
    *,
    kernel: Tuple[int, int],
    stride: Tuple[int, int],
    out_hw: Tuple[int, int],
    block_r: int = 128,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """u[n,oh,ow,:] = patches(x) @ T^T for a conv-layout packed tile.

    x: (N, Hp, Wp, C) — already spatially padded so that every read is in
    bounds: Hp >= (OH-1)*sh + kh and Wp >= (kw-1) + OW*sw. C must be a
    multiple of 32. packed: (kh*kw, r, C/32) int32 (see
    repro.core.packing.pack_conv_tile); block_r must divide r (ops.py pads).
    Returns u (N, OH, OW, r) in ``out_dtype``.
    """
    n, hp, wp, c = x.shape
    kh, kw = kernel
    sh, sw = stride
    oh, ow = out_hw
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert c % LANE_BITS == 0, "C must be a multiple of 32 (packed lanes)"
    r = packed.shape[1]
    assert packed.shape == (kh * kw, r, c // LANE_BITS), packed.shape
    assert r % block_r == 0, (r, block_r)  # caller clamps/pads (ops.py)
    assert hp >= (oh - 1) * sh + kh, (hp, oh, sh, kh)
    assert wp >= (kw - 1) + ow * sw, (wp, ow, sw, kw)

    # split columns by stride phase: xrows[row, w % sw, w // sw, :]
    x = jnp.pad(x, ((0, 0), (0, 0), (0, (-wp) % sw), (0, 0)))
    wpp = x.shape[2] // sw
    xrows = x.reshape(n * hp, wpp, sw, c).transpose(0, 2, 1, 3)

    def x_index(mi, ri, ki):
        # input row for output row block mi=(n, oh) at kernel row ki
        return ((mi // oh) * hp + (mi % oh) * sh + ki, 0, 0, 0)

    kernel_fn = functools.partial(
        _conv_kernel, kw=kw, sw=sw, ow=ow, nk=kh,
        compute_dtype=(x.dtype if x.dtype in (jnp.bfloat16, jnp.float32)
                       else jnp.float32),
    )
    u = pl.pallas_call(
        kernel_fn,
        grid=(n * oh, r // block_r, kh),
        in_specs=[
            pl.BlockSpec((1, sw, wpp, c), x_index),
            pl.BlockSpec(
                (kw, block_r, c // LANE_BITS), lambda mi, ri, ki: (ki, ri, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, ow, block_r), lambda mi, ri, ki: (mi, 0, ri)),
        out_shape=jax.ShapeDtypeStruct((n * oh, ow, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((ow, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xrows, packed)
    return u.reshape(n, oh, ow, r)
