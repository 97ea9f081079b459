"""Jit-ready wrappers around the TBN Pallas kernels.

Public entry points:
  * ``tiled_dense_infer``  — serving-time FC layer from (packed tile, alpha)
    without materializing the dense weight. Pallas on TPU; pure-JAX
    structured math elsewhere (identical FLOPs — used by the SPMD dry-run).
    Small batches (m <= MATVEC_MAX_M, i.e. decode ticks) dispatch to the
    decode-blocked ``tiled_matvec_unique`` kernel instead of the 128-row
    matmul blocking. Under an active mesh whose rules map ``tile_rows`` to
    a >1 axis (distributed/sharding.py) the row-packed tile is
    tensor-parallel: a shard_map runs the same kernel per shard on r/TP
    unique rows (the decode dispatch applies per shard too) and the
    output stays sharded on the tile-row axis (DESIGN.md §5).
  * ``tiled_conv_infer``   — serving-time Conv2D from a conv-layout packed
    tile: fused im2col + tile-reuse matmul on TPU (the dense OIHW weight
    never exists); elsewhere the structured fallback runs the p-fold
    smaller tile bank through ``conv_general_dilated``. Same shard_map
    tensor-parallel path over the tile's unique filters.
  * ``tile_construct``     — (W[,A]) -> (packed tile, alpha) fused on TPU.
  * ``tbn_dense_train``    — training forward y = x @ B_hat^T that composes
    the two kernels (B_hat never hits HBM) with a custom VJP whose backward
    is the *paper-faithful* gradient (vjp of the pure-JAX reference), so the
    fused path is a drop-in for the reference during training.

Tile layouts accepted by ``tiled_dense_infer``:
  * flat  (ceil(q/32),) int32 — legacy/fused-train form; requires 32 | n_in
    on the Pallas path (enforced — ``FlatTileLayoutError``) and never
    engages tensor parallelism.
  * rows  (r, ceil(n_in/32)) int32 — the shipped serve form: one packed
    word-padded row per unique weight row, shardable on its leading axis.

Compute paths (``tiled_dense_infer(compute_path=...)``): "float" is the
byte-parity reference (unpack to ±1, MXU float MACs). "xnor" and "int8"
quantize the activations and accumulate in the INTEGER domain directly
against the packed tile words (kernels/tiled_xnor.py) — they engage at
decode m (<= MATVEC_MAX_M, per shard under tensor parallelism) on the
row-packed form; larger batches (prefill) fall back to the float path so
the MXU-fed matmul blocking keeps serving chunked prefill.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.packing import pack_bits, unpack_bits, unpack_conv_tile
from repro.core.tiling import (
    TileSpec,
    compute_alpha,
    plan_conv_tiling,
    tile_vector,
    tiled_matmul_reference,
    tiled_weight,
)
from repro.distributed.sharding import batch_shard_axes, tile_sharding
from repro.kernels.tile_construct import tile_construct_pallas
from repro.kernels.tiled_conv import tiled_conv_unique
from repro.kernels.tiled_matmul import LANE_BITS, WORD_BLOCK, tiled_matmul_unique
from repro.kernels.tiled_matvec import (
    DECODE_BLOCK_K,
    DECODE_BLOCK_R,
    MATVEC_MAX_M,
    sublane_rounded,
    tiled_matvec_unique,
)
from repro.kernels.tiled_xnor import (
    COMPUTE_PATHS,
    INT8_BLOCK_K,
    INT8_BLOCK_R,
    XNOR_BLOCK_R,
    XNOR_BLOCK_W,
    int8_matvec_packed,
    quantize_int8,
    quantize_sign,
    tiled_int8_matvec_unique,
    tiled_xnor_matvec_unique,
    xnor_matvec_words,
)


class FlatTileLayoutError(ValueError):
    """Flat-form packed tile fed to a path that needs whole packed rows.

    The flat (ceil(q/32),) layout packs the tile as ONE bit stream; the
    row-packed Pallas kernels index it as (r, n_in/32) words, which is
    only the same bits when 32 | n_in. Raised instead of letting
    ``reshape`` fail with an opaque size mismatch (or worse, silently
    mis-slice rows on a future refactor)."""


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------
# Inference matmul
# --------------------------------------------------------------------------
def _dense_unique_int_local(
    xm: jax.Array,
    packed_rows: jax.Array,
    *,
    n_in: int,
    use_pallas: bool,
    compute_path: str,
) -> jax.Array:
    """Integer-domain u = Q(x) . T^T against a row-packed tile slice.

    Quantizes the activation rows (sign-binarize for "xnor", per-row
    symmetric int8 for "int8"), runs the integer kernel (Pallas) or its
    packed-word structured twin (pure jnp — non-TPU backends stay in the
    integer domain too), and rescales: ``u = scale * acc``. The int32
    accumulator is bit-identical between the two backends and the ref.py
    oracles, so dispatch parity is exact, not approximate. Runs
    unchanged per shard under the tensor-parallel wrapper (rows shard on
    r; every shard sees full activation rows, so per-row quantization is
    shard-invariant).
    """
    m = xm.shape[0]
    r_loc, words = packed_rows.shape
    if compute_path == "xnor":
        xq, scale = quantize_sign(xm, n_in)          # (m, words), (m, 1)
        if not use_pallas:
            acc = xnor_matvec_words(xq, packed_rows, n_in=n_in)
        else:
            bw = min(XNOR_BLOCK_W, words)
            br = min(XNOR_BLOCK_R, r_loc)
            xq_p = _pad_to(
                _pad_to(xq, 0, sublane_rounded(m, jnp.int32)), 1, bw
            )
            tm_p = _pad_to(_pad_to(packed_rows, 0, br), 1, bw)
            acc = tiled_xnor_matvec_unique(
                xq_p, tm_p, n_in=n_in, block_r=br, block_w=bw,
            )[:m, :r_loc]
    else:  # int8
        q, scale = quantize_int8(xm, n_in)           # (m, n_in), (m, 1)
        if not use_pallas:
            acc = int8_matvec_packed(q, packed_rows, n_in=n_in)
        else:
            bk = min(INT8_BLOCK_K, words * 32)
            br = min(INT8_BLOCK_R, r_loc)
            q_p = jnp.pad(q, ((0, 0), (0, words * 32 - n_in)))
            q_p = _pad_to(
                _pad_to(q_p, 0, sublane_rounded(m, jnp.int8)), 1, bk
            )
            tm_p = _pad_to(_pad_to(packed_rows, 0, br), 1, bk // 32)
            acc = tiled_int8_matvec_unique(
                q_p, tm_p, r=tm_p.shape[0], block_r=br, block_k=bk,
            )[:m, :r_loc]
    return scale * acc.astype(jnp.float32)


def _dense_unique_local(
    xm: jax.Array,
    packed_rows: jax.Array,
    *,
    n_in: int,
    use_pallas: bool,
    block_m: int,
    block_r: int,
    block_k: int,
    compute_path: str = "float",
) -> jax.Array:
    """u = x @ T^T against a row-packed tile slice.

    xm (m, n_in); packed_rows (r_loc, words) int32 with words*32 >= n_in
    (rows pad to whole words: pad bits unpack to -1 but only ever multiply
    zero-padded activation columns). Runs unchanged per shard under the
    tensor-parallel wrapper — r_loc is then r/TP.

    ``compute_path`` "xnor"/"int8" routes decode-sized batches
    (m <= MATVEC_MAX_M) to the integer-domain kernels; bigger batches
    keep the float matmul blocking (prefill stays MXU-fed).
    """
    m = xm.shape[0]
    r_loc, words = packed_rows.shape
    if compute_path != "float" and m <= MATVEC_MAX_M:
        return _dense_unique_int_local(
            xm, packed_rows, n_in=n_in, use_pallas=use_pallas,
            compute_path=compute_path,
        )
    if not use_pallas:
        tm = unpack_bits(packed_rows, n_in, dtype=xm.dtype)  # (r_loc, n_in)
        return jnp.einsum("mk,rk->mr", xm, tm)
    xp = jnp.pad(xm, ((0, 0), (0, words * 32 - n_in)))
    if m <= MATVEC_MAX_M:
        # Decode fast path: m is the whole (sublane-rounded) batch, so the
        # matmul kernel's 128-row m blocks would be mostly zero padding.
        # The matvec variant takes the batch as ONE m block and widens the
        # r/k blocking to keep the unpack-dominant regime fed.
        br = min(DECODE_BLOCK_R, r_loc)
        bk = min(DECODE_BLOCK_K, words * 32)
        xp = _pad_to(_pad_to(xp, 0, sublane_rounded(m, xp.dtype)), 1, bk)
        tm_p = _pad_to(_pad_to(packed_rows, 0, br), 1, bk // 32)
        return tiled_matvec_unique(
            xp, tm_p, r=tm_p.shape[0], block_r=br, block_k=bk,
        )[:m, :r_loc]
    # k blocks: the whole padded row when it fits one block, else block_k
    # steps over a row padded to a multiple (the Mosaic block rule,
    # kernels/tiled_matmul.py); r likewise
    br = min(block_r, r_loc)
    bk = min(block_k, words * 32)
    xp = _pad_to(_pad_to(xp, 0, block_m), 1, bk)
    tm_p = _pad_to(_pad_to(packed_rows, 0, br), 1, bk // 32)
    return tiled_matmul_unique(
        xp,
        tm_p,
        r=tm_p.shape[0],
        block_m=block_m,
        block_r=br,
        block_k=bk,
    )[:m, :r_loc]


def _replicate_dense_out(u: jax.Array, alpha: jax.Array, spec: TileSpec):
    """u (m, r_loc) -> y (m, p, r_loc): the tile-replica broadcast-scale."""
    m, r_loc = u.shape
    alpha = alpha.astype(u.dtype)
    if spec.alpha_mode == "layer":
        return jnp.broadcast_to(u[:, None, :], (m, spec.p, r_loc)) \
            * alpha.reshape(1)
    return jnp.broadcast_to(u[:, None, :] * alpha[None, :, None],
                            (m, spec.p, r_loc))


def tiled_dense_infer(
    x: jax.Array,
    packed: jax.Array,
    alpha: jax.Array,
    spec: TileSpec,
    *,
    use_pallas: Optional[bool] = None,
    block_m: int = 128,
    block_r: int = 128,
    block_k: int = WORD_BLOCK * LANE_BITS,
    compute_path: str = "float",
) -> jax.Array:
    """y = x @ W_hat^T from the shipped representation.

    x: (..., n_in); packed: int32, flat (ceil(q/32),) or row-packed
    (r, ceil(n_in/32)) — see module docstring; alpha: (n_alpha,).
    Weight logical shape spec.shape == (n_out, n_in), aligned tiling.

    Row-packed tiles are tensor-parallel under an active mesh: the tile
    rows shard over the ``tile_rows`` axis, each shard runs the same
    kernel on r/TP rows, and the (m, p, r) output stays sharded on its
    unique-row axis until the caller's reshape (DESIGN.md §5).

    ``compute_path`` (see module docstring): "float" (default, byte-
    parity reference) | "int8" | "xnor". The integer paths quantize the
    activations, so outputs are approximate w.r.t. the float path — the
    exactness contract moves to the integer accumulator (bit-identical
    to the ref.py oracles). They apply at decode m on row-packed (or
    Pallas-reshaped flat) tiles; elsewhere the call silently keeps the
    float path rather than failing mid-model.
    """
    if compute_path not in COMPUTE_PATHS:
        raise ValueError(
            f"unknown compute_path {compute_path!r}: expected one of "
            f"{COMPUTE_PATHS}"
        )
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    n_out, n_in = spec.shape[0], spec.n // spec.shape[0]
    r = spec.rows_per_tile
    lead = x.shape[:-1]
    xm = x.reshape(-1, n_in)
    m = xm.shape[0]

    row_form = packed.ndim == 2
    if not row_form:
        if not use_pallas:
            t = unpack_bits(packed, spec.q, dtype=x.dtype)
            y = tiled_matmul_reference(xm, t, alpha, spec)
            return y.reshape(*lead, n_out).astype(x.dtype)
        if n_in % 32:
            raise FlatTileLayoutError(
                f"flat-form packed tile cannot be viewed as packed rows: "
                f"n_in={n_in} is not a multiple of 32 (spec.shape="
                f"{spec.shape}), so row boundaries fall mid-word. Ship "
                f"the row-packed (r, ceil(n_in/32)) serve form (each row "
                f"padded to whole words) for the Pallas path."
            )
        packed = packed.reshape(r, n_in // 32)

    local = functools.partial(
        _dense_unique_local, n_in=n_in, use_pallas=use_pallas,
        block_m=block_m, block_r=block_r, block_k=block_k,
        compute_path=compute_path,
    )
    tp = tile_sharding(r) if row_form else None
    if tp is not None:
        mesh, ax, _ = tp
        m_ax = batch_shard_axes(ax, m) or None
        y3 = jax.shard_map(
            lambda xl, pl_, al: _replicate_dense_out(local(xl, pl_), al, spec),
            mesh=mesh,
            in_specs=(P(m_ax, None), P(ax, None), P()),
            out_specs=P(m_ax, None, ax),
            check_vma=False,
        )(xm, packed, alpha)
    else:
        y3 = _replicate_dense_out(local(xm, packed), alpha, spec)
    return y3.reshape(*lead, n_out).astype(x.dtype)


# --------------------------------------------------------------------------
# Inference conv
# --------------------------------------------------------------------------
Padding = Union[str, Sequence[Tuple[int, int]]]


def _conv_spatial(size: int, k: int, s: int, pad) -> Tuple[int, int, int]:
    """(out_size, pad_lo, pad_hi) with conv_general_dilated semantics."""
    if pad in ("SAME", "SAME_LOWER"):
        out = -(-size // s)
        total = max((out - 1) * s + k - size, 0)
        half = total // 2
        lo = half if pad == "SAME" else total - half
        return out, lo, total - lo
    if pad == "VALID":
        lo = hi = 0
    elif isinstance(pad, str):
        raise ValueError(f"unsupported padding {pad!r} for tiled conv")
    else:
        lo, hi = pad
    return (size + lo + hi - k) // s + 1, lo, hi


def resolve_conv_padding(
    hw: Tuple[int, int], kernel: Tuple[int, int], stride: Tuple[int, int],
    padding: Padding,
) -> Tuple[Tuple[int, int], Tuple[Tuple[int, int], Tuple[int, int]]]:
    """-> ((OH, OW), explicit ((lo_h, hi_h), (lo_w, hi_w)))."""
    pads = (padding, padding) if isinstance(padding, str) else tuple(padding)
    oh, lo_h, hi_h = _conv_spatial(hw[0], kernel[0], stride[0], pads[0])
    ow, lo_w, hi_w = _conv_spatial(hw[1], kernel[1], stride[1], pads[1])
    return (oh, ow), ((lo_h, hi_h), (lo_w, hi_w))


def _replicate_conv_out(u, alpha, spec: TileSpec):
    """u (N, OH, OW, r_loc) -> y (N, OH, OW, p, r_loc), replica-major.

    Kept unflattened so the tensor-parallel wrapper can declare the
    unique-filter axis sharded; callers reshape to (N, OH, OW, p*r)."""
    n, oh, ow, r_loc = u.shape
    alpha = alpha.astype(u.dtype)
    if spec.alpha_mode == "layer":
        return jnp.broadcast_to(u[..., None, :], (n, oh, ow, spec.p, r_loc)) \
            * alpha.reshape(1)
    return jnp.broadcast_to(
        u[..., None, :] * alpha[None, None, None, :, None],
        (n, oh, ow, spec.p, r_loc),
    )


def tiled_conv_infer(
    x: jax.Array,
    packed: jax.Array,
    alpha: jax.Array,
    spec: TileSpec,
    *,
    stride: Tuple[int, int] = (1, 1),
    padding: Padding = "SAME",
    use_pallas: Optional[bool] = None,
    block_r: int = 128,
) -> jax.Array:
    """y = conv(x, W_hat) from the shipped conv representation.

    x: (N, H, W, C) NHWC; packed: (kh*kw, r, ceil(C/32)) int32 conv-layout
    tile (repro.core.packing.pack_conv_tile); alpha: (n_alpha,). The weight
    logical shape spec.shape == (c_out, C, kh, kw) with p | c_out.

    The dense weight is never materialized on either path: the conv runs
    against the r = c_out/p unique filters of the tile and the p replicas
    are a broadcast-scale on the output channels (exact conv analogue of
    ``tiled_matmul_reference`` — validated against
    ``kernels.ref.tiled_conv_ref``).
    """
    plan = plan_conv_tiling(spec)
    if plan is None:
        raise ValueError(f"spec {spec.shape} has no aligned conv tiling")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    kh, kw = plan.kernel
    sh, sw = stride
    n, h, w, c = x.shape
    assert c == plan.c_in, (c, plan.c_in)
    r = plan.r
    (oh, ow), pads = resolve_conv_padding((h, w), (kh, kw), stride, padding)

    if use_pallas:
        # Pallas path: pad spatially so every kernel read is in bounds
        # (Hp >= (OH-1)*sh + kh, Wp >= kw-1 + OW*sw), channels to whole
        # int32 lanes (zero activations x any tile bit contribute nothing);
        # the filter axis pads to block_r multiples per shard below.
        hp = max(h + pads[0][0] + pads[0][1], (oh - 1) * sh + kh)
        wp = max(w + pads[1][0] + pads[1][1], (kw - 1) + ow * sw)
        cpad = (-c) % 32
        xin = jnp.pad(
            x,
            (
                (0, 0),
                (pads[0][0], hp - h - pads[0][0]),
                (pads[1][0], wp - w - pads[1][0]),
                (0, cpad),
            ),
        )
    else:
        xin = x

    def local_u(x_l, packed_l):
        """u = conv(x, T_loc) against a conv-layout tile slice of r_loc
        unique filters (r_loc = r/TP under the tensor-parallel wrapper)."""
        r_loc = packed_l.shape[1]
        if not use_pallas:
            bank = unpack_conv_tile(packed_l, r_loc, c, kh, kw, dtype=x.dtype)
            return jax.lax.conv_general_dilated(
                x_l, bank, window_strides=stride, padding=pads,
                dimension_numbers=("NHWC", "OIHW", "NHWC"),
            )
        br = min(block_r, r_loc)
        packed_p = jnp.pad(packed_l, ((0, 0), (0, (-r_loc) % br), (0, 0)))
        return tiled_conv_unique(
            x_l,
            packed_p,
            kernel=(kh, kw),
            stride=stride,
            out_hw=(oh, ow),
            block_r=br,
        )[..., :r_loc]

    tp = tile_sharding(r)
    if tp is not None:
        mesh, ax, _ = tp
        n_ax = batch_shard_axes(ax, n) or None
        y5 = jax.shard_map(
            lambda xl, pl_, al: _replicate_conv_out(local_u(xl, pl_), al, spec),
            mesh=mesh,
            in_specs=(P(n_ax, None, None, None), P(None, ax, None), P()),
            out_specs=P(n_ax, None, None, None, ax),
            check_vma=False,
        )(xin, packed, alpha)
    else:
        y5 = _replicate_conv_out(local_u(xin, packed), alpha, spec)
    return y5.reshape(n, oh, ow, spec.p * r).astype(x.dtype)


# --------------------------------------------------------------------------
# Construction
# --------------------------------------------------------------------------
def tile_construct(
    w: jax.Array,
    spec: TileSpec,
    a: Optional[jax.Array] = None,
    *,
    use_pallas: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Master weight(s) -> (packed tile int32, alpha (n_alpha,))."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    src = a if spec.alpha_source == "A" else None
    if not use_pallas:
        t = tile_vector(w, spec)
        alpha = compute_alpha(w if src is None else src, spec)
        return pack_bits(t), alpha.astype(jnp.float32)

    w2d = _pad_to(w.reshape(spec.p, spec.q), 1, 32)
    a2d = None if src is None else _pad_to(src.reshape(spec.p, spec.q), 1, 32)
    q_pad = w2d.shape[1]
    # pick a block that divides the padded q
    block_q = min(4096, q_pad)
    while q_pad % block_q:
        block_q -= 32
    packed, alpha_t = tile_construct_pallas(w2d, a2d, block_q=block_q)
    alpha_t = alpha_t * (q_pad / spec.q)  # kernel divides by padded q
    n_words = (spec.q + 31) // 32
    packed = packed[:n_words]
    if spec.alpha_mode == "layer":
        alpha = jnp.mean(alpha_t, keepdims=True)
    else:
        alpha = alpha_t
    return packed, alpha.astype(jnp.float32)


# --------------------------------------------------------------------------
# Fused training forward (custom VJP)
# --------------------------------------------------------------------------
def _train_ref_forward(x, w, a, spec: TileSpec):
    """Paper-faithful reference: materialize B_hat, dense matmul."""
    bhat = tiled_weight(w, spec, a=a, dtype=x.dtype)
    n_out, n_in = spec.shape[0], spec.n // spec.shape[0]
    return jnp.einsum("...k,ok->...o", x, bhat.reshape(n_out, n_in))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def tbn_dense_train(x, w, a, spec: TileSpec):
    """Training forward via the fused kernels; gradient == reference VJP.

    ``a`` may equal ``w`` (alpha_source == "W"); pass the same array.
    """
    packed, alpha = tile_construct(w, spec, a=a)
    return tiled_dense_infer(x, packed, alpha, spec).astype(x.dtype)


def _tbn_dense_train_fwd(x, w, a, spec):
    y = tbn_dense_train(x, w, a, spec)
    return y, (x, w, a)


def _tbn_dense_train_bwd(spec, res, g):
    x, w, a = res
    # Backward is the exact VJP of the paper-faithful reference forward —
    # recomputes B_hat once (remat) instead of storing it.
    _, vjp = jax.vjp(lambda x, w, a: _train_ref_forward(x, w, a, spec), x, w, a)
    return vjp(g)


tbn_dense_train.defvjp(_tbn_dense_train_fwd, _tbn_dense_train_bwd)
