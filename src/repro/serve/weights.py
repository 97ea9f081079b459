"""Convert TRAIN-mode masters into the shipped SERVE representation.

TRAIN params hold full-precision masters (W [, A]); SERVE params hold what
the paper actually stores after training (Section 3, "After training is
complete"):

    tiled layer   -> packed tile bits (q bits in int32 lanes) + alpha(s)
    BWNN layer    -> row-packed sign bits + one alpha
    fp32 layer    -> weights cast to the serving compute dtype

The converter pairs the two spec trees of the *same* architecture built in
TRAIN and SERVE mode and dispatches on the serve node's keys, so it works
for Dense, stacked (scan-over-layers) Dense, and (L, E, ...) MoE expert
banks without any per-model code.

This is also the elastic-rejoin broadcast payload (DESIGN.md §5): packed
tiles are ~32*p smaller than fp32 masters, so re-seeding a repaired node
with serving weights costs ~1/128th the bytes at p=4.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import pack_bits, pack_conv_tile, packed_len
from repro.core.policy import TBNPolicy
from repro.core.tiling import (
    TileSpec,
    compute_alpha,
    plan_conv_tiling,
    plan_tiling,
    tile_vector,
)
from repro.nn import module as mod


def _derive_layer_spec(policy: TBNPolicy, layer_shape: Tuple[int, ...]):
    """Re-derive a layer's TileSpec from the policy (single source for every
    export branch, so a new policy field threads through exactly once)."""
    return plan_tiling(
        layer_shape,
        p=policy.p,
        min_size=policy.min_size,
        alpha_mode=policy.alpha_mode,
        alpha_source=policy.alpha_source,
        ste=policy.ste,
        require_aligned=policy.require_aligned,
    )


def _derive_spec(
    policy: TBNPolicy, layer_shape: Tuple[int, ...], tile_packed: int,
    n_alpha: int,
) -> TileSpec:
    """TileSpec for a flat-tile layer; cross-check vs the serve decl."""
    spec = _derive_layer_spec(policy, layer_shape)
    if spec is None:
        raise ValueError(f"policy does not tile layer of shape {layer_shape}")
    if packed_len(spec.q) != tile_packed or spec.n_alpha != n_alpha:
        raise ValueError(
            f"derived spec (q={spec.q}, n_alpha={spec.n_alpha}) does not match "
            f"serve decl (packed={tile_packed}, n_alpha={n_alpha}) "
            f"for shape {layer_shape}"
        )
    return spec


def _tile_and_alpha(w, a, spec: TileSpec):
    """The shipped (t ±1 (q,), alpha (n_alpha,)) — shared by every layout."""
    t = tile_vector(w.astype(jnp.float32), spec)
    src = a if (spec.alpha_source == "A" and a is not None) else w
    return t, compute_alpha(src.astype(jnp.float32), spec)


def _export_tiled(w, a, spec: TileSpec):
    """(packed int32 (ceil(q/32),), alpha (n_alpha,)) for one layer."""
    t, alpha = _tile_and_alpha(w, a, spec)
    return pack_bits(t), alpha


def _export_tiled_rows(w, a, spec: TileSpec):
    """Row-packed shipped form: (r, ceil(n_in/32)) int32 + alpha (n_alpha,).

    Same tile bits as ``_export_tiled`` laid out one word-padded packed row
    per unique weight row, so the leading axis is directly shardable over
    the tensor-parallel mesh axis ("tile_rows") and the matmul kernel
    streams a (block_r, block_k/32) block without crossing rows.
    """
    t, alpha = _tile_and_alpha(w, a, spec)
    r = spec.rows_per_tile
    n_in = spec.n // spec.shape[0]
    return pack_bits(t.reshape(r, n_in)), alpha


def _export_conv_tiled(w, a, spec: TileSpec):
    """Conv-layout packed tile (kh*kw, r, ceil(c_in/32)) + alpha.

    Same tile bits as ``_export_tiled``, laid out per kernel position so the
    fused im2col kernel (repro.kernels.tiled_conv) streams them directly —
    the serving host never re-shuffles, and the dense OIHW weight never
    exists on the serving path.
    """
    plan = plan_conv_tiling(spec)
    t, alpha = _tile_and_alpha(w, a, spec)
    kh, kw = plan.kernel
    return pack_conv_tile(t, plan.r, plan.c_in, kh, kw), alpha


def _export_bwnn(w):
    """Row-packed sign bits + single alpha for one weight tensor.

    Rows are the leading dim; trailing dims flatten into the packed axis
    (dense (n_out, n_in) rows and conv (c_out, c_in*kh*kw) filters alike).
    """
    alpha = jnp.mean(jnp.abs(w.astype(jnp.float32))).reshape(1)
    rows = jnp.where(w > 0, 1.0, -1.0).reshape(w.shape[0], -1)
    return pack_bits(rows), alpha


def _vmap_n(fn, n_lead: int):
    for _ in range(n_lead):
        fn = jax.vmap(fn)
    return fn


def export_serving_params(
    train_specs: mod.SpecTree,
    serve_specs: mod.SpecTree,
    train_params: Dict,
    policy: TBNPolicy,
) -> Dict:
    """Walk the two spec trees; emit the SERVE param tree from masters."""

    def convert(tr_spec, sv_spec, tr_par):
        if not isinstance(sv_spec, dict):
            raise TypeError(f"unexpected serve spec node {type(sv_spec)}")
        keys = set(sv_spec)
        if "tile_conv" in keys:  # tiled Conv2D (conv-layout packed tile)
            tile_decl: mod.ParamSpec = sv_spec["tile_conv"]
            alpha_decl: mod.ParamSpec = sv_spec["alpha"]
            w = tr_par["w"]
            a = tr_par.get("a")
            n_lead = len(tile_decl.shape) - 3
            layer_shape = tuple(w.shape[n_lead:])
            spec = _derive_layer_spec(policy, layer_shape)
            plan = plan_conv_tiling(spec)
            if plan is None or plan.packed_shape() != tile_decl.shape[n_lead:] \
                    or spec.n_alpha != alpha_decl.shape[-1]:
                raise ValueError(
                    f"derived conv plan does not match serve decl "
                    f"{tile_decl.shape} for shape {layer_shape}"
                )
            fn = _vmap_n(lambda we, ae: _export_conv_tiled(we, ae, spec), n_lead)
            tile, alpha = fn(w, w if a is None else a)
            out = {"tile_conv": tile, "alpha": alpha}
            if "b" in keys:
                out["b"] = tr_par["b"].astype(sv_spec["b"].dtype)
            return out
        if "tile" in keys:  # TBN layer (possibly stacked / expert bank)
            tile_decl: mod.ParamSpec = sv_spec["tile"]
            alpha_decl: mod.ParamSpec = sv_spec["alpha"]
            w = tr_par["w"]
            a = tr_par.get("a")
            # Layout dispatch: a row-packed decl is (*lead, r, words) over a
            # 2-D (n_out, n_in) layer; anything else is the flat q-bit form
            # (unaligned tilings, and 4-D conv fallbacks).
            exported = None
            if len(tile_decl.shape) >= 2:
                n_lead = len(tile_decl.shape) - 2
                layer_shape = tuple(w.shape[n_lead:])
                if len(layer_shape) == 2:
                    spec = _derive_layer_spec(policy, layer_shape)
                    if spec is not None and spec.aligned_rows:
                        rows = (spec.rows_per_tile, packed_len(layer_shape[1]))
                        if rows == tile_decl.shape[n_lead:] \
                                and spec.n_alpha == alpha_decl.shape[-1]:
                            fn = _vmap_n(
                                lambda we, ae: _export_tiled_rows(we, ae, spec),
                                n_lead,
                            )
                            exported = fn(w, w if a is None else a)
            if exported is None:
                n_lead = len(tile_decl.shape) - 1
                layer_shape = tuple(w.shape[n_lead:])
                spec = _derive_spec(
                    policy, layer_shape, tile_decl.shape[-1],
                    alpha_decl.shape[-1],
                )
                fn = _vmap_n(lambda we, ae: _export_tiled(we, ae, spec), n_lead)
                exported = fn(w, w if a is None else a)
            tile, alpha = exported
            out = {"tile": tile, "alpha": alpha}
            if "b" in keys:
                out["b"] = tr_par["b"].astype(sv_spec["b"].dtype)
            return out
        if "wbits" in keys:  # BWNN layer
            wb_decl: mod.ParamSpec = sv_spec["wbits"]
            w = tr_par["w"]
            n_lead = len(wb_decl.shape) - 2
            fn = _vmap_n(_export_bwnn, n_lead)
            bits, alpha = fn(w)
            out = {"wbits": bits, "alpha": alpha.reshape(alpha.shape[:n_lead] + (1,))
                   if n_lead else alpha}
            if "b" in keys:
                out["b"] = tr_par["b"].astype(sv_spec["b"].dtype)
            return out
        if isinstance(sv_spec.get("w"), mod.ParamSpec) or any(
            isinstance(v, mod.ParamSpec) for v in sv_spec.values()
        ):
            # leaf layer kept dense (fp32/below-lambda) or norm/embed node
            out = {}
            for k, decl in sv_spec.items():
                if isinstance(decl, mod.ParamSpec):
                    out[k] = tr_par[k].astype(decl.dtype)
                else:
                    out[k] = convert(tr_spec[k], decl, tr_par[k])
            return out
        return {
            k: convert(tr_spec[k], sv_spec[k], tr_par[k]) for k in sv_spec
        }

    return convert(train_specs, serve_specs, train_params)


def serving_bytes(params) -> int:
    """Exact bytes of a (serve-form) param tree."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(params)
    )


def _tile_leaves(params):
    """(path-key, leaf) for every packed-tile leaf (``tile``/``tile_conv``)."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = [str(getattr(p, "key", p)) for p in path]
        if keys and keys[-1] in ("tile", "tile_conv"):
            yield "/".join(keys), leaf


def tile_serving_bytes(params) -> int:
    """Bytes of the packed tile bits alone (the 1/TP-scaling share)."""
    return sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for _, leaf in _tile_leaves(params)
    )


def per_device_bytes(leaves) -> Dict[str, int]:
    """device -> bytes of ``leaves`` RESIDENT on that device: a sharded
    leaf counts each shard on its own device, a replicated one fully on
    every device, an unplaced (host) one under one entry."""
    out: Dict[str, int] = {}
    for leaf in leaves:
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            dev = str(getattr(leaf, "device", "host"))
            out[dev] = out.get(dev, 0) + leaf.nbytes
            continue
        for sh in shards:
            dev = str(sh.device)
            out[dev] = out.get(dev, 0) + sh.data.nbytes
    return out


def per_device_tile_bytes(params) -> Dict[str, int]:
    """device -> bytes of packed tile bits RESIDENT on that device.

    For a mesh-placed param tree this is what each chip's HBM actually
    holds: sharded tiles count 1/TP of their bytes per device, replicated
    leaves count fully on every device. The tensor-parallel acceptance
    check is that the per-device number scales as 1/TP of
    ``tile_serving_bytes``.
    """
    return per_device_bytes(leaf for _, leaf in _tile_leaves(params))
