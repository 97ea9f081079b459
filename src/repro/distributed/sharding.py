"""Logical-axis sharding: one place that maps layer semantics to the mesh.

Layers annotate weights with *logical* axis names ("heads", "embed", ...)
and wrap hot activations in ``logical_constraint``. The launcher activates a
rule set for the current mesh; outside a rule context everything is a no-op
(single-device tests/benchmarks never touch device APIs).

Default rules (DESIGN.md §5):

  weights   heads/mlp/vocab/experts -> "model"   (tensor/expert parallel)
            embed                   -> "data"    (FSDP/ZeRO-3 master shard)
  acts      act_batch -> ("pod","data")          (data parallel)
            act_heads/act_mlp/act_vocab -> "model"
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

DEFAULT_RULES: Dict[str, AxisVal] = {
    # weight axes
    "heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "embed": "data",
    "kv": None,
    "conv_spatial": None,
    "layers": None,
    "stage": None,
    # unique-row axis of a row-packed serve tile: the r = n_out/p rows of
    # one tile shard over the model axis (r/TP rows per device), so HBM per
    # device holds q/TP tile bits. The kernels run per-shard under
    # shard_map (kernels/ops.py); alphas stay replicated — each shard's
    # rows appear in ALL p replica blocks of the output, so every shard
    # needs every alpha, and p floats are not worth slicing (DESIGN.md §5).
    "tile_rows": "model",
    # activation axes
    "act_batch": ("pod", "data"),
    "act_seq": None,
    # residual-stream sequence axis: sharded over "model" BETWEEN blocks
    # (Megatron-style sequence parallelism). GSPMD all-gathers at the QKV /
    # FFN entry and reduce-scatters after the output projection; the scan
    # carry saved for backward is 1/TP the size, which is what lets the
    # 4k-seq train cells fit HBM (EXPERIMENTS.md §Dry-run).
    "act_res_seq": "model",
    # MoE dispatch-group axes. "act_tok": the dispatch/combine domain —
    # groups shard over EVERY mesh axis (all index ops are group-local).
    # "act_cap": the expert-einsum domain — groups keep only the DP axes
    # so "act_experts" can take the model axis. The act_tok <-> act_cap
    # resharding GSPMD inserts is exactly the EP all-to-all.
    "act_tok": ("pod", "data", "model"),
    "act_cap": ("pod", "data"),
    "act_embed": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
    "act_experts": "model",
}


class _Active(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Dict[str, AxisVal]] = None


_ACTIVE = _Active()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict[str, AxisVal]] = None):
    """Activate logical->mesh rules (and the mesh) for a region."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    # Drop axes the mesh does not have (e.g. "pod" on the single-pod mesh).
    names = set(mesh.axis_names)

    def _filter(v: AxisVal) -> AxisVal:
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        kept = tuple(a for a in v if a in names)
        return kept if kept else None

    filtered = {k: _filter(v) for k, v in rules.items()}
    prev = (_ACTIVE.mesh, _ACTIVE.rules)
    _ACTIVE.mesh, _ACTIVE.rules = mesh, filtered
    try:
        with mesh:
            yield
    finally:
        _ACTIVE.mesh, _ACTIVE.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE.mesh


def _rule_axes(rule_name: str) -> Tuple[Optional[Mesh], Tuple[str, ...]]:
    """Active mesh + the rule's axes (normalized, filtered to the mesh)."""
    mesh, rules = _ACTIVE.mesh, _ACTIVE.rules
    if mesh is None or rules is None:
        return None, ()
    ax = rules.get(rule_name)
    if ax is None:
        return mesh, ()
    if isinstance(ax, str):
        ax = (ax,)
    return mesh, tuple(a for a in ax if a in mesh.axis_names)


def _dividing_prefix(mesh: Mesh, axes: Tuple[str, ...], dim: int):
    """Longest prefix of ``axes`` whose extent divides ``dim`` — the same
    degradation rule ``_divisible_spec`` applies to param placement, so
    trace-time decisions in the serve kernels can never disagree with
    where the params were actually placed."""
    for k in range(len(axes), 0, -1):
        cand = axes[:k]
        if dim % _mesh_extent(mesh, cand) == 0:
            return cand
    return ()


def tile_sharding(n_rows: int) -> Optional[Tuple[Mesh, Tuple[str, ...], int]]:
    """(mesh, axes, extent) to shard a tile's ``n_rows`` unique rows, or None.

    None means tile-row sharding is off: no active rules, the
    ``tile_rows`` rule maps to no mesh axis, or the longest
    dim-dividing prefix of its axes has extent 1 (including the
    TP-does-not-divide-r fallback). The serve kernels consult this at
    trace time to choose between the shard_map tensor-parallel path and
    the single-device path (kernels/ops.py)."""
    mesh, axes = _rule_axes("tile_rows")
    if mesh is None or not axes:
        return None
    chosen = _dividing_prefix(mesh, axes, n_rows)
    extent = _mesh_extent(mesh, chosen)
    if extent <= 1:
        return None
    return mesh, chosen, extent


def batch_shard_axes(exclude: Sequence[str], dim: int) -> Tuple[str, ...]:
    """Axes to shard a batch-like dim of size ``dim`` inside the serve
    shard_map wrappers: the longest dividing prefix of the ``act_batch``
    rule minus ``exclude`` — keeps activations data-parallel inside the
    tensor-parallel region instead of forcing replication."""
    mesh, axes = _rule_axes("act_batch")
    if mesh is None:
        return ()
    axes = tuple(a for a in axes if a not in exclude)
    return _dividing_prefix(mesh, axes, dim)


def spec_from_logical(logical: Sequence[Optional[str]]) -> P:
    rules = _ACTIVE.rules or {}
    return P(*(rules.get(name) if name else None for name in logical))


def _mesh_extent(mesh: Mesh, axes: AxisVal) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _divisible_spec(mesh: Mesh, shape, spec_axes) -> P:
    """Sanitize a spec: drop non-dividing axes and duplicate mesh axes.

    * Ragged dims (e.g. vocab=50280 over model=16) stay replicated instead
      of failing the pjit divisibility check.
    * A mesh axis may shard at most one positional dim; the FIRST logical
      dim that claims it wins (stacked MoE banks map both "experts" and
      "mlp" to "model"; square (d,d) weights map "embed" twice).
    """
    used: set = set()
    out = []
    for dim, ax in zip(shape, spec_axes):
        if ax is None:
            out.append(None)
            continue
        parts = (ax,) if isinstance(ax, str) else tuple(ax)
        parts = tuple(a for a in parts if a not in used)
        # longest prefix of the axis tuple that evenly divides the dim —
        # a (pod, data, model) batch rule degrades to (pod, data) for a
        # 32-sample prefill instead of replicating outright. Shared with
        # the serve kernels' trace-time decisions (tile_sharding /
        # batch_shard_axes) so placement and shard_map can never disagree.
        cand = _dividing_prefix(mesh, parts, dim)
        if not cand:
            out.append(None)
            continue
        used.update(cand)
        out.append(cand if len(cand) > 1 else cand[0])
    return P(*out)


def logical_constraint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint against the active rules; no-op outside."""
    if _ACTIVE.mesh is None or _ACTIVE.rules is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} axes for rank-{x.ndim} array")
    spec = spec_from_logical(logical)
    spec = _divisible_spec(_ACTIVE.mesh, x.shape, tuple(spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACTIVE.mesh, spec)
    )


def named_sharding(mesh: Mesh, *axes: AxisVal) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


# K/V cache leaves by key -> the axis holding the KV heads: (..., kv, hd)
# for k/v, (..., kv) for the int8 scales
_KV_HEAD_AXIS = {"k": -2, "v": -2, "ks": -1, "vs": -1}


def cache_shardings(mesh: Mesh, caches, rules=None):
    """NamedShardings for a tree of serving caches (arrays or avals).

    Every K/V leaf shards its KV-head axis where ``act_kv_heads`` maps —
    the heads each chip computes under tensor parallelism — so a chip
    holds 1/TP of the KV pool and a tick's cache writes stay local. All
    other axes and leaves (recurrent state) are replicated: pool pages
    are shared by every slot through the page table, so they do not
    split over the data axis.
    """
    rules = dict(DEFAULT_RULES, **(rules or {}))
    heads = rules.get("act_kv_heads")
    heads = (heads,) if isinstance(heads, str) else tuple(heads or ())
    heads = tuple(a for a in heads if a in mesh.axis_names) or None

    def one(path, leaf):
        spec = [None] * len(leaf.shape)
        key = getattr(path[-1], "key", None) if path else None
        if heads and key in _KV_HEAD_AXIS:
            spec[_KV_HEAD_AXIS[key]] = heads
        return NamedSharding(mesh, _divisible_spec(mesh, leaf.shape, spec))

    return jax.tree_util.tree_map_with_path(one, caches)


def param_shardings(mesh: Mesh, logical_tree, rules=None, abstract_tree=None):
    """Map a tree of logical-axis tuples to NamedShardings for jit.

    When ``abstract_tree`` (matching ShapeDtypeStructs) is given, mesh axes
    that do not evenly divide their dimension are dropped (replicated) so
    ragged dims — 50280-row vocab over a 16-way model axis — never fail the
    pjit divisibility check.
    """
    rules = dict(DEFAULT_RULES, **(rules or {}))
    names = set(mesh.axis_names)

    def _resolve(logical):
        spec = []
        for name in logical:
            v = rules.get(name) if name else None
            if isinstance(v, str) and v not in names:
                v = None
            if isinstance(v, tuple):
                v = tuple(a for a in v if a in names) or None
            spec.append(v)
        return spec

    is_leaf = lambda x: isinstance(x, tuple)
    if abstract_tree is None:
        return jax.tree.map(
            lambda lg: NamedSharding(mesh, P(*_resolve(lg))),
            logical_tree,
            is_leaf=is_leaf,
        )
    return jax.tree.map(
        lambda lg, ab: NamedSharding(
            mesh, _divisible_spec(mesh, ab.shape, _resolve(lg))
        ),
        logical_tree,
        abstract_tree,
        is_leaf=is_leaf,
    )
