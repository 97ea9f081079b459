"""Device time of a tick executable by named scope of the model.

The program wraps its serving paths in ``jax.named_scope``: ``embed``,
``attn`` (norm1 + the mixer, paged gather/scatter included; ``rec`` and
``ssm`` for the other mixer kinds), ``ffn`` (norm2 + FFN or MoE),
``head`` (final norm + logits), ``kv_layer`` (the layer scan's per-layer
cache index, barrier and update), and in the engine's ticks ``sample``
and ``merge``. A scope is a component of the ``metadata={op_name=...}``
path that the compiled HLO keeps on each instruction. A trace's
operation events carry the same instruction names, so each leaf
operation of a tick run is charged to the innermost scope its
instruction names, and to ``unscoped`` where it names none (copies the
compiler inserted carry no metadata at all). The buckets partition the
tick's leaf operation time exactly.

A reader may also name a scope that ``SCOPES`` lacks, such as a part of
one layer (``experts``, or the path ``ffn/experts``): an operation is
charged to it where it is the innermost of ``SCOPES`` and that scope in
the operation's path. Operations outside it are charged as they are
without it, so the buckets of ``SCOPES`` outside its parent read the
same.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from harness import trace

SCOPES = ("embed", "attn", "rec", "ssm", "ffn", "head", "kv_layer",
          "sample", "merge")
UNSCOPED = "unscoped"
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_of(op_name: str, extra: str = "") -> str:
    """The innermost scope of an ``op_name`` path: a component in
    ``SCOPES``, or ``extra`` (one component or a path of several) where
    it ends at or after the innermost such component."""
    parts = op_name.split("/")
    want = extra.split("/") if extra else []
    for i in range(len(parts), 0, -1):
        if want and parts[max(i - len(want), 0):i] == want:
            return extra
        if parts[i - 1] in SCOPES:
            return parts[i - 1]
    return UNSCOPED


def instruction_scopes(hlo_text: str, extra: str = "") -> Dict[str, str]:
    """``{instruction: scope}`` of every instruction of a compiled
    module's text (``extra`` as in ``scope_of``)."""
    out = {}
    for line in hlo_text.splitlines():
        name = _NAME.match(line)
        if not name:
            continue
        meta = _OP_NAME.search(line)
        out[name.group(1)] = scope_of(meta.group(1), extra) if meta \
            else UNSCOPED
    return out


def device_ms(run, tick: str, scope: str) -> Optional[float]:
    """Device time per run of ``tick`` (``decode_tick`` | ``extend_tick``)
    in the leaf operations charged to ``scope`` (one of ``SCOPES``,
    ``unscoped``, or another scope, read as the module docstring says),
    in ms. ``None`` without a trace, without a run of the tick on it, or
    for a program whose tick names no scope."""
    if run.trace is None or tick not in run.hlo:
        return None
    extra = "" if scope in SCOPES or scope == UNSCOPED else scope
    table = instruction_scopes(run.hlo[tick], extra)
    if all(s == UNSCOPED for s in table.values()):
        return None
    runs = sum(trace.module_matches(m.module, tick)
               for lst in run.trace.modules.values() for m in lst)
    if not runs:
        return None
    took = sum(o.end - o.start for lst in run.trace.ops.values()
               for o in lst if o.leaf and trace.module_matches(o.module, tick)
               and table.get(o.name, UNSCOPED) == scope)
    return 1e3 * took / 1e9 / runs
