"""The Pallas kernels' calls in the compiled ticks, and their rooflines.

Each Pallas call is one ``custom-call`` with ``custom_call_target=
"tpu_custom_call"`` in the tick's compiled HLO. Its ``backend_config``
carries the kernel's serialized body, which names the kernel function
(``_matvec_kernel`` of ``kernels/tiled_matvec.py``, say) more often than
any other, and its ``operand_layout_constraints`` give the call's padded
operand shapes, in the order the call takes them (a grouped kernel's
scalar-prefetch operands first). A trace's operation events carry the
same instruction names, so each event is matched to its call here.

``TILE_KERNELS`` names the program's tile kernels, which every family
serves: ``{kernel: (function name, count)}``, where ``count(fam, cfg,
shapes, rows, slots)`` gives ``(operations, bytes)`` of a call with those
operand shapes in a tick that computes at most ``rows`` token rows for
``slots`` slots. A tile call is matched to the tiled layer it serves
among the family's ``tiled_shapes(cfg)``. A family that brings a kernel
of its own adds it, in the same form, as ``KERNELS`` of
``perfbench/families/<family>.py``.
"""
from __future__ import annotations

import base64
import re
from typing import Dict, List, Optional, Tuple

from harness import counting, trace

_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _tile(fam, cfg: dict, shapes: list, most: int):
    """A tile call: activations ``(m, k)``, packed tile ``(r, k / 32)``,
    computing at most ``most`` of its ``m`` rows."""
    x, tile = shapes[0], shapes[1]
    r, n_in = counting.closest(fam.tiled_shapes(cfg), tile[0], x[1])
    return counting.tiled_linear(min(x[0], most), r, n_in, cfg["tbn_p"])


def _matvec(fam, cfg: dict, shapes: list, rows: int, slots: int):
    """A decode-path call: one row a slot at most."""
    return _tile(fam, cfg, shapes, min(rows, slots))


def _matmul(fam, cfg: dict, shapes: list, rows: int, slots: int):
    """A prefill-path call."""
    return _tile(fam, cfg, shapes, rows)


TILE_KERNELS = {
    "tiled_matvec": (b"_matvec_kernel", _matvec),    # kernels/tiled_matvec.py
    "tiled_matmul": (b"_matmul_kernel", _matmul),    # kernels/tiled_matmul.py
}


def calls(hlo_text: str, names: Dict[str, bytes]
          ) -> Dict[str, Tuple[str, List[tuple]]]:
    """``{instruction: (kernel, operand shapes)}`` of the calls of the
    kernels ``names`` (``{kernel: function name}``) in one compiled
    module."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, body = _NAME.match(line), _BODY.search(line)
        if not name or not body:
            continue
        raw = base64.b64decode(body.group(1) + "=" * (-len(body.group(1)) % 4))
        # the body names its own kernel function several times (symbol,
        # locations) and may name another in a location string once
        counts = {k: raw.count(fn) for k, fn in names.items()}
        kernel = max(counts, key=counts.get) if any(counts.values()) \
            else None
        olc = line.split("operand_layout_constraints=", 1)
        if kernel is None or len(olc) < 2:
            continue
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in _SHAPE.findall(olc[1].split("}}", 1)[0])]
        if shapes:
            out[name.group(1)] = (kernel, shapes)
    return out


def roofline(run, kernel: str) -> Optional[float]:
    """Sum over the traced calls of ``kernel`` of the least time the chip
    could take (the larger of operations over peak FLOP/s and bytes over
    peak bandwidth), over the sum of their device time, in %. ``None``
    for a kernel the cell's family does not serve."""
    tr = run.trace
    kernels = {**TILE_KERNELS, **getattr(run.family, "KERNELS", {})}
    if tr is None or run.peaks is None or kernel not in kernels:
        return None
    names = {k: fn for k, (fn, _) in kernels.items()}
    count = kernels[kernel][1]
    srv = run.mix["server"]
    rows = {"decode_tick": srv["n_slots"],
            "extend_tick": srv["n_slots"] * srv["chunk_tokens"]}
    least = took = 0.0
    for tick, text in run.hlo.items():
        table = {k: v for k, v in calls(text, names).items()
                 if v[0] == kernel}
        if not table:
            continue
        for lst in tr.ops.values():
            for op in lst:
                if op.name not in table or \
                        not trace.module_matches(op.module, tick):
                    continue
                flops, nbytes = count(run.family, run.cfg,
                                      table[op.name][1], rows[tick],
                                      srv["n_slots"])
                least += max(flops / run.peaks.flops_bf16,
                             nbytes / run.peaks.hbm_bw)
                took += (op.end - op.start) / 1e9
    return 100.0 * least / took if took > 0 else None
