"""The tile kernels' calls in the compiled ticks, and their rooflines.

Each Pallas call is one ``custom-call`` with ``custom_call_target=
"tpu_custom_call"`` in the tick's compiled HLO. Its ``backend_config``
carries the kernel's serialized body, which names the kernel function
(``_matvec_kernel`` of ``kernels/tiled_matvec.py``, ``_matmul_kernel`` of
``kernels/tiled_matmul.py``) more often than any other, and its ``operand_layout_constraints`` give
the call's padded operand shapes: activations ``(m, k)`` and packed tile
``(r, k / 32)``. A trace's operation events carry the same instruction
names, so each event is matched to its call here.
"""
from __future__ import annotations

import base64
import re
from typing import Dict, Optional, Tuple

from harness import counting, trace

KERNELS = {"tiled_matvec": b"_matvec_kernel", "tiled_matmul": b"_matmul_kernel"}
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def calls(hlo_text: str) -> Dict[str, Tuple[str, tuple, tuple]]:
    """``{instruction: (kernel, x shape, tile shape)}`` of the tile
    kernels' calls in one compiled module."""
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
        counts = {k: raw.count(fn) for k, fn in KERNELS.items()}
        kernel = max(counts, key=counts.get) if any(counts.values()) \
            else None
        olc = line.split("operand_layout_constraints=", 1)
        if kernel is None or len(olc) < 2:
            continue
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in _SHAPE.findall(olc[1].split("}}", 1)[0])]
        if len(shapes) >= 2:
            out[name.group(1)] = (kernel, shapes[0], shapes[1])
    return out


def roofline(run, kernel: str) -> Optional[float]:
    """Sum over the traced calls of ``kernel`` of the least time the chip
    could take (the larger of operations over peak FLOP/s and bytes over
    peak bandwidth, counted by ``counting.tiled_linear``), over the sum of
    their device time, in %."""
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    srv = run.mix["server"]
    rows = {"decode_tick": srv["n_slots"],
            "extend_tick": srv["n_slots"] * srv["chunk_tokens"]}
    least = took = 0.0
    for tick, text in run.hlo.items():
        table = {k: v for k, v in calls(text).items() if v[0] == kernel}
        if not table:
            continue
        for lst in tr.ops.values():
            for op in lst:
                if op.name not in table or \
                        not trace.module_matches(op.module, tick):
                    continue
                _, x, tile = table[op.name]
                m = min(x[0], rows[tick], srv["n_slots"]) \
                    if kernel == "tiled_matvec" else min(x[0], rows[tick])
                r, n_in = counting.match_linear(run.cfg, tile[0], x[1])
                flops, nbytes = counting.tiled_linear(
                    m, r, n_in, run.cfg["tbn_p"])
                least += max(flops / run.peaks.flops_bf16,
                             nbytes / run.peaks.hbm_bw)
                took += (op.end - op.start) / 1e9
    return 100.0 * least / took if took > 0 else None
