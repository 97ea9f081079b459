"""Readings of the engine's two tick executables, from the trace and from
the benchmark's spans around the calls that run them."""
from __future__ import annotations

from typing import Optional

from harness import trace

SPAN = {"decode_tick": "decode", "extend_tick": "extend"}


def device_ms(run, tick: str) -> Optional[float]:
    """Device time per run of ``tick``, in ms: the mean of its
    executable's runs on the trace's ``XLA Modules`` line."""
    if run.trace is None:
        return None
    runs = [o for lst in run.trace.modules.values() for o in lst
            if trace.module_matches(o.module, tick)]
    if not runs:
        return None
    return 1e3 * sum(o.end - o.start for o in runs) / 1e9 / len(runs)


def mfu(run, tick: str) -> Optional[float]:
    """Operations the window's ``tick`` calls needed (the family's
    ``step_flops``), over the host-clock time of the benchmark's spans
    around them times the chip's peak, in %."""
    if run.peaks is None:
        return None
    spans = [s for s in run.spans if s.name == SPAN[tick]]
    wall = sum(s.t1 - s.t0 for s in spans)
    if not spans or wall <= 0:
        return None
    flops = sum(run.family.step_flops(run.cfg, s.rows, s.key_pairs,
                                      s.logit_rows) for s in spans)
    return 100.0 * flops / (wall * run.peaks.flops_bf16)
