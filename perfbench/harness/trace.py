"""Reduction of a profiler trace to intervals the metric readers use.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. On a TPU the device planes are
named ``/device:TPU:<n>``; their line ``XLA Modules`` holds one event per
executable run (``jit__decode_tick(<fingerprint>)``), and line ``XLA
Ops`` one event per operation, named by its HLO text (``%fusion.3 =
bf16[...] fusion(...)``). An operation belongs to the executable run that
contains it. The host plane holds the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``).

The device's timestamps are not on the host's clock (a v5e trace shows
executions that start before the host span that launched them). So the
host spans are moved onto the device clock by the one offset that puts
every tick execution inside the benchmark's span around the call that
launched it (``bench.decode`` runs ``decode_tick``, ``bench.extend``
runs ``extend_tick``); where no single offset does, by the median of the
offsets that centre each execution in its span.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import statistics
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
LAUNCHES = {"bench.decode": "decode_tick", "bench.extend": "extend_tick"}


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str                           # HLO instruction or span name
    module: str                         # executable name ('' when none)
    leaf: bool = True                   # False: holds other operations


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]            # device index -> its operations
    modules: Dict[int, List[Op]]        # device index -> executable runs
    spans: List[Op]                     # host spans bench.*, device clock

    @property
    def n_devices(self) -> int:
        return len(self.ops)


def find_xplane(directory: str) -> Optional[str]:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] ...`` -> ``fusion.3``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def module_name(text: str) -> str:
    """``jit__decode_tick(123)`` -> ``jit__decode_tick``."""
    return text.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    spans: List[Op] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and \
                name[len("/device:TPU:"):].isdigit():
            dev = int(name[len("/device:TPU:"):])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [Op(e.start_ns, e.end_ns, op_name(e.name), "")
                                for e in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [Op(e.start_ns, e.end_ns,
                                       module_name(e.name),
                                       module_name(e.name))
                                    for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans += [Op(e.start_ns, e.end_ns, e.name, "")
                          for e in line.events if e.name.startswith("bench.")]
    for dev in ops:
        ops[dev].sort(key=lambda o: (o.start, -o.end))
        mark_containers(ops[dev])
        mods = sorted(modules.get(dev, []), key=lambda o: o.start)
        modules[dev] = mods
        starts = [m.start for m in mods]
        for o in ops[dev]:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start < mods[i].end:
                o.module = mods[i].module
    spans.sort(key=lambda o: o.start)
    first = min(ops) if ops else None
    delta = host_offset(spans, modules.get(first, []))
    spans = [Op(s.start - delta, s.end - delta, s.name, "") for s in spans]
    return Trace(ops, modules, spans)


def mark_containers(ops: List[Op]) -> None:
    """Mark the operations (a while loop, a call) whose interval holds
    others on the same line; sorted by start, widest first."""
    open_: List[Op] = []
    for o in ops:
        while open_ and open_[-1].end <= o.start:
            open_.pop()
        if open_ and o.end <= open_[-1].end:
            open_[-1].leaf = False
        open_.append(o)


def host_offset(spans: List[Op], modules: List[Op]) -> float:
    """Host clock minus device clock (ns), from the tick executions and
    the benchmark's spans around the calls that launched them, paired in
    order (with the pairing shifted by up to three where the trace's
    edges cut one list short)."""
    host = [s for s in spans if s.name in LAUNCHES]
    dev = [m for m in modules
           if any(m.module.endswith(t) for t in LAUNCHES.values())]
    best = None
    for k in range(-3, 4):
        pairs = [(h, dev[i + k]) for i, h in enumerate(host)
                 if 0 <= i + k < len(dev)]
        pairs = [(h, d) for h, d in pairs
                 if d.module.endswith(LAUNCHES[h.name])]
        if not pairs:
            continue
        lo = max(h.start - d.start for h, d in pairs)
        hi = min(h.end - d.end for h, d in pairs)
        centre = statistics.median(
            (h.start + h.end - d.start - d.end) / 2 for h, d in pairs)
        fit = (len(pairs), lo <= hi)
        if best is None or fit > best[0]:
            best = (fit, (lo + hi) / 2 if lo <= hi else centre)
    return best[1] if best else 0.0


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(busy: List[Interval], spans: List[Interval]) -> float:
    """ns of ``spans`` (disjoint, sorted) that ``busy`` (disjoint, sorted)
    covers."""
    total, j = 0.0, 0
    for s, e in spans:
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            total += min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
    return total


def busy_intervals(trace: Trace, dev: int) -> List[Interval]:
    return union([(o.start, o.end) for o in trace.ops.get(dev, [])])


def module_matches(module: str, tick: str) -> bool:
    """Whether an executable is the engine's ``tick`` (``decode_tick`` or
    ``extend_tick``): jit names it ``jit__<function>``."""
    return module.endswith(tick)


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The ``n`` device operations that took most time (by executable and
    op name, summed over the chips and averaged; loops and calls that
    hold other operations left out), and the ``n`` longest
    idle gaps of device 0, named by the innermost benchmark span that
    covered each gap's middle (``host.none`` where none did)."""
    per: Dict[str, float] = {}
    ndev = max(trace.n_devices, 1)
    for dev, lst in trace.ops.items():
        for o in (o for o in lst if o.leaf):
            key = f"{o.module}/{o.name}" if o.module else o.name
            per[key] = per.get(key, 0.0) + (o.end - o.start) / 1e9 / ndev
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    busy = busy_intervals(trace, min(trace.ops) if trace.ops else 0)
    longest = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                      in zip(busy, busy[1:])), reverse=True)[:n]
    idle = []
    for dur, e0, s1 in longest:
        mid = (e0 + s1) / 2
        inner = [sp for sp in trace.spans if sp.start <= mid < sp.end]
        name = min(inner, key=lambda sp: sp.end - sp.start).name \
            if inner else "host.none"
        idle.append([name, dur / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
