"""Share of the time inside the benchmark's spans around
``BatchedEngine.step`` in which no operation ran on the device, in %,
from the trace (host spans and device operations share its clock)."""
from harness import trace


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    steps = trace.union([(s.start, s.end) for s in tr.spans
                         if s.name == "bench.step"])
    total = sum(e - s for s, e in steps)
    if total <= 0:
        return None
    busy = sum(trace.covered(trace.busy_intervals(tr, d), steps)
               for d in tr.ops) / len(tr.ops)
    return 100.0 * (1.0 - busy / total)
