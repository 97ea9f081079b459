"""Median gap between consecutive output tokens of a request, in ms, over
every gap that closed inside the window."""
import numpy as np


def _gaps(run):
    out = []
    for r in run.records:
        ts = [t for t in r["token_s"] if t < run.seconds]
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def read(run):
    g = _gaps(run)
    return 1e3 * float(np.percentile(g, 50)) if g else None
