"""95th percentile of the gaps between consecutive output tokens, in ms,
over every gap that closed inside the window."""
import numpy as np


def read(run):
    g = []
    for r in run.records:
        ts = [t for t in r["token_s"] if t < run.seconds]
        g += [b - a for a, b in zip(ts, ts[1:])]
    return 1e3 * float(np.percentile(g, 95)) if g else None
