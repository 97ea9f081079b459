"""Mean gap between consecutive output tokens of a request, in ms, over
every gap that closed inside the window: the window's token time, extend
ticks included, spread over the tokens served."""
import numpy as np


def read(run):
    g = []
    for r in run.records:
        ts = [t for t in r["token_s"] if t < run.seconds]
        g += [b - a for a, b in zip(ts, ts[1:])]
    return 1e3 * float(np.mean(g)) if g else None
