"""One general traffic generator, driven by a traffic file's parameters.

Every seed offers the same requests: lengths are the quantiles of their
distribution at evenly spaced probabilities, arrival gaps likewise, and
one fixed shuffle (``order_seed`` of the traffic file, 0 by default) puts
them in order. The seed draws what the requests say, their token ids (and
the benchmark draws the weights from it too). So every seed does the same
work at the same times, and a run's spread is the system's, not the
draw's: with ~10 requests a window, an order drawn from the seed changes
how often prefill lands inside a decode gap, and with it the ITL tail.

A traffic file (``perfbench/traffic/<name>.json``) holds:

- ``arrivals``: ``{"kind": "poisson", "rate": r}`` (open loop, requests
  due at stratified exponential gaps of mean 1/r) or ``{"kind":
  "backlog", "waiting_per_slot": w, "requests_per_second_max": c}``
  (offline batch: the client keeps ``n_slots * (1 + w)`` requests
  outstanding all through the window, from a backlog sized for ``c``
  completions a second).
- ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
  log-normal, clipped.
- optional ``documents``: ``{"live", "min", "max", "asks_min",
  "asks_max"}``: each prompt is a shared document (lengths evenly spread
  over ``[min, max]``) followed by a question whose length is drawn from
  ``prompt``; ``live`` documents are in use at a time, each asked between
  ``asks_min`` and ``asks_max`` times, its asks spread over its life;
  with ``"warm": true`` the first ``live`` documents are each served
  once in set-up, so the window starts with them cached, as a running
  service would.
- ``server``: the engine's settings for this load (``n_slots``,
  ``max_len``, ``chunk_tokens``, ``page_tokens``, ``pool_pages``,
  ``prefix_cache``).
- ``check``: the correctness sample and its limit.
- optional ``trace_at``: the fraction of the window at which a traced run
  starts its few seconds of trace (0.4 by default), placed where the
  mix's work of interest runs.

Pure numpy and the standard library: the load client imports nothing
that touches the chip.
"""
from __future__ import annotations

import itertools
import json
import math
import pathlib
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


def load(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def lognormal_quantiles(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at evenly spaced probabilities of a clipped log-normal
    (median ``spec["median"]``, log-sd ``spec["sigma"]``)."""
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(min(max(v, spec["min"]), spec["max"]))
    return out


def exponential_gaps(rate: float, n: int) -> List[float]:
    """``n`` inter-arrival gaps at evenly spaced probabilities of an
    exponential of mean ``1/rate``."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def n_requests(traffic: dict, seconds: float) -> int:
    """Requests one window offers: those due inside it (open loop), or a
    backlog long enough that the queue never runs dry (batch)."""
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, int(round(arr["rate"] * seconds)))
    if arr["kind"] == "backlog":
        return int(arr["requests_per_second_max"] * seconds) + \
            traffic["server"]["n_slots"] * (1 + arr["waiting_per_slot"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The schedule one run offers: ``{"mode", "outstanding", "requests":
    [{"due_s", "prompt", "max_tokens"}], "setup": [prompt]}``, requests
    sorted by due time; ``setup`` prompts are served before the window."""
    rng = np.random.default_rng(seed % (1 << 64))
    order = np.random.default_rng(traffic.get("order_seed", 0))
    n = n_requests(traffic, seconds)
    arr = traffic["arrivals"]
    p_len = order.permutation(lognormal_quantiles(traffic["prompt"], n))
    o_len = order.permutation(lognormal_quantiles(traffic["output"], n))
    if arr["kind"] == "poisson":
        due = np.cumsum(order.permutation(exponential_gaps(arr["rate"], n)))
        due = due - due[0]               # the first request opens the window
        mode, outstanding = "open", 0
    else:
        due = np.zeros(n)
        mode = "backlog"
        outstanding = traffic["server"]["n_slots"] * (
            1 + arr["waiting_per_slot"])
    docs = traffic.get("documents")
    prompts: List[List[int]] = []
    setup: List[List[int]] = []
    if docs is None:
        for plen in p_len:
            prompts.append(rng.integers(0, vocab, int(plen)).tolist())
    else:
        prompts, bodies = _document_prompts(docs, p_len, order, rng, vocab)
        if docs.get("warm"):
            setup = [b + rng.integers(0, vocab, 16).tolist()
                     for b in bodies[:docs["live"]]]
    reqs = [{"due_s": float(d), "prompt": p, "max_tokens": int(o)}
            for d, p, o in zip(due, prompts, o_len)]
    return {"mode": mode, "outstanding": int(outstanding), "requests": reqs,
            "setup": setup}


def _document_prompts(docs: dict, q_len, order, rng, vocab):
    """Shared documents, ``docs["live"]`` of them in use at a time.

    Request ``i`` asks the document in slot ``i % live``; a slot takes a
    new document once its current one has been asked its quota (quotas
    cycle from ``asks_min`` to ``asks_max``). So a document's asks are
    ``live`` requests apart, the first a miss and the rest hits while it
    stays cached, and every seed asks the same pattern over the same
    document lengths. Returns the prompts and the document bodies."""
    live = docs["live"]
    quotas = itertools.cycle(range(docs["asks_min"], docs["asks_max"] + 1))
    current, left, doc_of = [None] * live, [0] * live, []
    n_docs = 0
    for i in range(len(q_len)):
        slot = i % live
        if left[slot] == 0:
            current[slot], left[slot] = n_docs, next(quotas)
            n_docs += 1
        left[slot] -= 1
        doc_of.append(current[slot])
    d_len = order.permutation(
        evenly_spread(docs["min"], docs["max"], n_docs))
    bodies = [rng.integers(0, vocab, int(L)).tolist() for L in d_len]
    return [bodies[d] + rng.integers(0, vocab, int(q)).tolist()
            for d, q in zip(doc_of, q_len)], bodies


def evenly_spread(lo: int, hi: int, n: int) -> List[int]:
    """``n`` lengths evenly spread over ``[lo, hi]``."""
    if n == 1:
        return [(lo + hi) // 2]
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]
