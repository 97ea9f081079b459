"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, the
requests the server finished are compared, every one of them up to
``check.max_requests``; beyond that the longest and a sample of the rest
drawn from the seed. Requests, not tokens, are counted, so that every slot
the window filled is in the comparison. The plain reference runs once
over each sampled prompt followed by its served tokens, and at every
position where a token was served reads how far that token's logit lies
below the reference's best. The widest such gap is compared with the
cell's limit. That is valid for greedy tokens, and every request of these
mixes is greedy.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(records: list, schedule: dict, seed: int, check: dict
           ) -> List[Tuple[List[int], List[int]]]:
    """(prompt, served tokens) of the sampled finished requests."""
    done = [r for r in records if r["finished"] and r["tokens"]]
    if not done:
        return []
    size = [len(schedule["requests"][r["i"]]["prompt"]) + len(r["tokens"])
            for r in done]
    longest = int(np.argmax(size))
    rng = np.random.default_rng([seed % (1 << 63), 0xC4EC])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    return [(schedule["requests"][done[i]["i"]]["prompt"], done[i]["tokens"])
            for i in sorted(order[:check["max_requests"]])]


def gaps(ref, model_cfg: dict, params: dict,
         picked: List[Tuple[List[int], List[int]]], seq_len: int,
         max_out: int, control: str = "") -> np.ndarray:
    """Per served token, reference best logit minus the served token's.
    Sequences pad to ``seq_len`` and reads to ``max_out`` so one program
    serves every request (padding after a sequence is never attended:
    the reference is causal).

    With ``control`` ("int8" or "fp8") the reading is the control's: the
    reference computed in that precision, teacher-forced over the same
    prompts and served tokens; at each position, the reference's best
    logit minus that of the token the lower precision puts first."""
    fn = jax.jit(functools.partial(ref.logits_at, model_cfg))
    low = jax.jit(functools.partial(ref.logits_at, model_cfg,
                                    quant=control)) if control else None
    out = []
    for prompt, served in picked:
        seq = list(prompt) + list(served[:-1])
        tokens = np.zeros((seq_len,), np.int32)
        tokens[:len(seq)] = seq
        read = np.zeros((max_out,), np.int32)
        read[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        logits = fn(params, jnp.asarray(tokens), jnp.asarray(read))
        best = jnp.max(logits, -1)
        if low is None:
            pick = jnp.asarray(np.pad(served, (0, max_out - len(served))))
        else:
            pick = jnp.argmax(low(params, jnp.asarray(tokens),
                                  jnp.asarray(read)), -1)
        got = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        out.append(np.asarray(best - got)[:len(served)])
    return np.concatenate(out) if out else np.zeros((0,))
