"""Shipped serving weights, drawn on the device from the seed.

The benchmark makes the weights itself and hands them to the program, so
the reference reads arrays that the program did not make. They are drawn
straight in the shipped form (packed tile words and alphas; no training
masters) by one jitted program per configuration, in the dtypes that are
served. Each model family (``perfbench/families/<family>.py``) lays out
its own tree and draws its leaves with the helpers here.

Layout of a tiled linear, in every family: output width ``n_out``, input
width ``n_in``, any leading axes ``lead`` (layers, experts) is ``{"tile":
int32 (*lead, r, n_in / 32), "alpha": f32 (*lead, p)}`` with ``r = n_out
/ p``; output row ``j * r + i`` is ``alpha[j]`` times unique row ``i``,
whose element ``k`` is +1 where bit ``k % 32`` of word ``k // 32`` is set
and -1 where it is not. Every linear's alphas are ``gain / sqrt(n_in)``
times a factor in [0.75, 1.25] that differs per replica, so a mixed-up
replica or alpha shows in the logits; the family chooses each gain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number: its low 64 bits, as two
    32-bit words (``PRNGKey`` takes only what fits a signed 32-bit int
    without x64)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def tiled(key, lead, n_out, n_in, p, gain):
    """One tiled linear (or a stack of them along ``lead``)."""
    kt, ka = jax.random.split(key)
    words = n_in // 32
    tile = jax.lax.bitcast_convert_type(
        jax.random.bits(kt, (*lead, n_out // p, words), jnp.uint32),
        jnp.int32)
    alpha = (gain / np.sqrt(n_in)) * jax.random.uniform(
        ka, (*lead, p), jnp.float32, 0.75, 1.25)
    return {"tile": tile, "alpha": alpha}


def norm(key, lead, d, layernorm):
    """An RMSNorm's scale, or a LayerNorm's scale and bias."""
    ks, kb = jax.random.split(key)
    out = {"scale": jax.random.uniform(ks, (*lead, d), jnp.float32, 0.8, 1.2)}
    if layernorm:
        out["bias"] = 0.1 * jax.random.normal(kb, (*lead, d), jnp.float32)
    return out


def build(draw, cfg: dict, seed: int) -> dict:
    """``draw(cfg, key)`` on the default device in one jitted program;
    blocks until the arrays exist."""
    params = jax.jit(lambda k: draw(cfg, k))(seed_key(seed))
    return jax.block_until_ready(params)
