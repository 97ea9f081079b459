"""Shipped serving weights, drawn on the device from the seed.

The benchmark makes the weights itself and hands them to the program, so
the reference reads arrays that the program did not make. They are drawn
straight in the shipped form (packed tile words and alphas; no training
masters) by one jitted program per configuration, in the dtypes that are
served.

Layout (the program's serving parameter tree, one stacked entry per
layer): each tiled linear of output width ``n_out`` and input width
``n_in`` is ``{"tile": int32 (L, r, n_in / 32), "alpha": f32 (L, p)}``
with ``r = n_out / p``; output row ``j * r + i`` is ``alpha[j]`` times
unique row ``i``, whose element ``k`` is +1 where bit ``k % 32`` of word
``k // 32`` is set and -1 where it is not.

Scales keep the residual stream healthy over the full depth: every
linear's alphas are ``gain / sqrt(n_in)`` times a factor in [0.75, 1.25]
that differs per replica, so a mixed-up replica or alpha shows in the
logits. The two projections that write into the residual stream (attention
output, MLP down) get a gain of ``1 / sqrt(2 * layers)``, as in GPT-2's
initialisation, so that the stream's variance stays bounded and a small
rounding is not amplified layer after layer; query projections get a gain
of 2 so that attention is sharp enough for what the cache holds to matter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_GAIN = 2.0


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number: its low 64 bits, as two
    32-bit words (``PRNGKey`` takes only what fits a signed 32-bit int
    without x64)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def linears(cfg: dict) -> dict:
    """``{path: (n_out, n_in, gain)}`` of every tiled linear of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, f = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["intermediate_size"])
    res = 1.0 / np.sqrt(2 * cfg["num_hidden_layers"])
    out = {
        ("mixer", "wq"): (h * hd, d, Q_GAIN),
        ("mixer", "wk"): (kv * hd, d, 1.0),
        ("mixer", "wv"): (kv * hd, d, 1.0),
        ("mixer", "wo"): (d, h * hd, res),
        ("ffn", "up"): (f, d, 1.0),
        ("ffn", "down"): (d, f, res),
    }
    if cfg["gated_mlp"]:
        out[("ffn", "gate")] = (f, d, 1.0)
    return out


def _tiled(key, lead, n_out, n_in, p, gain):
    kt, ka = jax.random.split(key)
    words = n_in // 32
    tile = jax.lax.bitcast_convert_type(
        jax.random.bits(kt, (*lead, n_out // p, words), jnp.uint32),
        jnp.int32)
    alpha = (gain / np.sqrt(n_in)) * jax.random.uniform(
        ka, (*lead, p), jnp.float32, 0.75, 1.25)
    return {"tile": tile, "alpha": alpha}


def _norm(key, lead, d, layernorm):
    ks, kb = jax.random.split(key)
    out = {"scale": jax.random.uniform(ks, (*lead, d), jnp.float32, 0.8, 1.2)}
    if layernorm:
        out["bias"] = 0.1 * jax.random.normal(kb, (*lead, d), jnp.float32)
    return out


def draw(cfg: dict, key: jax.Array) -> dict:
    """The whole serving parameter tree (trace inside ``jax.jit``)."""
    L, d, p = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["tbn_p"]
    for n_out, n_in, _ in linears(cfg).values():
        if n_in % 32 or n_out % p:
            raise ValueError(f"({n_out}, {n_in}) cannot ship as p={p} rows")
    ln = cfg["norm"] == "layernorm"
    keys = iter(jax.random.split(key, 64))
    seg = {"norm1": _norm(next(keys), (L,), d, ln),
           "norm2": _norm(next(keys), (L,), d, ln),
           "mixer": {}, "ffn": {}}
    for (grp, name), (n_out, n_in, gain) in sorted(linears(cfg).items()):
        seg[grp][name] = _tiled(next(keys), (L,), n_out, n_in, p, gain)
        if cfg["qkv_bias"] and name in ("wq", "wk", "wv"):
            seg[grp][name]["b"] = 0.2 * jax.random.normal(
                next(keys), (L, n_out), jnp.float32)
    params = {
        "embed": {"table": jax.random.normal(
            next(keys), (cfg["vocab_size"], d), jnp.float32)},
        "final_norm": _norm(next(keys), (), d, ln),
        "seg0": seg,
    }
    if not cfg["tie_word_embeddings"]:
        params["head"] = _tiled(next(keys), (), cfg["vocab_size"], d, p, 1.0)
    return params


def build(cfg: dict, seed: int) -> dict:
    """Draw on the default device in one jitted program; blocks until the
    arrays exist."""
    params = jax.jit(lambda k: draw(cfg, k))(seed_key(seed))
    return jax.block_until_ready(params)
