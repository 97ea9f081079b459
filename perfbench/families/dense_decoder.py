"""The dense decoder family: llama-style layers, every linear tiled.

What the benchmark knows of this family's layout, for a configuration
file that names it (``"family": "dense_decoder"``):

- ``KEYS``, the keys of the file that fix the model's math: the harness
  hands these, and only these, to the reference, the check and the
  traffic generator;
- ``draw`` / ``build``, the shipped serving tree (layout of each tiled
  linear in ``harness/weights.py``): one stacked segment ``seg0`` of
  ``num_hidden_layers`` layers, each with attention (``wq``, ``wk``,
  ``wv``, ``wo``; biases on q/k/v with ``qkv_bias``) and an MLP (``up``,
  ``down``, and ``gate`` when gated), an f32 embedding table, the final
  norm, and a tiled head unless the embeddings are tied;
- ``program``, the program's serving model for the file;
- the operations its steps need (``step_flops``) and the shapes of the
  linears the program's tile kernels serve (``tiled_shapes``), by which
  ``harness/kernels.py`` counts each kernel call.

Scales keep the residual stream healthy over the full depth: the two
projections that write into the residual stream (attention output, MLP
down) get a gain of ``1 / sqrt(2 * layers)``, as in GPT-2's
initialisation, so that the stream's variance stays bounded and a small
rounding is not amplified layer after layer; query projections get a gain
of 2 so that attention is sharp enough for what the cache holds to matter.

Attention counts ``4 * heads * head_dim`` operations per (query, key) pair
(scores and the weighted sum of values); norms and the embedding lookup do
no matrix work and are left out.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import counting, weights

KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size",
        "vocab_size", "hidden_act", "gated_mlp", "norm", "rope_theta",
        "qkv_bias", "tie_word_embeddings", "tbn_p")

Q_GAIN = 2.0


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


def draw(cfg: dict, key: jax.Array) -> dict:
    """The whole serving parameter tree (trace inside ``jax.jit``)."""
    L, d, p = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["tbn_p"]
    for n_out, n_in, _ in linears(cfg).values():
        if n_in % 32 or n_out % p:
            raise ValueError(f"({n_out}, {n_in}) cannot ship as p={p} rows")
    ln = cfg["norm"] == "layernorm"
    keys = iter(jax.random.split(key, 64))
    seg = {"norm1": weights.norm(next(keys), (L,), d, ln),
           "norm2": weights.norm(next(keys), (L,), d, ln),
           "mixer": {}, "ffn": {}}
    for (grp, name), (n_out, n_in, gain) in sorted(linears(cfg).items()):
        seg[grp][name] = weights.tiled(next(keys), (L,), n_out, n_in, p,
                                       gain)
        if cfg["qkv_bias"] and name in ("wq", "wk", "wv"):
            seg[grp][name]["b"] = 0.2 * jax.random.normal(
                next(keys), (L, n_out), jnp.float32)
    params = {
        "embed": {"table": jax.random.normal(
            next(keys), (cfg["vocab_size"], d), jnp.float32)},
        "final_norm": weights.norm(next(keys), (), d, ln),
        "seg0": seg,
    }
    if not cfg["tie_word_embeddings"]:
        params["head"] = weights.tiled(next(keys), (), cfg["vocab_size"], d,
                                       p, 1.0)
    return params


def build(cfg: dict, seed: int) -> dict:
    """The tree drawn on the default device from ``seed``."""
    return weights.build(draw, cfg, seed)


def program(cfgd: dict):
    """The program's serving model for a configuration file."""
    from repro.configs import build_model, get_config
    from repro.nn.context import SERVE, ModelContext

    arch = get_config(cfgd["program_arch"])
    cfg = dataclasses.replace(
        arch, name=cfgd["name"], n_layers=cfgd["num_hidden_layers"],
        d_model=cfgd["hidden_size"], n_heads=cfgd["num_attention_heads"],
        n_kv=cfgd["num_key_value_heads"], head_dim=cfgd["head_dim"],
        d_ff=cfgd["intermediate_size"], vocab=cfgd["vocab_size"],
        activation=cfgd["hidden_act"], gated_mlp=cfgd["gated_mlp"],
        norm=cfgd["norm"], qkv_bias=cfgd["qkv_bias"],
        rope_theta=float(cfgd["rope_theta"]),
        tie_embeddings=cfgd["tie_word_embeddings"],
        tbn=dataclasses.replace(arch.tbn, p=cfgd["tbn_p"],
                                min_size=cfgd["tbn_min_size"]))
    return build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE))


# ------------------------------------------------------------- counts --
def layer_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(r, n_in) of every tiled linear of one layer."""
    return [(n_out // cfg["tbn_p"], n_in)
            for n_out, n_in, _ in linears(cfg).values()]


def head_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["vocab_size"] // cfg["tbn_p"], cfg["hidden_size"]


def step_flops(cfg: dict, rows: int, key_pairs: int, logit_rows: int) -> float:
    """Operations one model step needs: ``rows`` token rows through every
    layer, ``key_pairs`` (query, key) pairs of causal attention summed
    over layers' queries, and the head on ``logit_rows`` rows."""
    p = cfg["tbn_p"]
    per_layer = sum(counting.tiled_linear(rows, r, n_in, p)[0]
                    for r, n_in in layer_shapes(cfg))
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * key_pairs
    r, n_in = head_shape(cfg)
    return (cfg["num_hidden_layers"] * (per_layer + attn)
            + counting.tiled_linear(logit_rows, r, n_in, p)[0])


def tiled_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(r, n_in) of every linear the tile kernels serve: each layer's and
    the head."""
    return layer_shapes(cfg) + [head_shape(cfg)]
