"""A mixture-of-experts decoder family, for the harness's tests.

The tests drop it in as ``perfbench/families/moe_decoder.py``, beside its
reference, configuration and traffic mix, to show that a family whose
layers the harness has never seen runs through the benchmark as new files
alone. It serves the program's ``moe`` family: a first segment of one
dense layer, then a stacked segment whose FFN is ``n_routed_experts``
gated experts of width ``moe_intermediate_size`` (tiles ``(L, E, r,
words)``, alphas ``(L, E, p)``), an f32 router ``(L, E, d)`` whose
softmax picks ``num_experts_per_tok`` of them (gates renormalised), and
``n_shared_experts`` shared experts as one gated MLP. Attention is the
dense family's (grouped keys and values, RoPE); no biases.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import numpy as np

from harness import counting, weights

KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "scoring_func",
        "norm_topk_prob", "vocab_size", "hidden_act", "rope_theta",
        "tie_word_embeddings", "tbn_p")

Q_GAIN = 2.0
ROUTER_GAIN = 2.0


def _attention(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    res = 1.0 / np.sqrt(2 * cfg["num_hidden_layers"])
    return {"wq": (h * hd, d, Q_GAIN), "wk": (kv * hd, d, 1.0),
            "wv": (kv * hd, d, 1.0), "wo": (d, h * hd, res)}


def _mlp(cfg: dict, f: int) -> dict:
    d = cfg["hidden_size"]
    res = 1.0 / np.sqrt(2 * cfg["num_hidden_layers"])
    return {"up": (f, d, 1.0), "gate": (f, d, 1.0), "down": (d, f, res)}


def _check(cfg: dict) -> None:
    if cfg["first_k_dense_replace"] != 1:
        raise ValueError("the program's moe family has one dense layer first")
    if cfg["scoring_func"] != "softmax" or not cfg["norm_topk_prob"]:
        raise ValueError("the program routes by softmax, gates renormalised")


def _layer(cfg: dict, keys, lead: tuple, ffn: dict, experts: tuple) -> dict:
    d, p = cfg["hidden_size"], cfg["tbn_p"]
    return {"norm1": weights.norm(next(keys), lead, d, False),
            "norm2": weights.norm(next(keys), lead, d, False),
            "mixer": {k: weights.tiled(next(keys), lead, *v[:2], p, v[2])
                      for k, v in sorted(_attention(cfg).items())},
            "ffn": {k: weights.tiled(next(keys), lead + experts, *v[:2], p,
                                     v[2])
                    for k, v in sorted(ffn.items())}}


def draw(cfg: dict, key: jax.Array) -> dict:
    """The whole serving parameter tree (trace inside ``jax.jit``)."""
    _check(cfg)
    L, d, p = cfg["num_hidden_layers"] - 1, cfg["hidden_size"], cfg["tbn_p"]
    E = cfg["n_routed_experts"]
    keys = iter(jax.random.split(key, 64))
    seg1 = _layer(cfg, keys, (L,), _mlp(cfg, cfg["moe_intermediate_size"]),
                  (E,))
    seg1["ffn"]["router"] = (ROUTER_GAIN / np.sqrt(d)) * jax.random.normal(
        next(keys), (L, E, d), np.float32)
    seg1["ffn"]["shared"] = {
        k: weights.tiled(next(keys), (L,), *v[:2], p, v[2])
        for k, v in sorted(_mlp(cfg, cfg["moe_intermediate_size"]
                                * cfg["n_shared_experts"]).items())}
    params = {
        "embed": {"table": jax.random.normal(
            next(keys), (cfg["vocab_size"], d), np.float32)},
        "final_norm": weights.norm(next(keys), (), d, False),
        "seg0": _layer(cfg, keys, (), _mlp(cfg, cfg["intermediate_size"]),
                       ()),
        "seg1": seg1,
    }
    if not cfg["tie_word_embeddings"]:
        params["head"] = weights.tiled(next(keys), (), cfg["vocab_size"], d,
                                       p, 1.0)
    return params


def build(cfg: dict, seed: int) -> dict:
    return weights.build(draw, cfg, seed)


def program(cfgd: dict):
    """The program's serving model for a configuration file."""
    from repro.configs import build_model, get_config
    from repro.configs.base import MoESpec
    from repro.nn.context import SERVE, ModelContext

    _check(cfgd)
    arch = get_config(cfgd["program_arch"])
    cfg = dataclasses.replace(
        arch, name=cfgd["name"], family="moe",
        n_layers=cfgd["num_hidden_layers"], d_model=cfgd["hidden_size"],
        n_heads=cfgd["num_attention_heads"],
        n_kv=cfgd["num_key_value_heads"], head_dim=cfgd["head_dim"],
        d_ff=cfgd["intermediate_size"], vocab=cfgd["vocab_size"],
        moe=MoESpec(n_experts=cfgd["n_routed_experts"],
                    top_k=cfgd["num_experts_per_tok"],
                    n_shared=cfgd["n_shared_experts"],
                    d_ff_expert=cfgd["moe_intermediate_size"],
                    first_dense=True),
        activation=cfgd["hidden_act"], gated_mlp=True, norm="rmsnorm",
        qkv_bias=False, rope_theta=float(cfgd["rope_theta"]),
        tie_embeddings=cfgd["tie_word_embeddings"],
        tbn=dataclasses.replace(arch.tbn, p=cfgd["tbn_p"],
                                min_size=cfgd["tbn_min_size"]))
    return build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE))


# ------------------------------------------------------------- counts --
def _shapes(cfg: dict, lins: dict) -> List[Tuple[int, int]]:
    return [(n_out // cfg["tbn_p"], n_in) for n_out, n_in, _ in lins.values()]


def head_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["vocab_size"] // cfg["tbn_p"], cfg["hidden_size"]


def step_flops(cfg: dict, rows: int, key_pairs: int, logit_rows: int) -> float:
    """Operations one model step needs: per token row, every layer's
    attention projections, the dense layer's MLP, and in each MoE layer
    the router, the ``num_experts_per_tok`` experts it picks and the
    shared experts; ``4 * heads * head_dim`` per (query, key) pair and
    layer; the head on ``logit_rows`` rows."""
    p, L = cfg["tbn_p"], cfg["num_hidden_layers"]
    moe = L - cfg["first_k_dense_replace"]

    def tiled(lins, m):
        return sum(counting.tiled_linear(m, r, k, p)[0]
                   for r, k in _shapes(cfg, lins))
    f = cfg["moe_intermediate_size"]
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * key_pairs
    router = 2.0 * rows * cfg["n_routed_experts"] * cfg["hidden_size"]
    experts = (tiled(_mlp(cfg, f), rows * cfg["num_experts_per_tok"])
               + tiled(_mlp(cfg, f * cfg["n_shared_experts"]), rows))
    r, n_in = head_shape(cfg)
    return (L * (tiled(_attention(cfg), rows) + attn)
            + (L - moe) * tiled(_mlp(cfg, cfg["intermediate_size"]), rows)
            + moe * (router + experts)
            + counting.tiled_linear(logit_rows, r, n_in, p)[0])


def tiled_shapes(cfg: dict) -> List[Tuple[int, int]]:
    """(r, n_in) of the linears the tile kernels serve: attention, the
    dense and shared MLPs, the head (the experts' banks run outside
    them)."""
    f = cfg["moe_intermediate_size"]
    return (_shapes(cfg, _attention(cfg))
            + _shapes(cfg, _mlp(cfg, cfg["intermediate_size"]))
            + _shapes(cfg, _mlp(cfg, f * cfg["n_shared_experts"]))
            + [head_shape(cfg)])
