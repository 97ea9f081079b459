"""Plain float32 reference of the test MoE decoder served from packed tiles.

The tests drop it in as ``perfbench/references/moe_decoder.py``. Imports
nothing of the program; it takes the tiled linear, the norms, RoPE, the
activation and the control's rounding from the dense reference beside it
(``dense_decoder.py``) and computes, with no cache, no batching, no
dispatch buffers and no kernels, the logits of one sequence:

- embedding lookup in f32;
- per layer, pre-norm attention (as the dense reference's) added to the
  residual stream, then a pre-norm FFN: the first layer a gated MLP;
  every other the softmax of its f32 router over every expert, the
  ``num_experts_per_tok`` most probable experts with their probabilities
  renormalised to sum to 1 as gates, every expert computed on every
  token and the picked ones summed by gate, plus the shared gated MLP;
- final norm, then the tiled head.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp


def _dense():
    path = pathlib.Path(__file__).with_name("dense_decoder.py")
    spec = importlib.util.spec_from_file_location("moe_reference_dense", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


D = _dense()


def attend(cfg: dict, p: dict, x: jax.Array, rnd) -> jax.Array:
    T = x.shape[0]
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    h = rnd(D.norm(p["norm1"], x, "rmsnorm"))
    q = rnd(D.rope(rnd(D.linear(p["mixer"]["wq"], h)).reshape(T, H, hd),
                   cfg["rope_theta"]))
    k = rnd(D.rope(rnd(D.linear(p["mixer"]["wk"], h)).reshape(T, K, hd),
                   cfg["rope_theta"]))
    v = rnd(D.linear(p["mixer"]["wv"], h)).reshape(T, K, hd)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = rnd(jnp.einsum("hst,thd->shd", rnd(jax.nn.softmax(s, -1)), v))
    return rnd(x + rnd(D.linear(p["mixer"]["wo"], a.reshape(T, H * hd))))


def mlp(cfg: dict, f: dict, h: jax.Array, rnd) -> jax.Array:
    u = rnd(D.act(rnd(D.linear(f["gate"], h)), cfg["hidden_act"])
            * rnd(D.linear(f["up"], h)))
    return rnd(D.linear(f["down"], u))


def moe(cfg: dict, f: dict, h: jax.Array, rnd) -> jax.Array:
    probs = jax.nn.softmax(h @ f["router"].T, -1)            # (T, E)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = top / jnp.sum(top, -1, keepdims=True)
    banks = {k: f[k] for k in ("up", "gate", "down")}
    every = jax.vmap(lambda b: mlp(cfg, b, h, rnd))(banks)   # (E, T, d)
    picked = jnp.take_along_axis(every.transpose(1, 0, 2), idx[..., None],
                                 axis=1)                     # (T, k, d)
    y = rnd(jnp.sum(gates[..., None] * picked, 1))
    return rnd(y + mlp(cfg, f["shared"], h, rnd))


def logits_at(cfg: dict, params: dict, tokens: jax.Array,
              read: jax.Array, quant: str = "") -> jax.Array:
    """f32 logits (len(read), vocab) of one sequence ``tokens`` (T,) at
    positions ``read``, at the highest matmul precision; with ``quant``
    every stored value rounded to that precision instead: the control."""
    def rnd(v):
        return D.lower(v, quant) if quant else v

    def layer(ffn):
        def step(x, pl):
            x = attend(cfg, pl, x, rnd)
            h = rnd(D.norm(pl["norm2"], x, "rmsnorm"))
            return rnd(x + ffn(cfg, pl["ffn"], h, rnd)), None
        return step

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"]["table"][tokens].astype(jnp.float32))
        x, _ = layer(mlp)(x, params["seg0"])
        x, _ = jax.lax.scan(layer(moe), x, params["seg1"])
        h = rnd(D.norm(params["final_norm"], x[read], "rmsnorm"))
        if "head" in params:
            return D.linear(params["head"], h)
        return h @ params["embed"]["table"].T
