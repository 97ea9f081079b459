"""Plain float32 reference of a dense decoder served from packed tiles.

Imports nothing of the program. It reads the shipped arrays that the
benchmark drew (``harness/weights.py`` documents their layout) and
computes, in straightforward ``jax.numpy`` with no cache, no batching and
no kernels, the logits of one sequence at the positions asked for:

- embedding lookup in f32;
- per layer, pre-norm attention (RoPE, rotate-half, on the first and
  second halves of each head; grouped keys and values; causal softmax
  over every earlier position; scale 1/sqrt(head_dim)) and pre-norm MLP
  (``act(gate(x)) * up(x)`` when gated, ``act(up(x))`` otherwise), each
  added to the residual stream;
- final norm, then the tiled head.

A tiled linear computes the tile's ``r`` unique rows and replicates them:
output ``j * r + i`` is ``alpha[j] * (x . row_i)``; this is the same
product as with the whole ``(n_out, n_in)`` weight, without building it.

With ``quant`` it is a control: the same computation with every stored
value rounded to int8 or fp8 (one scale per row) where the program rounds
to bf16, a precision below the configuration's.

Departures from the published models, all shared with the program as it
is configured: no sliding window (contexts stay within it), no biases
outside q/k/v, RMSNorm epsilon 1e-6 and LayerNorm epsilon 1e-5.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def unpack_rows(tile: jax.Array, n_in: int) -> jax.Array:
    """int32 (r, n_in / 32) -> f32 (r, n_in) of +-1."""
    bits = (tile.astype(jnp.uint32)[:, :, None]
            >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(tile.shape[0], -1)[:, :n_in].astype(jnp.float32) \
        * 2.0 - 1.0


def lower(x: jax.Array, quant: str) -> jax.Array:
    """x rounded to the control's precision, one scale per row: ``int8``
    (symmetric, absmax / 127) or ``fp8`` (float8_e4m3fn, absmax / 448)."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    if quant == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(p: dict, x: jax.Array) -> jax.Array:
    """x (T, n_in) -> (T, p * r), replica-major."""
    rows = unpack_rows(p["tile"], x.shape[-1])
    z = x @ rows.T                                     # (T, r)
    y = (p["alpha"][:, None, None] * z[None]).transpose(1, 0, 2)
    y = y.reshape(x.shape[0], -1)
    if "b" in p:
        y = y + p["b"]
    return y


def norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x (T, heads, hd) at positions 0..T-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return x / (1.0 + jnp.exp(-x))
    if kind == "gelu":   # tanh form, as published (gelu_pytorch_tanh)
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(kind)


def block(cfg: dict, p: dict, x: jax.Array, rnd) -> jax.Array:
    """One layer; ``rnd`` rounds every value the layer stores (identity
    for the reference itself)."""
    T = x.shape[0]

    def lin(q, v):
        return rnd(linear(q, v))

    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    h = rnd(norm(p["norm1"], x, cfg["norm"]))
    q = rnd(rope(lin(p["mixer"]["wq"], h).reshape(T, H, hd),
                 cfg["rope_theta"]))
    k = rnd(rope(lin(p["mixer"]["wk"], h).reshape(T, K, hd),
                 cfg["rope_theta"]))
    v = lin(p["mixer"]["wv"], h).reshape(T, K, hd)
    k = jnp.repeat(k, H // K, axis=1)                  # head i reads KV i//G
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = rnd(jnp.einsum("hst,thd->shd", rnd(jax.nn.softmax(s, -1)), v))
    x = rnd(x + lin(p["mixer"]["wo"], a.reshape(T, H * hd)))
    h = rnd(norm(p["norm2"], x, cfg["norm"]))
    f = p["ffn"]
    if cfg["gated_mlp"]:
        u = rnd(act(lin(f["gate"], h), cfg["hidden_act"]) * lin(f["up"], h))
    else:
        u = rnd(act(lin(f["up"], h), cfg["hidden_act"]))
    return rnd(x + lin(f["down"], u))


def logits_at(cfg: dict, params: dict, tokens: jax.Array,
              read: jax.Array, quant: str = "") -> jax.Array:
    """f32 logits (len(read), vocab) of one sequence ``tokens`` (T,) at
    positions ``read``. Layer by layer (one scan step holds one layer's
    activations), at the highest matmul precision. With ``quant`` ("int8"
    or "fp8") every value stored between operations (embedding, norm,
    linear, attention and MLP outputs, the residual stream; the points at
    which the program rounds to bf16) is rounded to that precision
    instead: the control."""
    def rnd(v):
        return lower(v, quant) if quant else v

    with jax.default_matmul_precision("highest"):
        x = rnd(params["embed"]["table"][tokens].astype(jnp.float32))

        def step(x, pl):
            return block(cfg, pl, x, rnd), None

        x, _ = jax.lax.scan(step, x, params["seg0"])
        h = rnd(norm(params["final_norm"], x[read], cfg["norm"]))
        if "head" in params:
            return linear(params["head"], h)
        return h @ params["embed"]["table"].T
