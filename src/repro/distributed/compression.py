"""Gradient compression for the data-parallel reduction.

Two codecs + an explicit shard_map DP step builder that uses them:

  * EF-sign (1 bit/coordinate + one scalar): sign of (grad + error
    feedback), scaled by the mean magnitude; the residual stays in the
    per-worker error accumulator, which makes the method convergent
    (Karimireddy et al., "Error Feedback Fixes SignSGD").
  * int8 (8 bits/coordinate + one scalar per tensor): symmetric linear
    quantization of the local gradient before the ring reduction.

Integration contract: GSPMD's automatic gradient reduction is exact and
uncompressed; compression NEEDS the per-shard local gradients, so the
compressed path runs data-parallelism explicitly under shard_map
(``build_dp_train_step``). On the production mesh this composes as
hierarchical DP: the paper-faithful exact path in-pod, compressed ring
across the "pod" axis where links are scarce (DESIGN.md §5). TBN makes the
*parameter* side of that story free: packed tiles are what elastic rejoins
broadcast (repro.serve.weights).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



# ---------------------------------------------------------------------------
# codecs (pure per-worker math; reduction = psum of decoded payloads)
# ---------------------------------------------------------------------------
def ef_sign_encode(g: jax.Array, err: jax.Array):
    """-> (decoded payload to reduce, new error state).

    payload = sign(g + err) * mean|g + err|  (1 bit + 1 scalar on the wire)
    """
    c = g + err
    scale = jnp.mean(jnp.abs(c))
    payload = jnp.sign(c) * scale
    return payload, c - payload


def int8_encode(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (int8 codes, f32 scale). Wire cost: 8 bits + 1 scalar."""
    amax = jnp.max(jnp.abs(g))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decode(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def wire_bits(kind: str, n: int) -> int:
    """Per-worker bytes on the wire for an n-element gradient."""
    return {"none": 32 * n, "int8": 8 * n + 32, "ef_sign": n + 32}[kind]


# ---------------------------------------------------------------------------
# explicit-DP train step with compressed reduction
# ---------------------------------------------------------------------------
class CompressionState(NamedTuple):
    """Error-feedback accumulators (zeros for int8/none)."""

    error: Any

    @staticmethod
    def init(params, kind: str) -> "CompressionState":
        if kind == "ef_sign":
            z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        else:
            z = jax.tree.map(lambda p: jnp.zeros((), jnp.float32), params)
        return CompressionState(error=z)


def compressed_psum_mean(grads, err_tree, *, kind: str, axis: str):
    """Per-shard compress -> psum -> mean, inside shard_map.

    Returns (reduced grads, new error tree). ``kind`` in
    {"none", "int8", "ef_sign"}.
    """
    n = jax.lax.psum(1, axis)

    def one(g, e):
        if kind == "none":
            return jax.lax.psum(g, axis) / n, e
        if kind == "int8":
            q, s = int8_encode(g)
            dec = int8_decode(q, s)
            return jax.lax.psum(dec, axis) / n, e
        if kind == "ef_sign":
            payload, new_e = ef_sign_encode(g, e)
            return jax.lax.psum(payload, axis) / n, new_e
        raise ValueError(kind)

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(err_tree)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    red = treedef.unflatten([o[0] for o in out])
    new_err = treedef.unflatten([o[1] for o in out])
    return red, new_err


def build_dp_train_step(
    loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    *,
    compression: str = "ef_sign",
    dp_axis: str = "data",
    clip_norm: Optional[float] = 1.0,
):
    """Explicit data-parallel train step under shard_map.

    Params/opt state are replicated across ``dp_axis``; each shard computes
    local grads on its batch slice, the reduction goes through the chosen
    codec, and every shard applies the identical update. The returned step
    takes and returns a (TrainState, CompressionState) pair.

    This is the integration point for the compressed cross-pod reduction:
    on the (pod, data, model) mesh call it with dp_axis="pod" around a
    step whose inner GSPMD reduction covers "data" only.
    """
    from repro.optim import clip_by_global_norm
    from repro.train.step import TrainState

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state: TrainState, comp: CompressionState, batch):
        (loss, aux), grads = grad_fn(state.params, batch)
        grads, new_err = compressed_psum_mean(
            grads, comp.error, kind=compression, axis=dp_axis
        )
        loss = jax.lax.pmean(loss, dp_axis)
        gnorm = jnp.zeros(())
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        new_params, new_opt = optimizer.update(
            grads, state.opt_state, state.params
        )
        metrics = {"loss": loss, "grad_norm": gnorm}
        return (
            TrainState(new_params, new_opt, state.step + 1),
            CompressionState(error=new_err),
            metrics,
        )

    rep = P()
    batch_spec = {"x": P(dp_axis), "y": P(dp_axis)}
    return jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(rep, rep, batch_spec),
            out_specs=(rep, rep, rep),
            check_vma=False,
        )
    )
