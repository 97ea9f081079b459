"""Compile the serving kernels for a described TPU v5e, at real widths.

Interpret mode (every other kernel test) checks numerics but none of the
Mosaic rules: block shapes whose last two dims are not (8, 128)-aligned
or whole, integer ops the TPU has no lowering for, per-step VMEM over
the scoped limit. These tests compile each kernel of the serving path
for a v5e chip that is described, not attached, at granite-8b widths
(bf16, d_model 4096, d_ff 14336, TBN p=8) and a ResNet-18 stage for the
conv kernel, and assert the Pallas kernel (``tpu_custom_call``) is in
the compiled program. Nothing runs; no chip is needed.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time, so a test worker
that is not given this file must not load it.
"""
import base64
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.tiling import plan_conv_tiling, plan_tiling
from repro.distributed.sharding import axis_rules
from repro.kernels.ops import (
    _dense_unique_local,
    tiled_conv_infer,
    tiled_dense_infer,
)

P_TILE = 8
# (n_in, n_out) of every granite-8b tiled layer: q/o, k/v, gate/up, down,
# and the lm head (r = n_out / 8 unique rows per tile)
GRANITE_LAYERS = {
    "wq": (4096, 4096),
    "wk": (4096, 1024),
    "gate": (4096, 14336),
    "down": (14336, 4096),
    "head": (4096, 49152),
}
# the tile kernels' function names; the benchmark attributes a Pallas call
# to the one its serialized body names most
KERNEL_FNS = {"matvec": b"_matvec_kernel", "matmul": b"_matmul_kernel"}
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels pick interpret mode from ``jax.default_backend()``,
    which here is the CPU; compile them as the chip would run them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_name_counts(hlo_text):
    """Per ``tpu_custom_call`` of a compiled module, how often its
    serialized body names each tile kernel function."""
    out = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = _BODY.search(line).group(1)
        raw = base64.b64decode(body + "=" * (-len(body) % 4))
        out.append({k: raw.count(fn) for k, fn in KERNEL_FNS.items()})
    return out


def _rows(n_in, n_out, sharding):
    r = n_out // P_TILE
    return jax.ShapeDtypeStruct((r, -(-n_in // 32)), jnp.int32,
                                sharding=sharding)


@pytest.mark.parametrize(
    "m,compute_path",
    [(256, "float"), (512, "float"), (16, "float"), (16, "int8"),
     (16, "xnor")],
    ids=["matmul-prefill", "matmul-extend", "matvec-decode", "int8-decode",
         "xnor-decode"],
)
@pytest.mark.parametrize("layer", sorted(GRANITE_LAYERS))
def test_dense_kernels_compile_at_granite_widths(
        one_chip, on_tpu, layer, m, compute_path):
    """The serve dispatch (ops._dense_unique_local): padding, block choice
    and kernel, for prefill m=256 and an extend tick's m=512 (8 slots x
    chunk 64; matmul) and decode m=16 (8 slots, bf16-rounded: matvec and
    the two integer paths), at the default blocks with no VMEM limit
    raised, so Mosaic refuses blocks and unpack temporaries that overrun
    the default scoped VMEM. A float call's body must name its own
    kernel function more often than the other's: the benchmark
    attributes each call to a kernel by that count."""
    n_in, n_out = GRANITE_LAYERS[layer]
    x = jax.ShapeDtypeStruct((m, n_in), jnp.bfloat16, sharding=one_chip)
    compiled = _compile(
        lambda x, t: _dense_unique_local(
            x, t, n_in=n_in, use_pallas=True, block_m=128, block_r=128,
            block_k=4096, compute_path=compute_path),
        x, _rows(n_in, n_out, one_chip))
    if compute_path == "float":
        (counts,) = _kernel_name_counts(compiled.as_text())
        own, other = ("matvec", "matmul") if m <= 32 else ("matmul", "matvec")
        assert counts[own] > counts[other], counts


@pytest.mark.parametrize("m", [256, 8])
def test_tensor_parallel_dense_compiles_on_four_chips(topo, on_tpu, m):
    """TP=4 over the model axis: per-shard kernels on r/4 rows. The k/v
    projection leaves 32 rows per shard, under one 128-row block."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n_in, n_out = GRANITE_LAYERS["wk"]
    spec = plan_tiling((n_out, n_in), p=P_TILE, min_size=1,
                       alpha_mode="tile", alpha_source="W")
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((m, n_in), jnp.bfloat16, sharding=rep)
    alpha = jax.ShapeDtypeStruct((spec.n_alpha,), jnp.float32, sharding=rep)
    tile = _rows(n_in, n_out, NamedSharding(mesh, P("model", None)))
    with axis_rules(mesh):
        _compile(lambda x, t, a: tiled_dense_infer(
            x, t, a, spec, use_pallas=True), x, tile, alpha)


# ResNet-18 stages: (channels in and out, input side, stride of the
# stage's first 3x3 conv)
RESNET18_CONVS = [(64, 56, 1), (128, 56, 2), (256, 14, 1), (512, 7, 1)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("c,hw,stride", RESNET18_CONVS)
def test_conv_kernel_compiles_at_resnet18_widths(
        one_chip, on_tpu, c, hw, stride, dtype):
    spec = plan_tiling((c, c, 3, 3), p=4, min_size=1, alpha_mode="tile",
                       alpha_source="W")
    plan = plan_conv_tiling(spec)
    x = jax.ShapeDtypeStruct((1, hw, hw, c), dtype, sharding=one_chip)
    packed = jax.ShapeDtypeStruct(plan.packed_shape(), jnp.int32,
                                  sharding=one_chip)
    alpha = jax.ShapeDtypeStruct((spec.n_alpha,), jnp.float32,
                                 sharding=one_chip)
    _compile(lambda x, t, a: tiled_conv_infer(
        x, t, a, spec, stride=(stride, stride), padding="SAME",
        use_pallas=True), x, packed, alpha)
