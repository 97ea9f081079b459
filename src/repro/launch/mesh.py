"""Production mesh definitions (TPU v5e pods).

make_production_mesh is a FUNCTION so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types: sharding propagates from
    the placed inputs and the logical constraints, as every caller here
    assumes (the installed jax defaults to Explicit axes)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model per pod; (2,16,16) pod x data x model across two."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh over forced-host devices for multi-device tests."""
    return make_auto_mesh((data, model), ("data", "model"))


def parse_mesh_arg(spec: str):
    """CLI '--mesh DPxTP' (e.g. '1x4') -> (data, model) Mesh; None for 1x1.

    Shared by the train and serve launchers so both validate the device
    count the same way instead of surfacing a raw jax error."""
    try:
        dp, tp = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DPxTP (e.g. 1x4), got {spec!r}")
    if dp < 1 or tp < 1:
        raise SystemExit(f"--mesh dims must be >= 1, got {spec!r}")
    if dp * tp == 1:
        return None
    n_dev = len(jax.devices())
    if dp * tp > n_dev:
        raise SystemExit(
            f"--mesh {spec} needs {dp * tp} devices, have {n_dev} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "for local testing)"
        )
    return make_auto_mesh((dp, tp), ("data", "model"))
