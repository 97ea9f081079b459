import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. compiles the FULL config (chunked attention = the real memory plan),
     prints memory_analysis() (proves it fits) and cost_analysis(),
  2. optionally (--roofline) compiles depth-1 and depth-2 variants with
     exact (unchunked) attention and extrapolates scan trip counts to get
     true per-cell FLOPs/bytes/collective bytes (see roofline.analysis),
  3. writes one JSON record under results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --roofline
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import cell_applicable
from repro.distributed.sharding import axis_rules
from repro.launch.cells import build_cell, scan_trips
from repro.launch.mesh import make_production_mesh
from repro.roofline import hw
from repro.roofline.analysis import (
    analyze_compiled,
    combine_extrapolated,
    model_flops,
    subtract,
)

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile(arch, shape, mesh, *, depth=None, exact=False):
    plan = build_cell(arch, shape, mesh, depth=depth, exact_flops=exact)
    rules = dict(get_config(arch).rules_override)
    with axis_rules(mesh, rules):
        lowered = jax.jit(
            plan["fn"],
            in_shardings=plan["in_shardings"],
            out_shardings=plan["out_shardings"],
            donate_argnums=plan.get("donate_argnums", ()),
        ).lower(*plan["args"])
        compiled = lowered.compile()
    return compiled


def run_cell(arch: str, shape: str, mesh_kind: str, roofline: bool = True) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape]
    ok, reason = cell_applicable(cfg, cell)
    rec = dict(arch=arch, shape=shape, mesh=mesh_kind)
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    compiled = _compile(arch, shape, mesh)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()
    mem_rec = dict(
        argument_bytes=int(mem.argument_size_in_bytes),
        output_bytes=int(mem.output_size_in_bytes),
        temp_bytes=int(mem.temp_size_in_bytes),
        alias_bytes=int(mem.alias_size_in_bytes),
    )
    peak = (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
    )
    full_terms = analyze_compiled(compiled, hw.V5E)
    rec.update(
        status="ok",
        chips=chips,
        compile_s=round(t_full, 1),
        memory=mem_rec,
        peak_bytes_per_device=int(peak),
        fits_hbm=bool(peak <= hw.peaks(hw.V5E).hbm_bytes),
        full_cost=full_terms.as_dict(),
    )

    if roofline:
        t1 = time.time()
        c1 = _compile(arch, shape, mesh, depth=1, exact=True)
        c2 = _compile(arch, shape, mesh, depth=2, exact=True)
        terms1 = analyze_compiled(c1, hw.V5E)
        terms2 = analyze_compiled(c2, hw.V5E)
        delta = subtract(terms2, terms1)
        trips = scan_trips(cfg)
        total = combine_extrapolated(terms1, delta, trips - 1)
        # the grad-accumulation scan body is also visited once by
        # cost_analysis: scale to the full global batch (over-counts the
        # once-per-step optimizer update by ~1-2%; noted in EXPERIMENTS.md)
        accum = cfg.grad_accum if cell.kind == "train" else 1
        if accum > 1:
            total = combine_extrapolated(total, total, accum - 1)
        n_active = active_params(cfg)
        mf = model_flops(cfg, cell, n_active)
        hlo_flops_global = total.flops * chips
        rec.update(
            roofline=total.as_dict(),
            roofline_compile_s=round(time.time() - t1, 1),
            scan_trips=trips,
            n_params_active=n_active,
            model_flops_global=mf,
            useful_flops_ratio=(mf / hlo_flops_global) if hlo_flops_global else None,
        )
    return rec


def active_params(cfg) -> int:
    """Parameters touched per token (MoE: routed top-k of E + shared)."""
    from repro.configs import build_model
    from repro.nn.context import TRAIN, ModelContext

    model = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN))
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(model.abstract()):
        keys = [str(getattr(p, "key", "")) for p in path]
        n = int(np.prod(leaf.shape))
        # routed expert banks carry a leading E dim under seg*/ffn/{up,down,gate}/w
        if (
            cfg.moe is not None
            and len(leaf.shape) == 3
            and any(k in ("up", "down", "gate") for k in keys)
            and "shared" not in keys
            and leaf.shape[0] == cfg.moe.n_experts
        ):
            n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--roofline", action="store_true", default=True)
    ap.add_argument("--no-roofline", dest="roofline", action="store_false")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            name = f"{arch}__{shape}__{mk}"
            out = pathlib.Path(args.out) if args.out else RESULTS / f"{name}.json"
            try:
                rec = run_cell(arch, shape, mk, roofline=args.roofline)
            except Exception as e:  # a failing cell is a bug in the system
                rec = dict(arch=arch, shape=shape, mesh=mk, status="error",
                           error=f"{type(e).__name__}: {e}",
                           traceback=traceback.format_exc()[-4000:])
                failures += 1
            out.write_text(json.dumps(rec, indent=2))
            summary = {k: rec.get(k) for k in
                       ("status", "compile_s", "peak_bytes_per_device", "fits_hbm")}
            print(f"{name}: {summary}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
