"""Serving benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 perfbench/run.py --workload granite-8b.chat --seed 7 \
        --seconds 40 --trace 0

Set-up draws the cell's weights on the chip from ``--seed`` and builds the
program's serving model, both as the configuration's family says
(``perfbench/families/<family>.py``), puts the program's
``BatchedEngine`` behind its ``EngineServer``, compiles the two tick
executables (checking that both hold the Pallas tile kernels), and
sends warm-up requests over the wire. Then a child process that imports
no JAX replays the cell's traffic against ``POST /generate`` for
``--seconds``; that is the measured window. After it, the served tokens
of a sample of finished requests are compared with a plain f32 reference
(``harness/check.py``), and the last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, ``breakdown`` (``--trace 1``) and ``check``,
the numbers compared with their limits.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "perfbench"))
sys.path.insert(0, str(ROOT / "src"))

# the fraction of the window after which a traced run starts its trace
# (a traffic file's ``trace_at`` overrides it), and how long the trace lasts
TRACE_AT = 0.4
TRACE_SECONDS = 3.0


def server_config(traffic: dict, seed: int):
    from repro.serve.engine import ServeConfig

    s = traffic["server"]
    return ServeConfig(
        n_slots=s["n_slots"], max_len=s["max_len"],
        chunk_tokens=s["chunk_tokens"], page_tokens=s["page_tokens"],
        pool_pages=s.get("pool_pages"), prefix_cache=s["prefix_cache"],
        seed=seed % (1 << 31))


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 3


def main(argv=None, *, allow_cpu: bool = False, fault=None,
         control: str = "") -> int:
    """One run. ``allow_cpu``, ``fault`` (called with the engine before
    the window, to break the timed path) and ``control`` (judge the
    reference computed in that lower precision, on the same sample, in
    the program's place) are for the harness's own tests and the
    control's readings; the benchmark's runs use none."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from harness import check, serving, trace, traffic as gen
    from harness.peaks import peaks
    from harness.registry import Registry, read_all

    reg = Registry(ROOT)
    cell = reg.workload(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        return fail(f"no TPU: JAX found {devs[0].platform} "
                    f"({devs[0].device_kind}); nothing was run")
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"found {len(devs)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    on_tpu = devs[0].platform == "tpu"

    from repro.serve.engine import BatchedEngine

    cfgd = reg.config(cell["config"])
    fam = reg.family(cfgd["family"])
    mcfg = {k: cfgd[k] for k in fam.KEYS}
    mix = reg.traffic(cell["traffic"])
    compiles = serving.Compiles()

    phases = {"start": time.perf_counter() - T_PROC}
    params = fam.build(mcfg, args.seed)
    phases["weights"] = time.perf_counter() - T_PROC
    model = fam.program(cfgd)
    eng = BatchedEngine(model, params, server_config(mix, args.seed))
    compile_s = eng.warmup()
    phases["ticks"] = time.perf_counter() - T_PROC
    hlo = {name: eng._aot[name].as_text()
           for name in ("decode_tick", "extend_tick")}
    for name, text in hlo.items():
        if on_tpu and "tpu_custom_call" not in text:
            raise RuntimeError(f"{name} compiled without a Pallas kernel")
    spans = serving.Spans(eng)
    if fault is not None:
        fault(eng)
    schedule = gen.generate(mix, args.seed, args.seconds, mcfg["vocab_size"])
    workdir = tempfile.mkdtemp(prefix="perfbench-")
    try:
        win = asyncio.run(_serve(
            eng, schedule, args.seconds, workdir, mix,
            args.trace == 1))
        mem = devs[0].memory_stats() or {}
        memory_peak = int(mem.get("peak_bytes_in_use", 0))
        tr = None
        if win.trace_dir is not None:
            path = trace.find_xplane(win.trace_dir)
            tr = trace.load(path) if path else None
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    setup_s = win.start - T_PROC
    window_spans = spans.between(win.start, win.close)
    serving.require_spans(window_spans, win.records, args.seconds)
    del eng
    gc.collect()

    # correctness: once the window has closed and the engine is freed
    picked = check.sample(win.records, schedule, args.seed, mix["check"])
    ref = reg.reference(cfgd["reference"])
    g = check.gaps(ref, mcfg, params, picked, mix["server"]["max_len"],
                   mix["output"]["max"])
    max_gap = float(g.max()) if g.size else float("inf")
    due = [r for r in win.records
           if r["due_s"] is not None and r["due_s"] < args.seconds]
    failed = [r for r in due if r["error"] or
              (r["status"] is not None and r["status"] != 200)]

    run = SimpleNamespace(
        records=win.records, seconds=args.seconds, setup_s=setup_s,
        start=win.start, close=win.close, stats0=win.stats0,
        stats1=win.stats1, spans=window_spans,
        trace=tr, trace_window=win.trace_window, cfg=mcfg, family=fam,
        mix=mix, peaks=peaks(devs[0].device_kind) if on_tpu else None, hlo=hlo,
        schedule=schedule)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_all(reg, kind, args.workload, run)

    print(f"set-up: process start {phases['start']:.3f} s, weights drawn "
          f"by {phases['weights']:.3f} s, ticks ready by "
          f"{phases['ticks']:.3f} s, window opened at {setup_s:.3f} s",
          file=sys.stderr)
    if g.size:
        print(f"gaps: {g.size} tokens from {len(picked)} requests; mean "
              f"{float(g.mean())!r}, not the reference's best "
              f"{float((g > 0).mean())!r}, max {max_gap!r}", file=sys.stderr)
    if control:
        # the control in the program's place: judged instead of it
        g = check.gaps(ref, mcfg, params, picked, mix["server"]["max_len"],
                       mix["output"]["max"], control=control)
        max_gap = float(g.max()) if g.size else float("inf")
        print(f"control {control}: {g.size} tokens; mean "
              f"{float(g.mean())!r}, not the reference's best "
              f"{float((g > 0).mean())!r}, max {max_gap!r}", file=sys.stderr)
    limit = mix["check"]["limit"]
    correct = bool(g.size >= mix["check"]["min_tokens"]
                   and max_gap <= limit and not failed)
    lateness = [r["send_s"] - r["due_s"] for r in due
                if r["send_s"] is not None]
    print(f"window: {len(due)} requests due, "
          f"{sum(r['finished'] for r in due)} finished; generator late by "
          f"median {statistics.median(lateness or [0]) * 1e3:.3f} ms, max "
          f"{max(lateness or [0]) * 1e3:.3f} ms; compiles in window "
          f"{compiles.between(win.start, win.close)}; tick compiles "
          f"{ {k: round(v, 3) for k, v in compile_s.items()} }",
          file=sys.stderr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(due),
              "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and tr is not None:
        busy = sum(e - s for d in tr.ops
                   for s, e in trace.busy_intervals(tr, d)) / 1e9
        device["busy_s"] = busy / max(tr.n_devices, 1)
        device["window_s"] = win.trace_window[1] - win.trace_window[0]
        result["breakdown"] = trace.breakdown(tr)
    result["check"] = {
        "max_logit_gap": {"value": max_gap, "limit": limit},
        "tokens_compared": {"value": int(g.size),
                            "limit": mix["check"]["min_tokens"]},
        "failed_requests": {"value": len(failed), "limit": 0},
    }
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


async def _serve(eng, schedule, seconds, workdir, mix, traced):
    from harness import serving
    from repro.serve.server import EngineServer, ServerConfig

    srv = EngineServer(eng, ServerConfig(port=0))
    await srv.start(aot=False)
    try:
        chunk = mix["server"]["chunk_tokens"]
        rng_prompts = [[(7 * i + j) % 1000 for j in range(chunk + 16 + i)]
                       for i in range(2)]
        await serving.warm_requests(srv.port, rng_prompts, 4)
        if schedule["setup"]:
            await serving.warm_requests(srv.port, schedule["setup"], 1)
        return await serving.run_window(
            srv, schedule, seconds, workdir,
            trace_at=mix.get("trace_at", TRACE_AT) * seconds if traced
            else None,
            trace_len=min(TRACE_SECONDS, (1 - TRACE_AT) * seconds / 2))
    finally:
        await srv.close()


if __name__ == "__main__":
    sys.exit(main())
