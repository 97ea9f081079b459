"""Batched serving driver with packed-tile weights and chunked prefill.

    python -m repro.launch.serve --arch granite-8b --reduced \\
        --requests 8 --max-tokens 16 --chunk-tokens 32

Or boot the async HTTP/SSE front-end instead of draining a synthetic
batch (``POST /generate``, ``GET /stats``, ``GET /healthz``; Ctrl-C to
stop):

    python -m repro.launch.serve --arch granite-8b --reduced \\
        --serve --port 8000

The driver dispatches on the config's model family (see ``--help`` for
the matrix): decoder-only families (dense / moe / ssm / hybrid / vlm)
drive token prompts; the encdec family additionally feeds each request a
synthetic source-frame clip and exercises the ENCODE phase + encoder
reuse (``--enc-sources`` distinct clips cycled over the batch):

    python -m repro.launch.serve --arch seamless-m4t-large-v2 --reduced \\
        --requests 6 --enc-tokens 16 --enc-sources 2

``--aot`` (default on in ``--serve`` mode) AOT-compiles the decode and
extend tick executables at startup so the FIRST request pays no
trace/compile inside its TTFT; ``--no-aot`` measures the difference.

``--priorities`` turns on class-aware admission (interactive > batch,
prefix-aware queue jumping with an aging floor) and ``--preempt``
additionally lets a waiting interactive request park a decoding batch
slot and resume it later byte-exactly (DESIGN.md §6.4); requests choose
a class with the HTTP body's ``"priority"`` field.

Tensor-parallel serving shards each layer's packed tile rows over the
model mesh axis (DESIGN.md §5):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python -m repro.launch.serve --arch granite-8b --reduced --mesh 1x4

(`--mesh DPxTP`; on a real TPU slice the devices are the chips and the
XLA_FLAGS trick is unnecessary — it only fakes a multi-device host for
local testing.)

Flow: init TRAIN masters (or restore a checkpoint), export the SERVE
representation (packed tile bits + alpha scalars — repro.serve.weights),
stand up the slot-based BatchedEngine (mesh-placed when --mesh is given)
and drain a batch of synthetic prompts, timing every engine tick. Prints
the compression of the shipped weights vs the masters, the per-device
resident tile bytes, engine throughput, and a TTFT / inter-token-latency
report — the tail-latency numbers the chunked-prefill scheduler exists
to protect (`--chunk-tokens` bounds how much prompt work any one tick
carries beside the live decodes).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import build_model, get_config
from repro.ft.checkpoint import latest_step, restore_into
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import parse_mesh_arg
from repro.nn import module as mod
from repro.nn.context import SERVE, TRAIN, ModelContext
from repro.serve.engine import BatchedEngine, ServeConfig
from repro.serve.sampling import SamplingParams
from repro.serve.servable import SERVABLE_FAMILIES, UnservableModelError
from repro.serve.weights import (
    export_serving_params,
    per_device_tile_bytes,
    serving_bytes,
    tile_serving_bytes,
)


def latency_report(reqs, tick_ends):
    """Per-request TTFT and inter-token latencies from the engine's
    token_steps tick indices + the driver's per-tick wall clock.

    tick_ends[i] is the cumulative wall time at the end of tick i; a
    token emitted at tick t therefore landed by tick_ends[t]."""
    ttfts, itls = [], []
    for r in reqs:
        if not r.token_steps:
            continue
        ttfts.append(tick_ends[r.token_steps[0]])
        for a, b in zip(r.token_steps, r.token_steps[1:]):
            itls.append(tick_ends[b] - tick_ends[a])
    return ttfts, itls


def main(argv=None):
    family_matrix = "servable model families:\n" + "\n".join(
        f"  {k:<8}{v}" for k, v in SERVABLE_FAMILIES.items()
    )
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=family_matrix,
    )
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore TRAIN masters before exporting")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="prefill chunk width == per-tick token budget "
                         "(clamped to --max-len)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="attention KV pool page size (must divide "
                         "--max-len)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool capacity in pages (default: the "
                         "dense-equivalent slots * max_len / page_tokens)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix-trie shared-prefix reuse across admissions "
                         "(--no-prefix-cache disables)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common tokens to every prompt "
                         "(a synthetic system prompt — makes the prefix "
                         "cache line non-trivial)")
    ap.add_argument("--enc-tokens", type=int, default=None,
                    help="encoder capacity in source frames (encdec "
                         "family only; default --max-len)")
    ap.add_argument("--enc-sources", type=int, default=2,
                    help="distinct synthetic source clips cycled over "
                         "the batch (encdec family; >1 exercises "
                         "encoder-output reuse)")
    ap.add_argument("--compute-path", default="float",
                    choices=["float", "int8", "xnor"],
                    help="dense serve compute: float (byte-parity "
                         "reference), int8 (quantized activations, "
                         "integer MACs) or xnor (sign-binarized "
                         "activations, XNOR+popcount on the packed tile "
                         "words) — the integer paths apply to decode "
                         "ticks; outputs are approximate vs float")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None,
                    help="engine-default top-k (per-request params override)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="1x1",
                    help="DPxTP serving mesh, e.g. 1x4 (default single device)")
    ap.add_argument("--serve", action="store_true",
                    help="boot the async HTTP/SSE front-end instead of "
                         "draining a synthetic batch")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port (0 = OS-assigned, printed at startup)")
    ap.add_argument("--max-queued", type=int, default=64,
                    help="admission-queue capacity; a full queue returns "
                         "HTTP 429 (--serve mode)")
    ap.add_argument("--priorities", action="store_true",
                    help="class-aware admission (interactive > batch, "
                         "prefix-aware queue jumping, aging floor) instead "
                         "of FIFO; requests pick a class via the "
                         "'priority' field / SamplingParams.priority")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt-and-resume: a waiting interactive "
                         "request may park a decoding batch slot "
                         "(snapshot + retained pages, restored "
                         "byte-exactly); implies --priorities")
    ap.add_argument("--default-priority", default="batch",
                    help="class for requests that don't set one "
                         "(interactive | batch)")
    ap.add_argument("--aot", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="AOT-compile the tick executables at startup "
                         "(default: on with --serve, off otherwise)")
    ap.add_argument("--telemetry", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serving telemetry: metric registry, request "
                         "spans, tick phase timing, retrace detector "
                         "(--no-telemetry for overhead-sensitive runs; "
                         "tokens are byte-identical either way)")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    help="print a one-line telemetry report every S "
                         "seconds (--serve mode; 0 disables)")
    ap.add_argument("--trace-log", default=None, metavar="FILE",
                    help="drain the structured trace-event ring "
                         "(submit/admit/preempt/resume/finish/retrace) "
                         "to FILE as JSON lines at shutdown")
    ap.add_argument("--trace-events", type=int, default=4096,
                    help="trace-event ring capacity (with --trace-log; "
                         "oldest events drop past it)")
    args = ap.parse_args(argv)
    if args.stats_interval < 0:
        raise SystemExit(
            f"--stats-interval must be >= 0: {args.stats_interval}")
    if args.trace_log and not args.telemetry:
        raise SystemExit("--trace-log requires --telemetry (the ring is "
                         "fed from the telemetry call sites)")
    mesh = parse_mesh_arg(args.mesh)
    if args.shared_prefix + 12 > args.max_len:
        # 12 = the max random tail length below; fail before minutes of
        # model build/compile, not at the first submit()
        raise SystemExit(
            f"--shared-prefix {args.shared_prefix} + tail (<=12) exceeds "
            f"--max-len {args.max_len}"
        )

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # family dispatch: every SERVABLE_FAMILIES key rides the same
    # BatchedEngine; anything else fails with the menu attached
    family = getattr(cfg, "family", "dense")
    if family not in SERVABLE_FAMILIES:
        raise UnservableModelError(f"config family {family!r}")
    encdec = family == "encdec"
    if encdec and args.serve:
        raise SystemExit(
            "--serve (HTTP front-end) carries token prompts only; the "
            "encdec family needs per-request source frames — drive it "
            "with the synthetic batch (drop --serve)"
        )
    if encdec and args.enc_sources < 1:
        raise SystemExit(f"--enc-sources must be >= 1: {args.enc_sources}")

    use_compile_cache()
    t_model = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN))
    s_model = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                            compute_path=args.compute_path))
    if args.compute_path != "float":
        print(f"compute path: {args.compute_path} (decode ticks quantize "
              f"activations and accumulate on the packed tile words; "
              f"outputs are approximate vs --compute-path float)")
    params = mod.init_params(t_model.specs(), jax.random.PRNGKey(args.seed))
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        step, restored = restore_into(params, args.ckpt_dir)
        params = restored
        print(f"restored masters at step {step}")

    sp = export_serving_params(
        t_model.specs(), s_model.specs(), params, cfg.tbn
    )
    master_b = serving_bytes(params)
    ship_b = serving_bytes(sp)
    print(f"arch={cfg.name} TBN p={cfg.tbn.p}: masters {master_b/1e6:.2f}MB "
          f"-> shipped {ship_b/1e6:.2f}MB ({master_b/ship_b:.1f}x smaller)")

    aot = args.aot if args.aot is not None else args.serve
    eng = BatchedEngine(
        s_model, sp,
        ServeConfig(n_slots=args.slots, max_len=args.max_len,
                    chunk_tokens=min(args.chunk_tokens, args.max_len),
                    temperature=args.temperature,
                    top_k=args.top_k, seed=args.seed,
                    page_tokens=args.page_tokens,
                    pool_pages=args.pool_pages,
                    prefix_cache=args.prefix_cache,
                    enc_tokens=(args.enc_tokens if encdec else None),
                    max_queued=args.max_queued if args.serve else None,
                    priorities=args.priorities or args.preempt,
                    preempt=args.preempt,
                    default_priority=args.default_priority,
                    compute_path=args.compute_path,
                    telemetry=args.telemetry,
                    trace_events=(args.trace_events if args.trace_log
                                  else 0)),
        mesh=mesh,
    )

    def _flush_trace_log():
        if args.trace_log and eng.tel is not None and eng.tel.ring:
            n = eng.tel.ring.write_jsonl(args.trace_log)
            dropped = eng.tel.ring.dropped
            print(f"trace log: {n} events -> {args.trace_log}"
                  + (f" ({dropped} older events dropped by the "
                     f"{eng.cfg.trace_events}-event ring)" if dropped
                     else ""))

    if args.serve:
        import asyncio

        from repro.serve.server import ServerConfig, run_server

        def _ready(_srv, port):
            # the readiness line subprocess harnesses wait for
            print(f"serving on http://{args.host}:{port} "
                  f"(aot={'on' if aot else 'off'})", flush=True)

        try:
            asyncio.run(run_server(
                eng, ServerConfig(host=args.host, port=args.port),
                aot=aot, ready=_ready,
                stats_interval=args.stats_interval))
        except KeyboardInterrupt:
            pass
        _flush_trace_log()
        print("server closed")
        return []
    if aot:
        t = eng.warmup()
        print(f"AOT warmup: {', '.join(f'{k} {v:.2f}s' for k, v in t.items())}")
    if mesh is not None:
        total_tile = tile_serving_bytes(sp)
        per_dev = per_device_tile_bytes(eng.params)
        worst = max(per_dev.values()) if per_dev else 0
        print(f"mesh={dict(mesh.shape)}: tile bits {total_tile/1e6:.3f}MB total, "
              f"{worst/1e6:.3f}MB max/device "
              f"({total_tile/max(worst, 1):.1f}x sharding)")
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab, size=args.shared_prefix)
    sources = None
    if encdec:
        # a small set of distinct source clips cycled over the batch:
        # every repeat admission past the first is an encoder-reuse hit
        cap = eng.enc_tokens
        sources = [
            rng.standard_normal(
                (int(rng.integers(max(1, cap // 2), cap + 1)), cfg.d_model)
            ).astype(np.float32)
            for _ in range(args.enc_sources)
        ]
    reqs = [
        eng.submit(
            np.concatenate([
                shared, rng.integers(0, cfg.vocab, size=rng.integers(3, 12))
            ]).astype(np.int32),
            SamplingParams(
                max_tokens=args.max_tokens,
                # under --priorities make the synthetic batch exercise the
                # scheduler: every 4th request is interactive
                priority=("interactive" if eng.cfg.priorities and i % 4 == 3
                          else None)),
            frames=(sources[i % len(sources)] if encdec else None))
        for i in range(args.requests)
    ]
    t0 = time.time()
    tick_ends = []
    ticks = eng.run_until_drained(
        on_tick=lambda _: tick_ends.append(time.time() - t0)
    )
    dt = tick_ends[-1] if tick_ends else 0.0
    tok = sum(len(r.output) for r in reqs)
    # a ~0s drain (tiny reduced config, everything cached) must not
    # divide-by-zero the throughput line
    rate = (f"{tok / dt:.1f} tok/s on {jax.devices()[0].platform}"
            if dt > 1e-9 else "instant drain")
    print(f"{len(reqs)} requests, {tok} tokens in {ticks} engine ticks, "
          f"{dt:.2f}s ({rate})")
    ttfts, itls = latency_report(reqs, tick_ends)
    if ttfts:
        line = (f"TTFT mean {1e3 * np.mean(ttfts):.1f}ms "
                f"max {1e3 * np.max(ttfts):.1f}ms")
        if itls:
            line += (f" | ITL mean {1e3 * np.mean(itls):.1f}ms "
                     f"max {1e3 * np.max(itls):.1f}ms")
        print(f"latency (chunk={eng.cfg.chunk_tokens}): {line}")
    st = eng.stats()
    if encdec:
        fam = st["cache_families"]
        pools = ", ".join(
            f"{name} {f['in_use']}/{f['pages']} pages "
            f"({100 * f['utilization']:.0f}%)"
            for name, f in fam.items()
        )
        print(f"encode phase: {st['encode_ticks']} encode ticks, "
              f"{st['enc_cache_hits']}/{st['admitted']} admissions reused "
              f"a cached encoder output "
              f"({st['enc_cache_entries']} cached sources); {pools}")
    elif eng.cfg.prefix_cache:
        line = (f"hit rate {100 * st['hit_rate']:.0f}% "
                f"({st['prefix_hits']}/{st['admitted']} admissions), "
                f"{st['prefill_tokens_skipped']}/{st['prompt_tokens']} "
                f"prefill tokens skipped")
        if "pool_pages" in st:
            line += (f", pool {st['pages_in_use']}/{st['pool_pages']} pages "
                     f"({100 * st['page_utilization']:.0f}%)")
        print(f"prefix cache (page={eng.cfg.page_tokens}): {line}")
    else:
        print("prefix cache: disabled (--prefix-cache to enable)")
    if eng.cfg.priorities:
        per_cls = ", ".join(
            f"{cls} {t} ticks (n={st['class_counts'][cls]})"
            for cls, t in st["class_ttft_ticks"].items()
        )
        print(f"scheduler ({'priority+preempt' if eng.cfg.preempt else 'priority'}): "
              f"{st['preempts']} preempts / {st['resumes']} resumes, "
              f"{st['preempted_tokens']} context tokens parked, "
              f"preempt-free tick rate {st['preempt_free_tick_rate']:.2f}; "
              f"TTFT {per_cls or 'n/a'}")
    if "latency" in st:
        lat = st["latency"]
        print("telemetry: "
              + " ".join(f"{k} p50={v['p50']}ms p99={v['p99']}ms"
                         for k, v in lat.items()
                         if v["count"] and k in ("ttft_ms", "itl_ms",
                                                 "tick_ms"))
              + (f" | retraces={st['retraces']}" if st.get("retraces")
                 else ""))
    _flush_trace_log()
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> {r.output}")
    return reqs


if __name__ == "__main__":
    main()
