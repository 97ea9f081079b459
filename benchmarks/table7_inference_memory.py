"""Table 7 — inference memory on an ImageNet-scale ViT (dim 768, depth 6,
mlp 4096 — the paper's '6 attention layers x ~8.4M params' profile).

Weight-residency is exact from the ledger (the tile-reuse kernel keeps ONE
tile per layer live); activation residency is the max per-layer live set
for a single image. Four variants as in the paper: FP32, FP32+tiling
(full-precision tiles — the paper's Triton experiment), BWNN (1-bit), and
TBN (packed sub-bit tiles).

A MEASURED CNN section exercises the conv serving path itself: with
``tiled_conv_infer`` the dense OIHW weights never exist at inference, so
the shipped-bytes and latency numbers below are observed on the real
packed representation, not derived from the ledger. (The observed packed
bytes can sit slightly above q/8 per layer: the conv layout pads each
(kernel position, filter) row of channels to whole int32 words.)

A MEASURED SHARDED-SERVING section scales the claim over a tensor-parallel
mesh: the packed tile rows of a reduced LM shard over the model axis
(DESIGN.md §5) and we report per-device resident tile bytes, decode tick
latency, and the max |logit| deviation vs the single-device path. On the
CPU it runs in a subprocess because the 8 forced host devices must be
configured before jax initializes (the same trick the multi-device tests
use); on an accelerator it runs in-process on the host's chips.

A MEASURED CHUNKED-PREFILL section runs the mixed workload the serving
scheduler exists for: one slot decoding while a long prompt streams in
through fixed-width extend chunks. It reports the long request's TTFT and
the decoding slot's inter-token latency (solo vs during-prefill, mean and
max) per chunk size, with the whole-prompt single chunk as the monolithic
baseline — decode ITL must stay flat in tick terms (1 token/tick) and the
max wall-clock ITL must shrink with the chunk.

A MEASURED PREFIX-CACHING section serves 8 concurrent requests sharing a
128-token prefix through the paged KV pool, with the radix-trie prefix
cache off / cold / warm: warm admissions map the shared pages in O(1)
and prefill only each request's distinct tail, so TTFT drops by roughly
the prefix/tail ratio while per-slot cache bytes stay <= the dense
layout at equal max_len (the pool defaults to dense-equivalent size).

A MEASURED DECODE-BLOCKING section times the decode hot path's matmul at
serving batch sizes: the old route padded an (n_slots, 1) decode batch to
the matmul kernel's 128-row m block (~97% zero rows at 4 slots); the
small-m dispatch in ``ops.tiled_dense_infer`` now routes those batches to
``tiled_matvec_unique`` (whole sublane-rounded batch as one m block).
Both paths run the same backend (Pallas on TPU,
interpret elsewhere), so the reported delta is the blocking's, not the
platform's."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp

from benchmarks.common import fmt_table, measure_serve_delta, save_rows
from repro.core.policy import tbn_policy
from repro.models.paper import build_paper_model
from repro.nn.context import ModelContext

ROOT = pathlib.Path(__file__).resolve().parents[1]


def sharded_rows(tps, ticks):
    """Per-device tile bytes, decode tick latency and the max |logit|
    deviation from TP=1, for each tensor-parallel degree in ``tps``, on
    this process's devices."""
    import contextlib
    import time

    import jax
    import numpy as np

    from repro.configs import build_model, get_config
    from repro.distributed.sharding import axis_rules, param_shardings
    from repro.launch.mesh import make_auto_mesh
    from repro.nn import module as mod
    from repro.nn.context import SERVE, TRAIN
    from repro.serve.weights import (
        export_serving_params, per_device_tile_bytes, tile_serving_bytes)

    cfg = get_config("granite-8b").reduced()
    tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                       compute_dtype=jnp.float32))
    sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                       compute_dtype=jnp.float32,
                                       use_pallas=False))
    tp0 = mod.init_params(tm.specs(), jax.random.PRNGKey(0))
    sp = export_serving_params(tm.specs(), sm.specs(), tp0, cfg.tbn)
    batch = {"tokens": jnp.array([[5, 3, 2, 7, 1, 4, 6, 2]], jnp.int32)}
    logical = mod.logical_axes(sm.specs())
    abstract = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), sp)
    total_tile = tile_serving_bytes(sp)

    rows, ref_logits = [], None
    for tp in tps:
        if tp == 1:
            mesh, params = None, sp
        else:
            mesh = make_auto_mesh((tp,), ("model",))
            params = jax.device_put(
                sp, param_shardings(mesh, logical, abstract_tree=abstract))
        ctx = axis_rules(mesh) if mesh is not None else contextlib.nullcontext()
        with ctx:
            prefill = jax.jit(lambda p, b: sm.prefill(p, b, 16))
            decode = jax.jit(sm.decode_step)
            logits, caches, lengths = prefill(params, batch)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            lg, caches, lengths = decode(params, tok, caches, lengths)  # compile
            lg.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(ticks):
                lg, caches, lengths = decode(params, tok, caches, lengths)
            lg.block_until_ready()
            tick_ms = 1e3 * (time.perf_counter() - t0) / ticks
        if ref_logits is None:
            ref_logits = np.asarray(logits, np.float32)
            diff = 0.0
        else:
            diff = float(np.max(np.abs(
                ref_logits - np.asarray(logits, np.float32))))
        per_dev = per_device_tile_bytes(params)
        worst = max(per_dev.values())
        rows.append(dict(
            tp=tp,
            tile_kb_total=round(total_tile / 1e3, 2),
            tile_kb_per_device=round(worst / 1e3, 2),
            sharding=f"{total_tile / worst:.1f}x",
            tick_ms=round(tick_ms, 1),
            max_logit_diff=f"{diff:.2e}",
        ))
    return rows


# CPU only: the 8 forced host devices must be configured before jax
# initializes, so the section runs in a fresh interpreter
_SHARDED_PROG = """
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))
import json
from benchmarks.table7_inference_memory import sharded_rows
print("SHARDED_JSON=" + json.dumps(sharded_rows(%(tps)s, %(ticks)d)))
"""


def measure_sharded_serving(quick: bool):
    """Per-device tile bytes + decode tick latency over a model-axis mesh.

    On an accelerator the section runs in this process on the host's own
    devices: a child could not open the chips this process holds. On the
    CPU it runs in a child with 8 forced host devices. Either way a
    failure raises — the section never reports itself skipped."""
    import jax

    tps = [1, 4] if quick else [1, 2, 4]
    ticks = 4 if quick else 16
    if jax.default_backend() != "cpu":
        n_dev = len(jax.devices())
        if n_dev < max(tps):
            raise RuntimeError(
                f"sharded serving section needs {max(tps)} devices, this "
                f"host has {n_dev}")
        return sharded_rows(tps, ticks)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_PROG % dict(tps=tps, ticks=ticks)],
        capture_output=True, text=True, cwd=str(ROOT), timeout=900, env=env,
    )
    for line in out.stdout.splitlines():
        if line.startswith("SHARDED_JSON="):
            return json.loads(line[len("SHARDED_JSON="):])
    raise RuntimeError(
        f"sharded serving section failed: rc={out.returncode}\n"
        f"{out.stderr[-2000:]}")


def measure_decode_blocking(quick: bool):
    """Old 128-row matmul blocking vs the small-m matvec dispatch at
    decode batch sizes (n_slots tokens per tick, one token per slot)."""
    import time

    import jax
    import numpy as np

    from repro.core.packing import pack_bits
    from repro.kernels.tiled_matmul import tiled_matmul_unique
    from repro.kernels.tiled_matvec import (
        DECODE_BLOCK_K, DECODE_BLOCK_R, sublane_rounded, tiled_matvec_unique)

    k_dim, r = (1024, 256) if quick else (2048, 512)
    reps = 3 if quick else 10
    key = jax.random.PRNGKey(0)
    packed = pack_bits(
        jnp.where(jax.random.bernoulli(key, 0.5, (r, k_dim)), 1.0, -1.0))

    def timed(fn, x):
        fn(x).block_until_ready()            # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(x)
        out.block_until_ready()
        return 1e3 * (time.perf_counter() - t0) / reps

    rows = []
    for m in (4, 16):
        x = jax.random.normal(jax.random.PRNGKey(m), (m, k_dim), jnp.float32)

        @jax.jit
        def old_path(x, m=m):
            xp = jnp.pad(x, ((0, 128 - m), (0, 0)))
            return tiled_matmul_unique(xp, packed, r=r)[:m]

        @jax.jit
        def new_path(x, m=m):
            xp = jnp.pad(x, ((0, sublane_rounded(m, x.dtype) - m), (0, 0)))
            return tiled_matvec_unique(
                xp, packed, r=r,
                block_r=min(DECODE_BLOCK_R, r),
                block_k=min(DECODE_BLOCK_K, k_dim),
            )[:m]

        np.testing.assert_allclose(                 # same math before timing
            np.asarray(old_path(x)), np.asarray(new_path(x)),
            rtol=1e-5, atol=1e-3)
        old_ms, new_ms = timed(old_path, x), timed(new_path, x)
        rows.append(dict(
            n_slots=m, k=k_dim, r=r,
            old_ms=round(old_ms, 3), new_ms=round(new_ms, 3),
            old_tok_s=round(1e3 * m / old_ms, 1),
            new_tok_s=round(1e3 * m / new_ms, 1),
            speedup=f"{old_ms / new_ms:.2f}x",
        ))
    return rows


def measure_chunked_prefill(quick: bool):
    """Mixed-workload tail latency: a slot decoding WHILE a long prompt
    prefills, across prefill chunk sizes.

    The last row admits the whole prompt as ONE chunk in ONE tick — the
    chunk width exceeds prompt + decode load, so the decode-priority
    budget cannot split it — i.e. the old admission-time monolithic
    behavior, and its max inter-token latency shows the head-of-line
    spike the chunked scheduler removes. In tick terms every row's
    decoder emits exactly 1 token/tick (the fairness invariant); the
    wall-clock ITL columns show how much prompt work each chunk size
    lets a single tick carry."""
    import time

    import jax
    import numpy as np

    from repro.configs import build_model, get_config
    from repro.nn import module as mod
    from repro.nn.context import SERVE, TRAIN, ModelContext
    from repro.serve.engine import BatchedEngine, ServeConfig
    from repro.serve.sampling import SamplingParams
    from repro.serve.weights import export_serving_params

    cfg = get_config("granite-8b").reduced()
    tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                       compute_dtype=jnp.float32))
    sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                       compute_dtype=jnp.float32,
                                       use_pallas=False))
    tp0 = mod.init_params(tm.specs(), jax.random.PRNGKey(0))
    sp = export_serving_params(tm.specs(), sm.specs(), tp0, cfg.tbn)

    plen = 48 if quick else 96
    warm = 4 if quick else 8
    long_prompt = [int(x) for x in np.arange(plen) % cfg.vocab]
    rows = []
    # chunk = plen + n_slots: budget covers the whole prompt even after
    # every decoding slot is charged its token, so the prompt truly lands
    # in one tick (a bare chunk = plen would split it (plen-1) + 1)
    mono = plen + 2
    for chunk in (8, 16, mono):
        eng = BatchedEngine(sm, sp, ServeConfig(
            n_slots=2, max_len=plen + 32, chunk_tokens=chunk))
        dec = eng.submit([1, 2, 3], SamplingParams(max_tokens=plen + 64))
        for _ in range(1 + warm):          # admit+prefill, then warm decode
            eng.step()
        # baseline: decode-only tick latency
        t0 = time.perf_counter()
        for _ in range(warm):
            eng.step()
        itl_solo = 1e3 * (time.perf_counter() - t0) / warm

        lreq = eng.submit(long_prompt, SamplingParams(max_tokens=4))
        submit_step, ticks = eng.steps, []
        while not lreq.output:
            before = len(dec.output)
            t0 = time.perf_counter()
            eng.step()
            ticks.append(1e3 * (time.perf_counter() - t0))
            assert len(dec.output) == before + 1   # fairness, in tick terms
        rows.append(dict(
            chunk=chunk if chunk != mono else f"{chunk} (monolithic)",
            prompt=plen,
            prefill_ticks=eng.steps - submit_step,
            ttft_ms=round(sum(ticks), 1),
            itl_solo_ms=round(itl_solo, 1),
            itl_mixed_ms=round(float(np.mean(ticks)), 1),
            itl_mixed_max_ms=round(float(np.max(ticks)), 1),
            decode_tok_per_tick=1.0,
        ))
    return rows


def measure_prefix_caching(quick: bool):
    """Shared-prefix serving: TTFT with/without the radix-trie prefix
    cache for 8 concurrent requests sharing a 128-token prefix (a system
    prompt), plus paged-pool vs dense cache bytes.

    Three admission regimes on the same engine shape: ``no-cache``
    (prefix cache off — every admission prefills the full prompt),
    ``cache-cold`` (cache on, empty trie — the 8 concurrent requests all
    miss, since none has retired/published yet), and ``cache-warm`` (the
    trie holds the shared prefix from the previous batch — every
    admission maps its 128 prefix tokens in O(1) and prefills only the
    distinct tail). The pool is the default dense-equivalent size, so
    per-slot cache bytes never exceed the dense layout at equal max_len;
    the in-use column shows what the pool actually holds once shared
    pages are counted once."""
    import time

    import jax
    import numpy as np

    from repro.configs import build_model, get_config
    from repro.nn import module as mod
    from repro.nn.context import SERVE, TRAIN, ModelContext
    from repro.serve.engine import BatchedEngine, ServeConfig
    from repro.serve.sampling import SamplingParams
    from repro.serve.weights import export_serving_params

    cfg = get_config("granite-8b").reduced()
    tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                       compute_dtype=jnp.float32))
    sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                       compute_dtype=jnp.float32,
                                       use_pallas=False))
    tp0 = mod.init_params(tm.specs(), jax.random.PRNGKey(0))
    sp = export_serving_params(tm.specs(), sm.specs(), tp0, cfg.tbn)

    plen, n_req, max_len = 128, 8, 160
    gen_toks = 2 if quick else 4
    shared = [int(x) % cfg.vocab for x in np.arange(plen)]

    def tails(salt):
        rng = np.random.default_rng(salt)
        return [[int(t) for t in rng.integers(0, cfg.vocab, size=6)]
                for _ in range(n_req)]

    def make_engine(prefix_cache):
        return BatchedEngine(sm, sp, ServeConfig(
            n_slots=n_req, max_len=max_len, chunk_tokens=32,
            page_tokens=16, prefix_cache=prefix_cache))

    def run_batch(eng, salt):
        reqs = [eng.submit(shared + tail, SamplingParams(max_tokens=gen_toks))
                for tail in tails(salt)]
        base = eng.steps
        tick_ends, t0 = [], time.perf_counter()
        eng.run_until_drained(
            on_tick=lambda _: tick_ends.append(time.perf_counter() - t0))
        ttfts = [1e3 * tick_ends[r.token_steps[0] - base] for r in reqs]
        return ttfts, reqs

    def cache_bytes(eng):
        return sum(v.nbytes for v in jax.tree_util.tree_leaves(eng.caches))

    # compile + allocator warmup on a throwaway engine, at the SAME
    # 8-concurrent load as the timed batches: the tick functions are
    # cached on the model, so the timed engines below all run
    # pre-compiled. Post-compile drains still jitter run-to-run on CPU,
    # so every variant averages over ``reps`` full batches.
    reps = 2 if quick else 4
    warm_eng = make_engine(False)
    run_batch(warm_eng, salt=0)

    rows = []
    # no-cache baseline: every admission prefills all plen+6 tokens
    eng = make_engine(False)
    ttfts = [t for i in range(reps) for t in run_batch(eng, salt=1 + i)[0]]
    rows.append(dict(
        variant="no-cache",
        ttft_mean_ms=round(float(np.mean(ttfts)), 1),
        ttft_max_ms=round(float(np.max(ttfts)), 1),
        prefill_skipped_tok=0,
        cache_mb_per_slot=round(cache_bytes(eng) / n_req / 1e6, 3),
        pool_pages="-",
    ))
    dense_per_slot = rows[0]["cache_mb_per_slot"]

    # cache-cold: first batch on a FRESH trie each rep (each batch's 8
    # concurrent admissions all miss — nothing retired/published yet)
    ttfts = []
    for i in range(reps):
        eng = make_engine(True)
        ttfts += run_batch(eng, salt=1 + i)[0]
    st = eng.stats()
    rows.append(dict(
        variant="cache-cold",
        ttft_mean_ms=round(float(np.mean(ttfts)), 1),
        ttft_max_ms=round(float(np.max(ttfts)), 1),
        prefill_skipped_tok=0,
        cache_mb_per_slot=round(cache_bytes(eng) / n_req / 1e6, 3),
        pool_pages=f"{st['pages_in_use']}/{st['pool_pages']}",
    ))

    # cache-warm: one engine, an untimed seeding batch, then timed
    # batches with distinct tails — every admission maps the shared 128
    # prefix tokens from the trie
    eng = make_engine(True)
    run_batch(eng, salt=100)                       # seeds the trie
    before = eng.stats()["prefill_tokens_skipped"]
    ttfts = [t for i in range(reps)
             for t in run_batch(eng, salt=101 + i)[0]]
    st = eng.stats()
    rows.append(dict(
        variant="cache-warm",
        ttft_mean_ms=round(float(np.mean(ttfts)), 1),
        ttft_max_ms=round(float(np.max(ttfts)), 1),
        prefill_skipped_tok=(st["prefill_tokens_skipped"] - before) // reps,
        cache_mb_per_slot=round(cache_bytes(eng) / n_req / 1e6, 3),
        pool_pages=f"{st['pages_in_use']}/{st['pool_pages']}",
    ))
    assert all(r["cache_mb_per_slot"] <= dense_per_slot for r in rows)
    warm, base = rows[2]["ttft_mean_ms"], rows[0]["ttft_mean_ms"]
    for r in rows:
        r["ttft_vs_nocache"] = f"{base / max(r['ttft_mean_ms'], 1e-9):.2f}x"
    print(f"\nwarm shared-prefix TTFT {base / max(warm, 1e-9):.2f}x faster "
          f"than no-cache ({plen}-token shared prefix, {n_req} concurrent)")
    return rows


PAPER = dict(fp=(222.5, 208.0), fp_tiled=(78.5, 52.0),
             bwnn=(18.4, 6.5), tbn=(13.4, 1.6))


def weight_bytes(rep, variant: str, p: int = 4) -> float:
    total = 0.0
    for r in rep.layers:
        if r.kind not in ("dense", "conv", "head"):
            continue
        if variant == "fp":
            total += 4 * r.n
        elif variant == "fp_tiled":
            total += 4 * (r.n // r.spec.p if r.spec else r.n)
        elif variant == "bwnn":
            total += r.n / 8
        elif variant == "tbn":
            total += r.stored_bits() / 8
    return total


def act_bytes(dim=768, tokens=197, mlp=4096, heads=12) -> float:
    """Max live activations for one image: in + out + qkv or mlp hidden."""
    token_buf = tokens * dim * 4
    qkv = tokens * 3 * dim * 4
    scores = heads * tokens * tokens * 4
    mlp_h = tokens * mlp * 4
    attn_peak = 2 * token_buf + qkv + scores
    mlp_peak = 2 * token_buf + mlp_h
    return max(attn_peak, mlp_peak)


def run(quick: bool = False):
    pol = tbn_policy(p=4, min_size=150_000, alpha_source="W")
    ctx = ModelContext(policy=pol, compute_dtype=jnp.float32)
    build_paper_model("vit", ctx, dim=768, depth=6, heads=12,
                      mlp_dim=4096, patch=16, img=224, classes=1000)
    rep = ctx.ledger.report()
    acts = act_bytes()
    rows = []
    for variant, pretty in [("fp", "Full Precision"),
                            ("fp_tiled", "FP, Tiled4"),
                            ("bwnn", "BWNN"), ("tbn", "TBN4")]:
        wb = weight_bytes(rep, variant)
        peak = wb + acts
        ref = PAPER[variant]
        rows.append(dict(
            variant=pretty,
            peak_mb=round(peak / 1e6, 1),
            param_mb=round(wb / 1e6, 1),
            pct_param=f"{100 * wb / peak:.1f}%",
            paper_peak=ref[0], paper_param=ref[1],
        ))
    fp_peak = rows[0]["peak_mb"]
    for r in rows:
        r["peak_saving"] = f"{fp_peak / r['peak_mb']:.1f}x"
    save_rows("table7_inference_memory", rows)
    print(fmt_table(rows, ["variant", "peak_mb", "param_mb", "pct_param",
                           "peak_saving", "paper_peak", "paper_param"]))

    # measured conv serving path: dense weights vs packed conv tiles
    cnn_pol = tbn_policy(p=4, min_size=64_000, alpha_source="W")
    m = measure_serve_delta("resnet18", cnn_pol, repeats=1 if quick else 3)
    mrows = [dict(variant=k, weight_mb=round(v["bytes"] / 1e6, 3),
                  latency_ms=round(v["latency_ms"], 1))
             for k, v in m.items() if k != "delta"]
    mrows.append(dict(variant="delta",
                      weight_mb=f'{m["delta"]["bytes_saving"]:.1f}x smaller',
                      latency_ms=f'{m["delta"]["latency_speedup"]:.2f}x'))
    save_rows("table7_cnn_measured", mrows)
    print("\nmeasured resnet18 serving (dense fp32 vs packed conv tiles):")
    print(fmt_table(mrows, ["variant", "weight_mb", "latency_ms"]))

    # measured decode blocking: the old 128-row-padded matmul vs the
    # small-m matvec dispatch the decode tick now takes
    drows = measure_decode_blocking(quick)
    save_rows("table7_decode_matvec", drows)
    print("\nmeasured decode-tick matmul (old 128-row blocking vs small-m "
          "matvec dispatch, per jitted call):")
    print(fmt_table(drows, ["n_slots", "k", "r", "old_ms", "new_ms",
                            "old_tok_s", "new_tok_s", "speedup"]))

    # measured chunked-prefill scheduling: decode tail latency while a
    # long prompt streams in, vs the monolithic single-chunk admission
    crows = measure_chunked_prefill(quick)
    save_rows("table7_chunked_prefill", crows)
    print("\nmeasured chunked-prefill mixed workload (decoding slot beside "
          "a long-prompt admission; ITL = decode inter-token latency):")
    print(fmt_table(crows, ["chunk", "prompt", "prefill_ticks", "ttft_ms",
                            "itl_solo_ms", "itl_mixed_ms",
                            "itl_mixed_max_ms", "decode_tok_per_tick"]))

    # measured prefix caching: shared-prefix TTFT with/without the
    # radix-trie cache + paged-pool vs dense cache bytes
    prows = measure_prefix_caching(quick)
    save_rows("table7_prefix_caching", prows)
    print("\nmeasured prefix caching (8 concurrent requests sharing a "
          "128-token prefix; paged KV pool at dense-equivalent size):")
    print(fmt_table(prows, ["variant", "ttft_mean_ms", "ttft_max_ms",
                            "ttft_vs_nocache", "prefill_skipped_tok",
                            "cache_mb_per_slot", "pool_pages"]))

    # measured tensor-parallel serving: tile rows sharded over the model
    # axis — per-device bytes must scale as 1/TP with unchanged logits
    srows = measure_sharded_serving(quick)
    save_rows("table7_sharded_serving", srows)
    print("\nmeasured sharded serving (reduced LM, tile rows over the "
          "model axis):")
    print(fmt_table(srows, ["tp", "tile_kb_total", "tile_kb_per_device",
                            "sharding", "tick_ms", "max_logit_diff"]))
    return rows


if __name__ == "__main__":
    run()
