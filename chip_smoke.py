"""Bring-up smoke run: serve granite-8b at full width on one TPU chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tensor-parallel (1, 4) mesh only

Drives the serving main path once, in this one process, through the entry
points a user calls: ``build_model`` in SERVE mode,
``export_serving_params``, ``BatchedEngine.warmup()`` and a drain of a few
requests through chunked prefill and decode, a shared prompt prefix
served from the prefix cache. The model is granite-8b at its published
widths with random weights from ``--seed``; only the depth is cut, to 4
layers. The tile kernels run as Pallas on the chip (the serve path's auto
rule). The run checks every token the engine emitted against a
teacher-forced forward pass of the structured jnp path
(``use_pallas=False``), and the Pallas logits against an f32 reference,
next to the structured path's own distance from it.

``--four-chips`` runs instead the same model on a (1, 4) mesh through
``BatchedEngine(mesh=...)``, holds its logits to the same f32-relative
gate with a single-device run on ``devices[0]`` in the structured
path's place, and checks that each chip holds exactly 1/4 of the
packed tile bytes and of the KV pool.

The last line of stdout is ``{"ok": true, "device": {...}}`` and the exit
code is 0 only when every phase passed. Without a TPU (for example under
``JAX_PLATFORMS=cpu``) the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import build_model, get_config  # noqa: E402
from repro.distributed.sharding import axis_rules  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.nn import module as mod  # noqa: E402
from repro.nn.context import SERVE, TRAIN, ModelContext  # noqa: E402
from repro.serve.engine import BatchedEngine, ServeConfig  # noqa: E402
from repro.serve.sampling import SamplingParams  # noqa: E402
from repro.serve.weights import (  # noqa: E402
    export_serving_params,
    per_device_bytes,
    per_device_tile_bytes,
    serving_bytes,
    tile_serving_bytes,
)

ARCH = "granite-8b"
N_LAYERS = 4                 # the only cut: depth; every width is published
N_SLOTS = 8
CHUNK_TOKENS = 256           # extend tick: 8 x 256 rows -> matmul kernel
MAX_LEN = 512
SHARED_PREFIX = 256          # two requests share this many prompt tokens
MAX_TOKENS = 12
CMP_BATCH, CMP_PROMPT, CMP_DECODE_STEPS = 8, 256, 4

# Pallas vs the f32 reference, per step, as a multiple of the structured
# path's distance from the same reference. Both bf16 paths feed bf16
# activations and exact ±1 weights to their matmuls and sum in f32; they
# round every norm, residual add and attention op alike and differ only
# in where a linear layer's f32 sum is rounded to bf16 (the kernel once,
# after alpha; XLA's einsum after the sum and again after alpha). So the
# two distances are the same bf16 noise and their ratio is ~1. A kernel
# that sums in bf16 (error ~sqrt(K) ulps per layer, K >= 4096) or reads a
# wrong bit (error ~ a whole logit) sets the Pallas distance far above
# the structured one. Two RMS distances over 8 x 49152 logits still
# scatter around each other (0.77-1.01 in CPU rehearsals at a reduced
# size); C_F32 leaves that room.
C_F32 = 1.25
# TP=4 is held to the same gate, with the single-device Pallas run in the
# structured path's place: RMS(TP=4 - f32) <= C_F32 x RMS(one device -
# f32). Under TP every chip runs the same kernel with the same K blocking
# on its 1/4 of each layer's tile rows and attention splits by whole
# heads, so the sums are the same; but the partitioned program places
# XLA's bf16 roundings around its collectives differently, and one
# relocated rounding in layer 1 reaches the logits as the same ~2^-5
# noise the one-chip run reads between Pallas and structured. A shard
# read from the wrong chip, a head mapped to the wrong KV group or a
# missing reduction moves the logits by a whole spread, far outside it.


@contextlib.contextmanager
def phase(name):
    """Name the phase on stdout; a failure names it on stderr and
    propagates (exit code 1, no result line)."""
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"chip_smoke: FAILED in phase '{name}'", file=sys.stderr)
        raise
    print(f"   ({name}: {time.perf_counter() - t0:.1f}s)", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def smoke_config():
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    print(f"config {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads / "
          f"{cfg.n_kv} KV, head_dim {hd}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, TBN p={cfg.tbn.p}")
    print(f"depth cut: {full.n_layers} -> {cfg.n_layers} layers "
          f"(dataclasses.replace); weights random from the seed")
    return cfg


def export_params(cfg, seed):
    """TRAIN masters from the seed -> shipped SERVE params. The masters
    (~5 GB fp32 at full width) are dropped before serving starts."""
    t_specs = build_model(cfg, ModelContext(policy=cfg.tbn,
                                            mode=TRAIN)).specs()
    s_model = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE))
    # one executable each instead of one per eager op and shape
    masters = jax.jit(lambda k: mod.init_params(t_specs, k))(
        jax.random.PRNGKey(seed))
    master_b = serving_bytes(masters)
    sp = jax.jit(lambda m: export_serving_params(
        t_specs, s_model.specs(), m, cfg.tbn))(masters)
    jax.block_until_ready(sp)
    del masters
    print(f"masters {master_b / 1e9:.3f} GB -> shipped "
          f"{serving_bytes(sp) / 1e9:.3f} GB (packed tiles "
          f"{tile_serving_bytes(sp) / 1e6:.3f} MB)")
    return s_model, sp


def make_engine(model, params, seed, mesh=None):
    return BatchedEngine(
        model, params,
        ServeConfig(n_slots=N_SLOTS, max_len=MAX_LEN,
                    chunk_tokens=CHUNK_TOKENS, prefix_cache=True, seed=seed),
        mesh=mesh,
    )


def warm(eng, cache_dir):
    """AOT-compile the tick executables; every one must hold a Pallas
    kernel (``tpu_custom_call``) — no silent structured fallback."""
    secs = eng.warmup()
    print(f"compile cache: {cache_dir}")
    for name, s in secs.items():
        print(f"compile {name}: {s:.2f}s")
    for name in ("extend_tick", "decode_tick"):
        has = "tpu_custom_call" in eng._aot[name].as_text()
        print(f"{name} HLO contains tpu_custom_call: {has}")
        check(has, f"{name} compiled without a Pallas kernel")


def drain(eng, vocab, seed, n_requests):
    """Greedy requests of mixed prompt lengths, MAX_TOKENS each, in two
    waves. Two prompts share SHARED_PREFIX tokens; the second is sent
    after the first has finished, so the prefix cache serves it."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX)
    first, second = (np.concatenate([shared, rng.integers(0, vocab, n)])
                     for n in (9, 23))
    rest = [rng.integers(0, vocab, int(n))
            for n in rng.integers(16, 300, n_requests - 2)]
    half = len(rest) // 2
    reqs, ticks = [], 0
    t0 = time.perf_counter()
    for wave in ([first] + rest[:half], [second] + rest[half:]):
        reqs += [eng.submit(p.astype(np.int32),
                            SamplingParams(max_tokens=MAX_TOKENS))
                 for p in wave]
        ticks += eng.run_until_drained()
    dt = time.perf_counter() - t0
    for r in reqs:
        print(f"req {r.rid}: prompt {len(r.prompt)} tokens "
              f"({r.prefix_hit_tokens} from the prefix cache) -> "
              f"{len(r.output)} tokens {list(r.output)}")
        check(len(r.output) == MAX_TOKENS,
              f"req {r.rid} produced {len(r.output)} of {MAX_TOKENS} tokens")
        check(all(0 <= t < vocab for t in r.output),
              f"req {r.rid} produced a token outside [0, {vocab})")
    st = eng.stats()
    print(f"drained {len(reqs)} requests in {ticks} ticks, {dt:.2f}s; "
          f"prefix hits {st['prefix_hits']}/{st['admitted']}, "
          f"retraces after warmup {st.get('retraces', 0)}")
    check(not st.get("retraces"), "a tick retraced after warmup")
    check(reqs[len(rest[:half]) + 1].prefix_hit_tokens >= SHARED_PREFIX,
          "the shared-prefix request was not served from the prefix cache")
    return reqs


def forced_logits(model, params, reqs):
    """Teacher-forced logits at every position where the engine emitted a
    token: one dense, causal forward pass over prompt + output, without
    the engine's paged caches, chunking or prefix cache. Returns
    (n_requests, MAX_TOKENS, vocab) f32."""
    width = -(-max(len(r.prompt) + MAX_TOKENS for r in reqs) // 128) * 128
    tokens = np.zeros((len(reqs), width), np.int32)
    pos = np.zeros((len(reqs), MAX_TOKENS), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.output[:-1]])
        tokens[i, :len(seq)] = seq   # right padding: causal, never read
        pos[i] = len(r.prompt) - 1 + np.arange(MAX_TOKENS)

    @jax.jit
    def run(params, tokens, pos):
        h, _ = model.backbone(params, model.embed(params["embed"], tokens))
        h = jnp.take_along_axis(h, pos[..., None], axis=1)
        return model.logits(params, h).astype(jnp.float32)

    return np.asarray(run(params, tokens, pos))


def check_engine_tokens(reqs, pallas, structured):
    """Every emitted token must be an argmax of the structured reference
    up to the rounding noise. The engine picked the argmax of its own
    logits e, so ref[best] - ref[tok] <= 2 max|e - ref|. The forced Pallas
    pass p runs the engine's kernels and differs from it only in how
    attention reads K/V (the engine: bf16 pages, in chunks):
    |e - ref| <= |e - p| + |p - ref|, each term the same bf16 noise,
    taken as max|p - ref| at these positions. A wrong page, cache row or prefix hit picks tokens
    whose gap is a whole logit spread. Run after the f32 gate, which
    holds the kernels to the bf16 noise this bound is made of."""
    noise = float(np.max(np.abs(pallas - structured)))
    bound = 4 * noise
    toks = np.asarray([r.output for r in reqs])
    gap = structured.max(-1) - np.take_along_axis(
        structured, toks[..., None], -1)[..., 0]
    match = int(np.sum(gap == 0))
    print(f"engine tokens vs structured forward: {match}/{gap.size} are "
          f"its argmax; largest gap {gap.max():.6g} (bound 4 x max|pallas "
          f"- structured| = 4 x {noise:.6g} = {bound:.6g}; logit spread "
          f"{float(np.median(structured.max(-1) - structured.min(-1))):.6g})")
    check(np.isfinite(structured).all() and np.isfinite(pallas).all(),
          "non-finite forced logits")
    check(gap.max() <= bound, f"an engine token is {gap.max():.6g} below "
                              f"the reference argmax (bound {bound:.6g})")


def cmp_prompts(vocab, seed):
    rng = np.random.default_rng(seed + 1)
    return jnp.asarray(rng.integers(0, vocab, (CMP_BATCH, CMP_PROMPT)),
                       jnp.int32)


def logit_run(model, params, prompts, feed=None, mesh=None):
    """Prefill logits, then CMP_DECODE_STEPS cached decode steps. Decode
    inputs are ``feed`` when given (so two paths see the same tokens),
    else this run's own greedy tokens. Returns (logits list, tokens)."""
    ctx = axis_rules(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                     MAX_LEN))
        decode = jax.jit(model.decode_step)
        lg, caches, lengths = prefill(params, prompts)
        out, toks = [np.asarray(lg, np.float32)], []
        for i in range(CMP_DECODE_STEPS):
            # host tokens: a committed device array would pin the call
            # to that device
            tok = (feed[i] if feed is not None
                   else np.asarray(jnp.argmax(lg, -1)[:, None], np.int32))
            toks.append(tok)
            lg, caches, lengths = decode(params, tok, caches, lengths)
            out.append(np.asarray(lg, np.float32))
    return out, toks


def distances(got, want):
    """Per step (prefill, then each decode step): max|d logit|, that over
    max|logit|, and the root mean square of d logit."""
    out = []
    for i, (g, w) in enumerate(zip(got, want)):
        check(np.isfinite(g).all() and np.isfinite(w).all(),
              f"non-finite logits at step {i}")
        d = np.abs(g - w)
        out.append((float(d.max()), float(d.max() / np.abs(w).max()),
                    float(np.sqrt(np.mean(d ** 2)))))
    return out


def step_name(i):
    return "prefill" if i == 0 else f"decode {i}"


def compare_to_f32(got, ref, exact, names=("pallas", "structured")):
    """Gate: at every step the RMS distance of ``got`` from the f32
    reference is at most C_F32 times that of ``ref``."""
    dg, dr, dgr = (distances(got, exact), distances(ref, exact),
                   distances(got, ref))
    a, b = names
    for i, (p, q, x) in enumerate(zip(dg, dr, dgr)):
        ratio = p[2] / q[2]
        print(f"{step_name(i)}: vs f32 RMS / max|d| (rel): {a} "
              f"{p[2]:.6g} / {p[0]:.6g} ({p[1]:.6g}), {b} "
              f"{q[2]:.6g} / {q[0]:.6g} ({q[1]:.6g}); {a} vs {b} "
              f"{x[2]:.6g} / {x[0]:.6g} ({x[1]:.6g}); RMS ratio "
              f"{ratio:.6g} (bound {C_F32})")
        check(ratio <= C_F32,
              f"{step_name(i)}: {a} logits are {ratio:.6g}x as far (RMS) "
              f"from the f32 reference as the {b} logits (bound {C_F32})")


def one_chip(cfg, seed, cache_dir):
    with phase("export"):
        s_model, sp = export_params(cfg, seed)
    with phase("engine warmup"):
        eng = make_engine(s_model, sp, seed)
        warm(eng, cache_dir)
    with phase("drain"):
        reqs = drain(eng, cfg.vocab, seed, n_requests=7)
    del eng
    ref_model = build_model(cfg, ModelContext(
        policy=cfg.tbn, mode=SERVE, use_pallas=False))
    # kernels first: once they are within the bf16 noise, the distance
    # between the Pallas and structured paths is a measure of that noise
    with phase("pallas and structured logits vs f32 reference"):
        f32_model = build_model(cfg, ModelContext(
            policy=cfg.tbn, mode=SERVE, use_pallas=False,
            compute_dtype=jnp.float32))
        prompts = cmp_prompts(cfg.vocab, seed)
        structured, toks = logit_run(ref_model, sp, prompts)
        pallas, _ = logit_run(s_model, sp, prompts, feed=toks)
        with jax.default_matmul_precision("highest"):
            exact, _ = logit_run(f32_model, sp, prompts, feed=toks)
        compare_to_f32(pallas, structured, exact)
    with phase("engine tokens vs teacher-forced structured forward"):
        check_engine_tokens(reqs, forced_logits(s_model, sp, reqs),
                            forced_logits(ref_model, sp, reqs))


def check_quarter(what, per_dev, total):
    for dev, b in sorted(per_dev.items()):
        print(f"{what} bytes on {dev}: {b} ({b / total:.4f} of {total})")
    check(len(per_dev) == 4 and all(4 * b == total
                                    for b in per_dev.values()),
          f"{what} bytes are not exactly 1/4 per chip")


def four_chips(cfg, seed, cache_dir):
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    with phase("export"):
        s_model, sp = export_params(cfg, seed)
    with phase("single-device and f32 references on devices[0]"):
        sp0 = jax.device_put(sp, devices[0])
        prompts = cmp_prompts(cfg.vocab, seed)
        one, toks = logit_run(s_model, sp0, prompts)
        f32_model = build_model(cfg, ModelContext(
            policy=cfg.tbn, mode=SERVE, use_pallas=False,
            compute_dtype=jnp.float32))
        with jax.default_matmul_precision("highest"):
            exact, _ = logit_run(f32_model, sp0, prompts, feed=toks)
        del sp0
    with phase("TP=4 engine warmup"):
        mesh = make_auto_mesh((1, 4), ("data", "model"))
        eng = make_engine(s_model, sp, seed, mesh=mesh)
        warm(eng, cache_dir)
    with phase("TP=4 placement"):
        check_quarter("packed tile", per_device_tile_bytes(eng.params),
                      tile_serving_bytes(sp))
        for path, leaf in jax.tree_util.tree_leaves_with_path(eng.params):
            check(len(leaf.sharding.device_set) == 4,
                  f"param {jax.tree_util.keystr(path)} is not on all 4 chips")
        kv_total = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.caches))
        kv_before = per_device_bytes(jax.tree.leaves(eng.caches))
        check_quarter("KV pool", kv_before, kv_total)
    with phase("TP=4 drain"):
        drain(eng, cfg.vocab, seed, n_requests=6)
        check(per_device_bytes(jax.tree.leaves(eng.caches)) == kv_before,
              "the drain moved the KV pool off its 1/4-per-chip layout")
    with phase("TP=4 and single-device logits vs f32 reference"):
        got, _ = logit_run(s_model, eng.params, prompts, feed=toks,
                           mesh=mesh)
        compare_to_f32(got, one, exact, names=("TP=4", "one device"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 mesh path and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    cfg = smoke_config()
    (four_chips if args.four_chips else one_chip)(cfg, args.seed, cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
