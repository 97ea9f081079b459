"""End-to-end training driver.

    python -m repro.launch.train --arch granite-8b --reduced \\
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Composes every production subsystem: config registry (--arch), TBN policy
override (--tbn-p / --mode), synthetic deterministic data pipeline,
AdamW + cosine schedule, microbatch accumulation, sharded train step under
the active mesh rules, checkpoint/restart via the RecoveryManager (resume
is automatic if --ckpt-dir holds a checkpoint), and the straggler
watchdog. On the CPU host use --reduced; on a real pod drop it and point
--mesh at the production topology.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import build_model, get_config
from repro.core.policy import bwnn_policy, fp32_policy
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import lm_batch
from repro.distributed.sharding import axis_rules
from repro.ft.checkpoint import CheckpointManager
from repro.ft.recovery import RecoveryManager
from repro.ft.watchdog import StepWatchdog
from repro.launch.cache import use_compile_cache
from repro.nn import module as mod
from repro.nn.context import TRAIN, ModelContext
from repro.optim import adamw, cosine_with_warmup
from repro.train.step import build_train_step, init_state


def make_policy(cfg, args):
    if args.mode == "fp32":
        return fp32_policy()
    if args.mode == "bwnn":
        return bwnn_policy()
    p = args.tbn_p or cfg.tbn.p
    return dataclasses.replace(cfg.tbn, p=p)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU host)")
    ap.add_argument("--mode", default="tbn", choices=["tbn", "bwnn", "fp32"])
    ap.add_argument("--tbn-p", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2x4' data x model over local devices")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, tbn=make_policy(cfg, args))

    ctx = ModelContext(policy=cfg.tbn, mode=TRAIN,
                       fsdp_weights=cfg.fsdp_weights)
    model = build_model(cfg, ctx)
    opt = adamw(cosine_with_warmup(args.lr, args.warmup, args.steps),
                weight_decay=0.1)
    step_fn = build_train_step(model.train_forward, opt,
                               grad_accum=args.grad_accum)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_arg

        mesh = parse_mesh_arg(args.mesh)

    def make_state():
        params = mod.init_params(model.specs(), jax.random.PRNGKey(args.seed))
        return init_state(params, opt)

    def gen(step):
        if cfg.family == "encdec":
            from repro.data.synthetic import frames_batch

            return frames_batch(args.seed, step, args.batch, args.seq, cfg)
        if cfg.modality == "vlm":
            b = lm_batch(args.seed, step, args.batch, args.seq, cfg.vocab)
            b["image_mask"] = jnp.zeros((args.batch, args.seq), bool)
            b["image_embeds"] = jnp.zeros(
                (args.batch, args.seq, cfg.d_model), jnp.bfloat16
            )
            return b
        return lm_batch(args.seed, step, args.batch, args.seq, cfg.vocab)

    def make_data(start):
        return DataPipeline(gen, start_step=start, prefetch=2)

    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    tbn_bits = ctx.ledger.report()
    print(f"arch={cfg.name} mode={cfg.tbn.mode} p={cfg.tbn.p} "
          f"params={mod.param_count(model.specs()):,} "
          f"stored_bits/param={tbn_bits.bits_per_param():.3f}")

    ckpt = CheckpointManager(
        args.ckpt_dir or f"/tmp/tbn_{cfg.name}",
        save_every=args.ckpt_every, max_to_keep=3,
    )
    history = []

    def hooks(step, state, metrics):
        if step % args.log_every == 0 or step == 1:
            loss = float(metrics["loss"])
            history.append((step, loss))
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)

    rm = RecoveryManager(
        ckpt, make_state=make_state, make_data=make_data,
        watchdog=StepWatchdog(threshold=5.0),
    )

    def wrapped(state, batch):
        if mesh is not None:
            with axis_rules(mesh):
                return jit_step(state, batch)
        return jit_step(state, batch)

    t0 = time.time()
    final = rm.run(wrapped, args.steps, hooks=hooks)
    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s), final step={int(final.step)}")
    if history:
        print(f"loss: first={history[0][1]:.4f} last={history[-1][1]:.4f}")
    return final, history


if __name__ == "__main__":
    main()
