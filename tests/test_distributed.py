"""Distributed runtime: sharding rules, gradient compression, GPipe.

Multi-device cases run in a subprocess with 8 forced host devices (the
main pytest process must stay single-device per the dry-run contract).
They carry the ``subprocess`` marker so CI runs them in their own lane
(`-m subprocess`) while the tier-1 lane stays fast (`-m "not
subprocess"`); a plain ``pytest`` still runs everything."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest


from repro.distributed.compression import (
    ef_sign_encode,
    int8_decode,
    int8_encode,
    wire_bits,
)


def run_subprocess(body: str):
    """Run ``body`` under 8 virtual devices; body must print PASS."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        """
    ) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        cwd="/root/repo", timeout=480,
    )
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr[-3000:]}"
    assert "PASS" in out.stdout, out.stdout


class TestCodecs:
    def test_int8_roundtrip_error_bound(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (256,))
        q, s = int8_encode(g)
        err = np.abs(np.asarray(int8_decode(q, s) - g))
        assert err.max() <= float(s) * 0.5 + 1e-6

    def test_ef_sign_error_feedback_identity(self):
        """payload + error == grad + previous error (nothing is lost)."""
        g = jax.random.normal(jax.random.PRNGKey(1), (64,))
        e0 = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
        payload, e1 = ef_sign_encode(g, e0)
        np.testing.assert_allclose(
            np.asarray(payload + e1), np.asarray(g + e0), rtol=1e-5, atol=1e-6
        )

    def test_wire_bits_ordering(self):
        n = 10_000
        assert wire_bits("ef_sign", n) < wire_bits("int8", n) < wire_bits("none", n)


@pytest.mark.subprocess
class TestShardingRules:
    def test_divisible_spec_drops_ragged(self):
        run_subprocess("""
        from repro.distributed.sharding import param_shardings
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        logical = {"t": ("vocab", "embed")}
        abst = {"t": jax.ShapeDtypeStruct((50281, 64), jnp.float32)}
        sh = param_shardings(mesh, logical, abstract_tree=abst)
        assert sh["t"].spec == jax.sharding.PartitionSpec(None, "data"), sh["t"].spec
        print("PASS")
        """)

    def test_duplicate_axis_first_wins(self):
        run_subprocess("""
        from repro.distributed.sharding import param_shardings
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        logical = {"t": ("experts", "mlp", "embed")}
        abst = {"t": jax.ShapeDtypeStruct((8, 16, 32), jnp.float32)}
        sh = param_shardings(mesh, logical, abstract_tree=abst)
        # experts takes "model"; mlp (also model) must be dropped
        assert sh["t"].spec == jax.sharding.PartitionSpec("model", None, "data"), sh["t"].spec
        print("PASS")
        """)


@pytest.mark.subprocess
class TestCompressedDP:
    def test_ef_sign_dp_converges(self):
        """Explicit-DP shard_map step with EF-sign reaches the same loss
        region as exact reduction on a least-squares problem."""
        run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (
            CompressionState, build_dp_train_step)
        from repro.optim import constant, sgd_momentum
        from repro.train.step import init_state

        mesh = jax.make_mesh((8,), ("data",))
        k = jax.random.PRNGKey(0)
        w_true = jax.random.normal(k, (16,))
        X = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        y = X @ w_true

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            l = jnp.mean((pred - batch["y"]) ** 2)
            return l, {}

        results = {}
        for kind in ("none", "ef_sign", "int8"):
            opt = sgd_momentum(constant(0.05), momentum=0.0)
            state = init_state({"w": jnp.zeros((16,))}, opt)
            comp = CompressionState.init(state.params, kind)
            step = build_dp_train_step(loss_fn, opt, mesh, compression=kind)
            for i in range(300):
                state, comp, m = step(state, comp, {"x": X, "y": y})
            results[kind] = float(m["loss"])
        assert results["none"] < 1e-3, results
        assert results["int8"] < 1e-2, results
        assert results["ef_sign"] < 5e-2, results
        print("PASS")
        """)


@pytest.mark.subprocess
class TestGPipe:
    def test_pipeline_matches_sequential(self):
        """4-stage GPipe output == running the stages sequentially."""
        run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import (
            build_gpipe_apply, bubble_fraction)

        mesh = jax.make_mesh((4,), ("stage",))
        S, M, MB, D = 4, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), S)
        Ws = jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in ks])

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        apply = build_gpipe_apply(
            stage_fn, mesh, params_spec=P("stage"),
        )
        x = jax.random.normal(jax.random.PRNGKey(9), (M, MB, D))
        y_pipe = apply(Ws, x)

        y_ref = x
        for s in range(S):
            y_ref = jnp.tanh(y_ref @ Ws[s])
        np.testing.assert_allclose(
            np.asarray(y_pipe), np.asarray(y_ref), rtol=2e-4, atol=2e-5)
        assert abs(bubble_fraction(8, 4) - 3/11) < 1e-9
        print("PASS")
        """)

    def test_pipeline_is_differentiable(self):
        run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import build_gpipe_apply

        mesh = jax.make_mesh((4,), ("stage",))
        S, M, MB, D = 4, 4, 2, 8
        Ws = jnp.stack([jax.random.normal(jax.random.PRNGKey(i), (D, D)) * 0.3
                        for i in range(S)])
        x = jax.random.normal(jax.random.PRNGKey(9), (M, MB, D))
        apply = build_gpipe_apply(stage_fn := (lambda w, h: jnp.tanh(h @ w)),
                                  mesh, params_spec=P("stage"))

        def loss_pipe(Ws):
            return jnp.sum(apply(Ws, x) ** 2)

        def loss_ref(Ws):
            h = x
            for s in range(S):
                h = jnp.tanh(h @ Ws[s])
            return jnp.sum(h ** 2)

        g_pipe = jax.grad(loss_pipe)(Ws)
        g_ref = jax.grad(loss_ref)(Ws)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                                   rtol=5e-4, atol=5e-5)
        print("PASS")
        """)


@pytest.mark.subprocess
class TestShardedServe:
    def test_tp_logits_parity_and_tile_bytes(self):
        """Tensor-parallel serve (tile rows sharded over a 4-way model
        axis) reproduces the single-device logits through prefill AND
        decode, and each device holds exactly 1/TP of the tile bytes."""
        run_subprocess("""
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import build_model, get_config
        from repro.distributed.sharding import axis_rules, param_shardings
        from repro.nn import module as mod
        from repro.nn.context import SERVE, TRAIN, ModelContext
        from repro.serve.weights import (
            export_serving_params, per_device_tile_bytes, tile_serving_bytes)

        TP = 4
        cfg = get_config("granite-8b").reduced()
        tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                           compute_dtype=jnp.float32))
        sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                           compute_dtype=jnp.float32,
                                           use_pallas=False))
        tp0 = mod.init_params(tm.specs(), jax.random.PRNGKey(0))
        sp = export_serving_params(tm.specs(), sm.specs(), tp0, cfg.tbn)
        batch = {"tokens": jnp.array([[5, 3, 2, 7, 1, 4, 6, 2]], jnp.int32)}

        ref_lg, ref_c, ref_len = jax.jit(
            lambda p, b: sm.prefill(p, b, 16))(sp, batch)

        mesh = make_auto_mesh((TP,), ("model",))
        logical = mod.logical_axes(sm.specs())
        abstract = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), sp)
        placed = jax.device_put(
            sp, param_shardings(mesh, logical, abstract_tree=abstract))
        with axis_rules(mesh):
            sh_lg, sh_c, sh_len = jax.jit(
                lambda p, b: sm.prefill(p, b, 16))(placed, batch)
        np.testing.assert_allclose(
            np.asarray(ref_lg), np.asarray(sh_lg), atol=1e-5)

        tok = jnp.argmax(ref_lg, -1)[:, None].astype(jnp.int32)
        t1 = t2 = tok
        for _ in range(4):
            ref_lg, ref_c, ref_len = jax.jit(sm.decode_step)(
                sp, t1, ref_c, ref_len)
            with axis_rules(mesh):
                sh_lg, sh_c, sh_len = jax.jit(sm.decode_step)(
                    placed, t2, sh_c, sh_len)
            np.testing.assert_allclose(
                np.asarray(ref_lg), np.asarray(sh_lg), atol=1e-5)
            t1 = jnp.argmax(ref_lg, -1)[:, None].astype(jnp.int32)
            t2 = jnp.argmax(sh_lg, -1)[:, None].astype(jnp.int32)
            assert (np.asarray(t1) == np.asarray(t2)).all()

        total = tile_serving_bytes(sp)
        per_dev = per_device_tile_bytes(placed)
        assert len(per_dev) == TP, per_dev
        for dev, nbytes in per_dev.items():
            assert nbytes * TP == total, (dev, nbytes, total)
        print("PASS")
        """)

    def test_tp_decode_matvec_parity(self):
        """The decode small-m dispatch engages inside the shard_map
        tensor-parallel wrapper (per-shard m stays tiny, per-shard r is
        r/TP) and matches the dense reconstruction oracle at m in
        {1, 3, 8} on a 4-way model mesh."""
        run_subprocess("""
        from repro.launch.mesh import make_auto_mesh
        from repro.core.packing import pack_bits
        from repro.core.tiling import plan_tiling
        from repro.distributed.sharding import axis_rules
        from repro.kernels.ops import tiled_dense_infer
        from repro.kernels.ref import tiled_matmul_ref

        mesh = make_auto_mesh((4,), ("model",))
        p_rep, n_in = 4, 128
        spec = plan_tiling((4 * 64, n_in), p=p_rep, min_size=1,
                           alpha_source="W")
        r = spec.rows_per_tile          # 64 -> 16 unique rows per shard
        t = jnp.where(jax.random.bernoulli(
            jax.random.PRNGKey(1), 0.5, (spec.q,)), 1.0, -1.0)
        rows = pack_bits(t.reshape(r, n_in))
        flat = pack_bits(t)
        alpha = jnp.abs(jax.random.normal(
            jax.random.PRNGKey(2), (spec.n_alpha,))) + 0.1
        for m in (1, 3, 8):
            x = jax.random.normal(jax.random.PRNGKey(m), (m, n_in))
            want = tiled_matmul_ref(x, flat, alpha, n_out=4 * 64, p=p_rep)
            with axis_rules(mesh):
                got = tiled_dense_infer(x, rows, alpha, spec,
                                        use_pallas=True)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
        print("PASS")
        """)

    def test_engine_mesh_per_slot_sampling_parity(self):
        """Per-slot sampling params survive the mesh path: a mixed batch
        (explicit greedy / temperature / top-k requests over a stochastic
        engine default) generates identical tokens single-device vs TP=4."""
        run_subprocess("""
        from repro.launch.mesh import make_auto_mesh
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
        work = [
            ([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=4)),
            ([4, 5], SamplingParams(temperature=1.0, max_tokens=4)),
            ([6, 7, 8], SamplingParams(temperature=1.0, top_k=2,
                                       max_tokens=4)),
        ]
        outs = {}
        for name, mesh in [
            ("single", None),
            ("tp", make_auto_mesh((4,), ("model",))),
        ]:
            eng = BatchedEngine(
                sm, sp,
                ServeConfig(n_slots=3, max_len=64, chunk_tokens=8,
                            temperature=0.7, seed=11),
                mesh=mesh,
            )
            reqs = [eng.submit(p, sp_) for p, sp_ in work]
            eng.run_until_drained()
            outs[name] = [r.output for r in reqs]
        assert outs["single"] == outs["tp"], outs
        print("PASS")
        """)

    def test_engine_mesh_token_parity(self):
        """BatchedEngine(mesh=...) generates the same tokens as the
        single-device engine for a batch of concurrent requests."""
        run_subprocess("""
        from repro.launch.mesh import make_auto_mesh
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
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        outs = {}
        for name, mesh in [
            ("single", None),
            ("tp", make_auto_mesh((2, 4), ("data", "model"))),
        ]:
            eng = BatchedEngine(
                sm, sp,
                ServeConfig(n_slots=3, max_len=64, chunk_tokens=8),
                mesh=mesh,
            )
            reqs = [eng.submit(p, SamplingParams(max_tokens=4))
                    for p in prompts]
            eng.run_until_drained()
            outs[name] = [r.output for r in reqs]
        assert outs["single"] == outs["tp"], outs
        print("PASS")
        """)

    def test_engine_mesh_splits_kv_pool_over_model(self):
        """On a (2, 2) data x model mesh every chip holds exactly half of
        the KV pool (KV heads over model, pages replicated over data),
        before and after a drain, and tokens match the single device."""
        run_subprocess("""
        import dataclasses
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import build_model, get_config
        from repro.nn import module as mod
        from repro.nn.context import SERVE, TRAIN, ModelContext
        from repro.serve.engine import BatchedEngine, ServeConfig
        from repro.serve.sampling import SamplingParams
        from repro.serve.weights import (
            export_serving_params, per_device_bytes)

        cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                                  n_kv=4)
        tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                           compute_dtype=jnp.float32))
        sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                           compute_dtype=jnp.float32,
                                           use_pallas=False))
        tp0 = mod.init_params(tm.specs(), jax.random.PRNGKey(0))
        sp = export_serving_params(tm.specs(), sm.specs(), tp0, cfg.tbn)

        outs = {}
        for name, mesh in [
            ("single", None),
            ("mesh", make_auto_mesh((2, 2), ("data", "model"))),
        ]:
            eng = BatchedEngine(
                sm, sp,
                ServeConfig(n_slots=2, max_len=64, chunk_tokens=8),
                mesh=mesh,
            )
            total = sum(l.nbytes for l in jax.tree.leaves(eng.caches))
            if mesh is not None:
                eng.warmup()
                before = per_device_bytes(jax.tree.leaves(eng.caches))
                assert len(before) == 4, before
                assert all(2 * b == total for b in before.values()), before
            reqs = [eng.submit(p, SamplingParams(max_tokens=4))
                    for p in ([1, 2, 3], [4, 5])]
            eng.run_until_drained()
            outs[name] = [r.output for r in reqs]
            if mesh is not None:
                assert per_device_bytes(jax.tree.leaves(eng.caches)) == before
        assert outs["single"] == outs["mesh"], outs
        print("PASS")
        """)


@pytest.mark.subprocess
class TestMultiDeviceTrainStep:
    def test_production_sharded_train_step_runs(self):
        """A reduced arch train step EXECUTES on a (2,4) host mesh with the
        production sharding rules (not just lowers — runs and updates)."""
        run_subprocess("""
        import dataclasses
        from repro.configs import get_config, build_model
        from repro.distributed.sharding import axis_rules, param_shardings
        from repro.launch.mesh import make_host_mesh
        from repro.nn import module as mod
        from repro.nn.context import TRAIN, ModelContext
        from repro.optim import adamw, cosine_with_warmup
        from repro.train.step import build_train_step, init_state

        cfg = get_config("granite-8b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=2, vocab=128)
        model = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN))
        params = mod.init_params(model.specs(), jax.random.PRNGKey(0))
        opt = adamw(cosine_with_warmup(1e-3, 2, 100))
        state = init_state(params, opt)
        step = build_train_step(model.train_forward, opt)
        mesh = make_host_mesh(2, 4)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
        with axis_rules(mesh):
            state2, metrics = jax.jit(step)(state, {"tokens": toks})
        assert jnp.isfinite(metrics["loss"]), metrics
        # params actually moved
        delta = jax.tree_util.tree_reduce(
            lambda a, b: a + float(jnp.sum(jnp.abs(b[0] - b[1]))),
            jax.tree.map(lambda a, b: (a, b), state.params, state2.params),
            0.0)
        assert delta > 0
        print("PASS")
        """)
