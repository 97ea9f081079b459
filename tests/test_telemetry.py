"""Telemetry test wall (repro.serve.telemetry + its engine/server wiring).

The load-bearing property: telemetry is OBSERVATION ONLY. With it on
(metrics + spans + trace ring) or off, the engine emits byte-identical
tokens — greedy and stochastic — pinned here as a parity wall. On top
of that, the numeric layer is held to references: histogram counts and
quantiles against numpy, the Prometheus exposition against a format
lint (cumulative buckets, +Inf == _count, HELP/TYPE per family), span
phase attribution against the wall clock (disjoint phases sum to the
request's wall time, across preemption parks and encdec ENCODE), and
the steady-state retrace detector against both a forced retrace (must
fire, warn once) and a clean post-warmup run (must stay silent).
"""
import asyncio
import functools
import json
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.nn import module as mod
from repro.nn.context import SERVE, TRAIN, ModelContext
from repro.serve.engine import BatchedEngine, ServeConfig
from repro.serve.sampling import SamplingParams
from repro.serve.weights import export_serving_params
from repro.serve.telemetry import (
    DECODE,
    DURATION_BUCKETS,
    ENCODE,
    PARKED,
    PREFILL,
    QUEUE,
    TICK_PHASES,
    EngineTelemetry,
    Histogram,
    MetricsRegistry,
    RequestSpan,
    TraceRing,
    log_buckets,
)

KEY = jax.random.PRNGKey(0)


def _export(arch):
    cfg = get_config(arch).reduced()
    tm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=TRAIN,
                                       compute_dtype=jnp.float32))
    sm = build_model(cfg, ModelContext(policy=cfg.tbn, mode=SERVE,
                                       compute_dtype=jnp.float32,
                                       use_pallas=False))
    tp = mod.init_params(tm.specs(), KEY)
    sp = export_serving_params(tm.specs(), sm.specs(), tp, cfg.tbn)
    return cfg, sm, sp


@functools.lru_cache(maxsize=None)
def build_serve(arch="granite-8b"):
    return _export(arch)


def make_engine(**cfg_kw):
    _, sm, sp = build_serve()
    kw = dict(n_slots=2, max_len=64, chunk_tokens=8, page_tokens=8)
    kw.update(cfg_kw)
    return BatchedEngine(sm, sp, ServeConfig(**kw))


def drain(eng, reqs):
    i = 0
    while eng.has_work:
        assert i < 2000, "engine wedged"
        eng.step()
        i += 1
    return [list(r.output) for r in reqs]


# ---------------------------------------------------------------------
# histogram / registry numerics


class TestHistogram:
    def test_counts_and_sum_match_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=-6, sigma=2, size=2000)
        h = Histogram(edges=DURATION_BUCKETS)
        for v in vals:
            h.observe(float(v))
        assert h.count == len(vals)
        assert h.sum == pytest.approx(float(np.sum(vals)))
        # per-bucket counts == numpy histogram over the same edges
        # (bucket i holds v <= edges[i], first bucket [0, edges[0]])
        edges = np.array((0.0,) + DURATION_BUCKETS + (np.inf,))
        ref, _ = np.histogram(vals, bins=edges)
        # np.histogram is right-exclusive, ours is right-INclusive; the
        # lognormal draw never lands exactly on an edge, so they agree
        assert h.counts == ref.tolist()

    @pytest.mark.parametrize("q", [0.1, 0.5, 0.9, 0.99])
    def test_quantile_within_one_bucket_of_numpy(self, q):
        rng = np.random.default_rng(1)
        vals = rng.lognormal(mean=-4, sigma=1.5, size=5000)
        h = Histogram(edges=DURATION_BUCKETS)
        for v in vals:
            h.observe(float(v))
        est, ref = h.quantile(q), float(np.quantile(vals, q))
        # bucket-interpolated estimate is accurate to one bucket width;
        # edges grow by 10^(1/6) per bucket
        growth = 10 ** (1 / 6)
        assert ref / growth <= est <= ref * growth, (q, est, ref)

    def test_empty_and_overflow(self):
        h = Histogram(edges=(1.0, 10.0))
        assert h.quantile(0.5) is None
        h.observe(5000.0)  # beyond the last edge -> +Inf bucket
        assert h.counts == [0, 0, 1]
        assert h.quantile(0.5) == 10.0  # clamped to last finite edge

    def test_log_buckets_shape(self):
        edges = log_buckets(1e-3, 1.0, per_decade=3)
        assert edges[0] == 1e-3
        assert list(edges) == sorted(set(edges))
        assert len(edges) == 10  # 3 decades * 3 + endpoint
        with pytest.raises(ValueError):
            log_buckets(0, 1.0)


class TestRegistry:
    def test_counter_gauge_labels_and_values(self):
        r = MetricsRegistry()
        c = r.counter("t_total", "help", labels=("kind",))
        c.labels(kind="a").inc()
        c.labels(kind="a").inc(2)
        c.labels(kind="b").inc()
        g = r.gauge("t_gauge", fn=lambda: 42)
        assert r.value_of("t_total", kind="a") == 3
        assert r.value_of("t_total", kind="b") == 1
        assert r.value_of("t_total", kind="zzz") is None
        assert g.get() == 42

    def test_reregistration_idempotent_and_conflict(self):
        r = MetricsRegistry()
        a = r.counter("x_total", labels=("k",))
        assert r.counter("x_total", labels=("k",)) is a
        with pytest.raises(ValueError):
            r.gauge("x_total")
        with pytest.raises(ValueError):
            r.counter("x_total", labels=("other",))
        with pytest.raises(ValueError):
            r.counter("bad name")

    def test_exposition_format_lint(self):
        """render() must be parseable Prometheus text: TYPE per family,
        cumulative non-decreasing buckets, +Inf bucket == _count."""
        r = MetricsRegistry()
        r.counter("lint_total", "a counter").inc(3)
        r.gauge("lint_gauge", "a gauge").set(1.5)
        h = r.histogram("lint_seconds", "a histogram", labels=("phase",))
        for i in range(50):
            h.labels(phase="p").observe(10 ** ((i % 9) - 5))
        text = r.render()
        assert text.endswith("\n")
        types, samples = {}, {}
        for line in text.splitlines():
            if line.startswith("# TYPE "):
                _, _, name, t = line.split()
                types[name] = t
            elif not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                float(val)  # every sample value parses
                samples[key] = float(val)
        assert types == {"lint_total": "counter", "lint_gauge": "gauge",
                         "lint_seconds": "histogram"}
        assert samples["lint_total"] == 3
        assert samples["lint_gauge"] == 1.5
        buckets = [(k, v) for k, v in samples.items()
                   if k.startswith("lint_seconds_bucket")]
        cums = [v for _, v in buckets]
        assert cums == sorted(cums), "buckets must be cumulative"
        assert 'le="+Inf"' in buckets[-1][0]
        assert buckets[-1][1] == samples['lint_seconds_count{phase="p"}'] == 50

    def test_label_escaping(self):
        r = MetricsRegistry()
        r.counter("esc_total", labels=("k",)).labels(k='a"b\nc\\d').inc()
        line = [l for l in r.render().splitlines()
                if l.startswith("esc_total{")][0]
        assert line == 'esc_total{k="a\\"b\\nc\\\\d"} 1'


# ---------------------------------------------------------------------
# spans + trace ring (pure, no engine)


class TestSpanAndRing:
    def test_phases_disjoint_and_cover_wall(self):
        s = RequestSpan(rid=1, now=100.0)
        s.mark_admit(101.0, PREFILL)     # 1s queued
        s.to_phase(DECODE, 101.5)        # 0.5s prefill
        s.to_phase(PARKED, 102.0)        # 0.5s decode
        s.to_phase(DECODE, 103.0)        # 1s parked
        s.finish(103.25, "length")       # 0.25s decode
        assert s.phases == {QUEUE: 1.0, PREFILL: 0.5,
                            DECODE: 0.75, PARKED: 1.0}
        assert sum(s.phases.values()) == pytest.approx(s.wall) == 3.25

    def test_token_marks_first(self):
        s = RequestSpan(rid=0, now=0.0)
        assert s.token(1.0) is True
        assert s.token(2.0) is False
        assert (s.first_token_t, s.last_token_t) == (1.0, 2.0)

    def test_ring_drops_oldest_and_counts(self):
        ring = TraceRing(capacity=3)
        for i in range(5):
            ring.emit("e", i=i)
        assert len(ring) == 3 and ring.dropped == 2
        assert [r["i"] for r in ring.drain()] == [2, 3, 4]
        assert len(ring) == 0

    def test_ring_jsonl_sink(self, tmp_path):
        ring = TraceRing(capacity=8)
        ring.emit("submit", rid=1)
        ring.emit("finish", rid=1, reason="length")
        path = tmp_path / "trace.jsonl"
        assert ring.write_jsonl(path) == 2
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        assert [r["event"] for r in recs] == ["submit", "finish"]
        assert all("ts" in r for r in recs)


# ---------------------------------------------------------------------
# engine wiring


class TestEngineTelemetry:
    def test_token_parity_on_vs_off(self, tmp_path):
        """The wall: byte-identical stochastic tokens with telemetry
        (metrics + spans + ring) off, on, and on under a running
        profiler trace, prefix cache exercised."""
        rng = np.random.default_rng(2)
        prompts = [[int(t) for t in rng.integers(0, 64, size=n)]
                   for n in (5, 11, 7, 9)]
        params = [SamplingParams(max_tokens=6, temperature=0.9, top_k=8,
                                 seed=50 + i) for i in range(len(prompts))]
        outs = {}
        for mode in ("off", "on", "profiled"):
            on = mode != "off"
            eng = make_engine(telemetry=on, prefix_cache=True,
                              trace_events=64 if on else 0)
            reqs = [eng.submit(p, sp) for p, sp in zip(prompts, params)]
            if mode == "profiled":
                with jax.profiler.trace(str(tmp_path)):
                    outs[mode] = drain(eng, reqs)
            else:
                outs[mode] = drain(eng, reqs)
        assert outs["on"] == outs["off"]
        assert outs["profiled"] == outs["off"]

    def test_lifecycle_metrics_and_spans(self):
        eng = make_engine(trace_events=64)
        reqs = [eng.submit([1, 2, 3, 4, 5], SamplingParams(max_tokens=4))
                for _ in range(3)]
        drain(eng, reqs)
        tel = eng.tel
        assert tel.submitted.get() == 3
        assert tel.finished.labels(reason="length").get() == 3
        assert tel.tokens.get() == 12
        assert tel.ttft._solo().count == 3   # one first token per request
        assert tel.itl._solo().count == 9    # 3 tokens after the first, each
        assert tel.tick._solo().count > 0
        observed = {p for p, h in tel.tick_phase.items() if h.count}
        assert {"admission", "extend.prepare", "extend.launch",
                "extend.wait", "extend.emit", "decode.prepare",
                "decode.launch", "decode.wait", "decode.emit"} <= observed
        assert "preempt" not in observed     # preemption off: never run
        assert "encode" not in observed      # decoder-only: never charged
        # spans: disjoint phases cover [submit, finish] for every request
        for r in reqs:
            s = r.span
            assert s.finish_reason == "length"
            assert set(s.phases) <= {QUEUE, PREFILL, DECODE}
            assert sum(s.phases.values()) <= s.wall + 1e-6
            assert sum(s.phases.values()) == pytest.approx(s.wall, rel=1e-3)
        events = [e["event"] for e in eng.tel.ring.drain()]
        assert events.count("submit") == events.count("finish") == 3
        # stats() surfaces the quantile summary
        st = eng.stats()
        assert st["latency"]["ttft_ms"]["count"] == 3
        assert st["retraces"] == 0

    def test_span_phases_across_preemption(self):
        """A preempted request's span charges its parked time to PARKED
        and still covers its wall."""
        eng = make_engine(n_slots=1, priorities=True, preempt=True,
                          max_queued=8)
        lo = eng.submit([1, 2, 3], SamplingParams(
            max_tokens=8, priority="batch"))
        for _ in range(3):
            eng.step()  # batch request admitted and decoding
        hi = eng.submit([4, 5], SamplingParams(
            max_tokens=2, priority="interactive"))
        drain(eng, [lo, hi])
        assert eng.tel.preempts.get() >= 1
        assert eng.tel.resumes.get() >= 1
        s = lo.span
        assert s.phases.get(PARKED, 0.0) > 0.0
        assert sum(s.phases.values()) == pytest.approx(s.wall, rel=1e-3)
        assert list(lo.output) and list(hi.output)

    def test_span_encode_phase_encdec(self):
        """Encoder-decoder admission charges span time to ENCODE and the
        tick breakdown records the encode phase."""
        cfg, sm, sp = build_serve("seamless-m4t-large-v2")
        eng = BatchedEngine(sm, sp, ServeConfig(
            n_slots=2, max_len=64, chunk_tokens=8, page_tokens=8,
            enc_tokens=16))
        frames = np.random.default_rng(3).standard_normal(
            (9, cfg.d_model)).astype(np.float32)
        req = eng.submit([3, 1, 4], SamplingParams(max_tokens=3),
                         frames=frames)
        drain(eng, [req])
        assert eng.tel.encode_ticks.get() == 1
        assert eng.tel.tick_phase["encode"].count == 1
        s = req.span
        assert s.phases.get(ENCODE, 0.0) > 0.0
        assert sum(s.phases.values()) == pytest.approx(s.wall, rel=1e-3)

    def test_pool_and_queue_gauges(self):
        eng = make_engine(prefix_cache=True)
        r = eng.tel.registry
        assert r.value_of("serve_pool_pages", family="self_attn") == \
            eng.pool.n_pages
        assert r.value_of("serve_pool_utilization", family="self_attn") == 0.0
        reqs = [eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=2))
                for _ in range(2)]
        assert r.value_of("serve_queue_depth") == 2
        eng.step()
        assert r.value_of("serve_queue_depth") == 0
        assert r.value_of("serve_live_slots") == 2
        assert r.value_of("serve_pool_utilization", family="self_attn") > 0.0
        drain(eng, reqs)
        lookups = (r.value_of("serve_prefix_lookups_total", result="hit")
                   + r.value_of("serve_prefix_lookups_total", result="miss"))
        assert lookups == 2  # one trie lookup per admission

    def test_telemetry_off_is_off(self, monkeypatch):
        eng = make_engine(telemetry=False)
        assert eng.tel is None
        req = eng.submit([1, 2, 3], SamplingParams(max_tokens=2))
        # no tick or tick phase opens a span or reads a clock: Timed, the
        # one thing that does, is never made
        from repro.serve import telemetry

        def opened(*a, **k):
            raise AssertionError("a tick phase was timed")
        monkeypatch.setattr(telemetry, "Timed", opened)
        drain(eng, [req])
        assert req.span is None
        st = eng.stats()
        assert "latency" not in st and "tick_phase_s" not in st

    def test_trace_events_requires_telemetry(self):
        with pytest.raises(ValueError):
            make_engine(telemetry=False, trace_events=16)


class TestTickPhases:
    """The tick phases (``telemetry.TICK_PHASES``): they tile each work
    tick, reach the profiler as ``serve.*`` spans, and the model's named
    scopes reach the compiled ticks."""

    def test_phases_cover_the_tick_and_count_every_call(self):
        eng = make_engine(n_slots=3, chunk_tokens=4)
        calls = {"extend": 0, "decode": 0}
        for kind in calls:
            run = getattr(eng, f"_run_{kind}")

            def counted(arg, run=run, kind=kind):
                calls[kind] += 1
                return run(arg)
            setattr(eng, f"_run_{kind}", counted)
        reqs = [eng.submit(list(range(1, n)), SamplingParams(max_tokens=5))
                for n in (6, 13, 9, 3)]
        drain(eng, reqs)
        st = eng.stats()
        assert set(st["tick_phase_s"]) == set(TICK_PHASES)
        phases = sum(st["tick_phase_s"].values())
        assert 0.95 * st["tick_s"] <= phases <= st["tick_s"]
        assert st["tick_s"] == pytest.approx(eng.tel.tick._solo().sum)
        n = st["tick_phase_n"]
        assert n["admission"] == st["work_ticks"] == eng.tel.tick._solo().count
        for kind in calls:
            assert calls[kind] > 0
            for part in ("prepare", "launch", "wait", "emit"):
                assert n[f"{kind}.{part}"] == calls[kind], (kind, part)

    def test_profiler_trace_holds_the_phase_spans(self, tmp_path):
        import glob

        from jax.profiler import ProfileData

        eng = make_engine()
        reqs = [eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=3))]
        with jax.profiler.trace(str(tmp_path)):
            drain(eng, reqs)
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
        assert {"serve.tick", "serve.admission", "serve.extend.launch",
                "serve.decode.prepare", "serve.decode.launch",
                "serve.decode.wait", "serve.decode.emit"} <= names

    def test_compiled_ticks_carry_the_model_scopes(self):
        eng = make_engine()
        eng.warmup()
        for tick in ("decode_tick", "extend_tick"):
            text = eng._aot[tick].as_text()
            paths = re.findall(r'op_name="([^"]*)"', text)
            scopes = {part for p in paths for part in p.split("/")}
            assert {"embed", "attn", "ffn", "head", "kv_layer",
                    "sample"} <= scopes, tick
        assert "merge" in {part for p in re.findall(
            r'op_name="([^"]*)"', eng._aot["decode_tick"].as_text())
            for part in p.split("/")}


class TestRetraceDetector:
    """Needs a FRESH model per engine: the lazy jitted tick callables
    cache on the model object, so the lru-cached suite model would
    already hold traces for these shapes and mask the forced retrace."""

    def _fresh_engine(self):
        _, sm, sp = _export("granite-8b")
        return BatchedEngine(sm, sp, ServeConfig(
            n_slots=2, max_len=64, chunk_tokens=8, page_tokens=8))

    def test_silent_after_warmup_and_fires_on_forced_retrace(self):
        eng = self._fresh_engine()
        eng.warmup()
        reqs = [eng.submit([1, 2, 3], SamplingParams(max_tokens=3))
                for _ in range(2)]
        with warnings.catch_warnings():
            # a clean post-warmup run must never trace: any retrace
            # warning here is the regression the detector exists for
            warnings.simplefilter("error")
            drain(eng, reqs)
        assert eng.tel.retraces.get() == 0

        # force a retrace: drop the AOT decode executable so the tick
        # falls back to the lazy jit, AND clear jax's tracing caches
        # (warmup's .lower() seeded them, so the fallback alone would
        # reuse the cached jaxpr without re-running the Python body) —
        # the next decode tick genuinely re-traces
        eng._aot.pop("decode_tick")
        jax.clear_caches()
        req = eng.submit([4, 5], SamplingParams(max_tokens=2))
        with pytest.warns(RuntimeWarning, match="retrace"):
            drain(eng, [req])
        n = eng.tel.retraces.get()
        assert n >= 1
        assert eng.stats()["retraces"] == n

        # warn-once: further retraced ticks count but stay quiet
        eng._aot.pop("extend_tick")
        req = eng.submit([6, 7, 8, 9], SamplingParams(max_tokens=2))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            drain(eng, [req])
        assert eng.tel.retraces.get() > n


# ---------------------------------------------------------------------
# server exposition (in-process)


class TestMetricsEndpoint:
    def test_metrics_endpoint_and_http_histogram(self):
        from repro.serve.client import request_json, request_text, sse_generate
        from repro.serve.server import EngineServer, ServerConfig

        async def go():
            eng = make_engine()
            srv = EngineServer(eng, ServerConfig(host="127.0.0.1", port=0))
            port = await srv.start(aot=False)
            try:
                await sse_generate("127.0.0.1", port, {
                    "prompt": [1, 2, 3], "max_tokens": 3})
                status, text = await request_text(
                    "127.0.0.1", port, "GET", "/metrics")
                _, stats = await request_json(
                    "127.0.0.1", port, "GET", "/stats")
            finally:
                await srv.close()
            return status, text, stats

        status, text, stats = asyncio.run(go())
        assert status == 200
        for name in ("serve_requests_submitted_total 1",
                     "serve_tokens_total 3",
                     "# TYPE serve_tick_seconds histogram",
                     "# TYPE serve_http_request_seconds histogram",
                     'serve_http_request_seconds_count{route="/generate"} 1',
                     "serve_streams_opened_total 1"):
            assert name in text, f"missing from /metrics: {name!r}"
        for phase in TICK_PHASES:
            assert f'serve_tick_phase_seconds_count{{phase="{phase}"}}' \
                in text
        # enriched /stats carries the same quantile summary + http route
        assert stats["latency"]["ttft_ms"]["count"] == 1
        assert stats["latency"]["http_ms"]["/generate"]["count"] == 1

    def test_metrics_404_when_disabled(self):
        from repro.serve.client import request_text
        from repro.serve.server import EngineServer, ServerConfig

        async def go():
            eng = make_engine(telemetry=False)
            srv = EngineServer(eng, ServerConfig(host="127.0.0.1", port=0))
            port = await srv.start(aot=False)
            try:
                return await request_text("127.0.0.1", port, "GET",
                                          "/metrics")
            finally:
                await srv.close()

        status, body = asyncio.run(go())
        assert status == 404
        assert json.loads(body)["error"] == "telemetry_disabled"


class TestLoadgenScrapeHelpers:
    def test_parse_and_check_metrics(self):
        from benchmarks.loadgen import (
            REQUIRED_METRICS,
            check_metrics,
            parse_metrics,
            server_quantiles,
        )

        tel = EngineTelemetry()
        r = tel.registry
        # fill in the front-end families check_metrics requires
        r.histogram("serve_http_request_seconds", labels=("route",))
        r.counter("serve_streams_opened_total")
        r.gauge("serve_queue_depth", fn=lambda: 0)
        r.gauge("serve_live_slots", fn=lambda: 0)
        before = parse_metrics(r.render())
        tel.submitted.inc(4)
        tel.tokens.inc(40)
        for i in range(10):
            tel.tick.observe(0.002 * (i + 1))
            tel.ttft.observe(0.05)
            tel.itl.observe(0.002)
        after = parse_metrics(r.render())
        assert set(REQUIRED_METRICS) <= after["families"]
        deltas = check_metrics(before, after)
        assert deltas["serve_tokens_total"] == 40
        assert deltas["serve_tick_seconds_count"] == 10
        q = server_quantiles(after)
        assert q["server_ttft_p50_ms"] == pytest.approx(50, rel=0.5)
        assert q["server_tick_p50_ms"] == pytest.approx(10, rel=0.6)
        # regression must trip the monotonicity check
        with pytest.raises(AssertionError):
            check_metrics(after, before)
