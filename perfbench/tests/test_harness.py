"""Tests of the serving benchmark's harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

A run of a tiny cell goes through the whole harness (weights drawn from
the seed, the program's engine and server, the load client in its own
process, the window, the reference check) with the look for a chip
skipped; the program's tile layers take their structured path off the
chip. The same run with the timed path broken underneath has to come out
``correct: false``. A second model family, a small mixture of experts,
goes the same way from new files alone.
"""
from __future__ import annotations

import base64
import hashlib
import importlib.util
import io
import json
import pathlib
import shutil
import sys
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

from harness import counting, kernels, trace, traffic  # noqa: E402
from harness.peaks import peaks  # noqa: E402
from harness.registry import Registry  # noqa: E402

TINY_CELL = {"name": "tiny.chat", "config": "tiny", "traffic": "tiny.chat",
             "chips": 1, "why": "test-only tiny cell"}
MOE_CELL = {"name": "tiny-moe.chat", "config": "tiny-moe",
            "traffic": "tiny-moe.chat", "chips": 1,
            "why": "test-only tiny cell of a second model family"}
# the tiny cells' files: (name under tests/data, path under perfbench/)
DROPPED = [("tiny.json", "configs/tiny.json"),
           ("tiny.chat.json", "traffic/tiny.chat.json"),
           ("tiny-moe.json", "configs/tiny-moe.json"),
           ("tiny-moe.chat.json", "traffic/tiny-moe.chat.json"),
           ("moe_decoder_family.py", "families/moe_decoder.py"),
           ("moe_decoder_reference.py", "references/moe_decoder.py")]


@pytest.fixture()
def root(tmp_path):
    """A checkout holding the benchmark as committed, plus two tiny cells
    dropped in as new files only: their configurations, traffic mixes,
    the second cell's model family and reference, a new per-layer metric,
    and their entries in BENCHMARK.json."""
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for src, dst in DROPPED:
        assert not (tmp_path / "perfbench" / dst).exists(), dst
        shutil.copy(DATA / src, tmp_path / "perfbench" / dst)
    (tmp_path / "perfbench/metrics/requests_finished.py").write_text(
        "def read(run):\n"
        "    return sum(r['finished'] for r in run.records)\n")
    for name in ("tiny", "tiny-moe"):
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"perfbench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    spec["workloads"] += [TINY_CELL, MOE_CELL]
    for m in spec["end_to_end"]:
        if "workloads" in m:    # the tiny cells report every one of them
            m["workloads"] += [TINY_CELL["name"], MOE_CELL["name"]]
    spec["per_layer"].append({
        "name": "requests_finished", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "front end",
        "moves": "itl_p50_ms", "workloads": ["tiny.chat", "tiny-moe.chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def _run_module(root: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_run_under_test", root / "perfbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root, seed=3, seconds=4.0, trace_on=0, fault=None,
             control="", workload="tiny.chat"):
    mod = _run_module(root)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = mod.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace_on)],
                      allow_cpu=True, fault=fault, control=control)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---------------------------------------------------------------- units --
def _family(name):
    return Registry(BENCH.parent).family(name)


def test_unique_row_count_of_one_granite_layer():
    """By hand: granite-8b's q (4096 -> 4096), k and v (4096 -> 1024), o
    (4096 -> 4096), gate and up (4096 -> 14336), down (14336 -> 4096),
    p = 8, on m = 16 rows: 2 * m * (n_out / 8) * n_in each."""
    cfg = json.loads((BENCH / "configs" / "granite-8b.json").read_text())
    fam = _family(cfg["family"])
    m = 16
    want = 2 * m * (512 * 4096 * 2 + 128 * 4096 * 2 + 1792 * 4096 * 2
                    + 512 * 14336)
    got = sum(counting.tiled_linear(m, r, k, 8)[0]
              for r, k in fam.layer_shapes(cfg))
    assert got == want
    # bytes of the down projection: tile, alphas, bf16 in, f32 out
    _, nbytes = counting.tiled_linear(m, 512, 14336, 8)
    assert nbytes == 512 * 448 * 4 + 8 * 4 + m * 14336 * 2 + m * 512 * 4
    # a whole step: layers, attention pairs, head
    step = fam.step_flops(cfg, m, key_pairs=100, logit_rows=m)
    assert step == 36 * (want + 4 * 32 * 128 * 100) + 2 * m * 6144 * 4096
    # padded kernel shapes map back to the layer they serve
    assert counting.closest(fam.tiled_shapes(cfg), 512, 16384) == \
        (512, 14336)
    assert counting.closest(fam.tiled_shapes(cfg), 1792, 4096) == \
        (1792, 4096)


def test_unique_row_count_of_the_test_moe_family():
    """By hand, the tiny MoE (d 128, p 4, 3 layers, the first dense): per
    row, attention q/o (32 x 128 unique rows each) and k/v (16 x 128);
    the dense MLP 64/64/32 rows; in each MoE layer the router (4 x 128),
    2 picked experts and 1 shared, 32/32/32 rows each; the head 1024."""
    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    spec = importlib.util.spec_from_file_location(
        "moe_family", DATA / "moe_decoder_family.py")
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    m, pairs = 4, 10
    attn = 2 * m * (32 * 128 * 2 + 16 * 128 * 2) + 4 * 4 * 32 * pairs
    dense = 2 * m * (64 * 128 * 2 + 32 * 256)
    expert = 2 * m * 32 * 128 * 3
    moe = 2 * m * 4 * 128 + 2 * expert + expert
    assert fam.step_flops(cfg, m, pairs, 1) == \
        3 * attn + dense + 2 * moe + 2 * 1 * 1024 * 128
    assert counting.closest(fam.tiled_shapes(cfg), 64, 128) == (64, 128)
    assert counting.closest(fam.tiled_shapes(cfg), 32, 256) == (32, 256)


def _digest(params) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of the tiny configuration's weights (every leaf's path, dtype,
# shape and bytes, in tree order) as the harness drew them before the
# draw moved into the family module, on the CPU
WEIGHT_DIGESTS = {
    3: "5651f80549aac0b622e930336c37d383f3b0438850f4445777e701ac27503717",
    2**33 + 5:
        "4fe7f34f30e0807b290d593cca3ad33b9f501db1ff6c9ab8e3fac99db818e0eb",
}


@pytest.mark.parametrize("seed", sorted(WEIGHT_DIGESTS))
def test_dense_family_draws_the_weights_it_always_drew(seed):
    cfgd = json.loads((DATA / "tiny.json").read_text())
    fam = _family(cfgd["family"])
    cfg = {k: cfgd[k] for k in fam.KEYS}
    assert _digest(fam.build(cfg, seed)) == WEIGHT_DIGESTS[seed]


def _kernel_hlo(tick: str, fn: bytes, x: tuple, tile: tuple) -> str:
    """A compiled tick whose one instruction, ``fusion`` (an operation of
    the recorded trace), is a Pallas call of kernel function ``fn``."""
    body = base64.b64encode(
        b"module { func @" + fn + b"() loc(" + fn + b") }").decode()
    return (f"HloModule jit__{tick}\n\nENTRY %main () -> () {{\n"
            f"  ROOT %fusion = f32[{x[0]},{tile[0]}]{{1,0}} custom-call("
            f'%x, %t), custom_call_target="tpu_custom_call", '
            f"operand_layout_constraints={{bf16[{x[0]},{x[1]}]{{1,0}}, "
            f"s32[{tile[0]},{tile[1]}]{{1,0}}}}, backend_config={{"
            f'"custom_call_config": {{"body":"{body}"}}}}\n}}\n')


# config: (decode call x and tile shapes, extend call's), step_flops at
# (rows, key_pairs, logit_rows), rooflines on the recorded trace: the
# harness's readings before the counts moved into the family module
RECORDED_COUNTS = {
    "configs/granite-8b.json": (
        [((16, 16384), (512, 512)), ((512, 4096), (1792, 128))],
        {(16, 100, 16): 32271237120.0, (512, 70000, 3): 1046461022208.0,
         (8, 20000, 8): 27902607360.0},
        {"tiled_matvec": 51.772766042859,
         "tiled_matmul": 811.2106050438717}),
    "tests/data/tiny.json": (
        [((8, 256), (32, 8)), ((256, 128), (64, 4))],
        {(16, 100, 16): 7886848.0, (512, 70000, 3): 221552640.0,
         (8, 20000, 8): 34586624.0},
        {"tiled_matvec": 0.2741522697782949,
         "tiled_matmul": 3.429732914663548}),
}


@pytest.mark.parametrize("config", sorted(RECORDED_COUNTS))
def test_dense_family_counts_what_the_harness_always_counted(config):
    (xd, td), (xe, te) = RECORDED_COUNTS[config][0]
    cfgd = json.loads((BENCH / config).read_text())
    fam = _family(cfgd["family"])
    cfg = {k: cfgd[k] for k in fam.KEYS}
    for args, want in RECORDED_COUNTS[config][1].items():
        assert fam.step_flops(cfg, *args) == want, args
    run = SimpleNamespace(
        trace=trace.load(str(DATA / "tiny_tpu.xplane.pb")),
        peaks=peaks("TPU v5 lite"), cfg=cfg, family=fam,
        mix={"server": {"n_slots": 8, "chunk_tokens": 64}},
        hlo={"decode_tick": _kernel_hlo("decode_tick", b"_matvec_kernel",
                                        xd, td),
             "extend_tick": _kernel_hlo("extend_tick", b"_matmul_kernel",
                                        xe, te)})
    for kernel, want in RECORDED_COUNTS[config][2].items():
        assert kernels.roofline(run, kernel) == want, kernel
    assert kernels.roofline(run, "no_such_kernel") is None


def test_harness_names_no_model_layout():
    """What a model family knows stays in its family module: the harness
    and the entry point name none of the dense decoder's keys or leaves."""
    words = ("num_key_value_heads", "intermediate_size", "head_dim", "wq",
             "gated_mlp")
    for path in sorted((BENCH / "harness").glob("*.py")) + [BENCH / "run.py"]:
        text = path.read_text()
        assert not [w for w in words if w in text], path.name


BACKLOG = {"kind": "backlog", "waiting_per_slot": 2,
           "requests_per_second_max": 1}


@pytest.mark.parametrize("mix_name,arrivals", [
    ("granite-8b.chat", {"kind": "poisson", "rate": 4.0}),
    ("granite-8b.docqa", {"kind": "poisson", "rate": 4.0}),
    ("granite-8b.chat", BACKLOG)], ids=["poisson", "documents", "backlog"])
def test_traffic_is_the_same_for_the_same_seed(mix_name, arrivals):
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    mix["arrivals"] = arrivals
    a = traffic.generate(mix, 2**33 + 5, 20.0, 49152)
    b = traffic.generate(mix, 2**33 + 5, 20.0, 49152)
    c = traffic.generate(mix, 6, 20.0, 49152)
    assert a == b
    assert a != c

    # another seed: the same sizes due at the same times, other tokens
    def shape(s):
        return [(len(r["prompt"]), r["max_tokens"], r["due_s"])
                for r in s["requests"]]
    assert shape(a) == shape(c)
    assert [len(p) for p in a["setup"]] == [len(p) for p in c["setup"]]
    if "documents" not in mix:
        assert all(32 <= len(r["prompt"]) <= 2048 for r in a["requests"])
    if arrivals["kind"] == "poisson":
        assert len(a["requests"]) == round(arrivals["rate"] * 20.0)
    else:
        assert a["outstanding"] == mix["server"]["n_slots"] * 3
        assert all(r["due_s"] == 0.0 for r in a["requests"])


def test_trace_reduction_on_a_recorded_trace():
    """A trace recorded on a v5e of a toy tick loop: two executables run
    inside ``bench.step`` spans."""
    tr = trace.load(str(DATA / "tiny_tpu.xplane.pb"))
    assert tr.n_devices == 1
    assert tr.spans and all(s.name.startswith("bench.") for s in tr.spans)
    busy = trace.busy_intervals(tr, 0)
    assert busy and all(b[0] < b[1] for b in busy)
    steps = trace.union([(s.start, s.end) for s in tr.spans
                         if s.name == "bench.step"])
    inside = trace.covered(busy, steps)
    assert 0 < inside <= sum(e - s for s, e in steps)
    bd = trace.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert any(trace.module_matches(o.module, "decode_tick")
               for o in tr.ops[0])


def test_interval_arithmetic():
    assert trace.union([(3, 5), (0, 2), (1, 4)]) == [(0, 5)]
    assert trace.covered([(0, 2), (4, 6)], [(1, 5)]) == 2


# ------------------------------------------------------------ whole run --
def test_dropped_in_cell_mix_and_metric_are_found_by_name(root):
    reg = Registry(root)
    cell = reg.workload("tiny.chat")
    assert reg.config(cell["config"])["hidden_size"] == 128
    assert reg.traffic(cell["traffic"])["server"]["n_slots"] == 4
    assert "requests_finished" in reg.metrics("per_layer", "tiny.chat")
    assert "requests_finished" not in reg.metrics("per_layer",
                                                  "granite-8b.chat")
    run = type("Run", (), {"records": [{"finished": True}] * 3})()
    assert reg.reader("per_layer", "requests_finished").read(run) == 3


def test_a_sound_run_is_correct(root):
    res = run_cell(root)
    assert res["correct"], res
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) >= {"itl_p50_ms", "itl_p95_ms",
                                    "itl_mean_ms", "setup_s"}
    assert list(res)[-1] == "check"
    assert res["device"]["platform"] == "cpu"


def test_a_traced_run_reads_the_per_layer_counts(root):
    res = run_cell(root, seed=11, trace_on=1)
    assert res["correct"], res
    assert res["metrics"]["requests_finished"]["value"] > 0


def _stale_decode(eng):
    """A decode step that returns its KV state unchanged."""
    fn = eng._aot["decode_tick"]

    def call(params, tokens, caches, *rest):
        nxt, _, lengths = fn(params, tokens, caches, *rest)
        return nxt, caches, lengths
    eng._aot["decode_tick"] = call


def _half_batch(eng):
    """A decode step that leaves out every other live slot: half of the
    batch, wherever in the slots the live requests sit."""
    fn = eng._aot["decode_tick"]

    def call(params, tokens, caches, lengths, active, *rest):
        import jax.numpy as jnp
        keep = active & (jnp.cumsum(active) % 2 == 1)
        nxt, caches, lengths = fn(params, tokens, caches, lengths, keep,
                                  *rest)
        return nxt, caches, lengths
    eng._aot["decode_tick"] = call


def _altered_token(eng):
    """A token altered where it is produced: every 5th decode token."""
    fn = eng._aot["decode_tick"]

    def call(*args):
        nxt, caches, lengths = fn(*args)
        return (nxt + (eng.steps % 5 == 0)) % eng.model.cfg.vocab, caches, \
            lengths
    eng._aot["decode_tick"] = call


@pytest.mark.parametrize("fault", [_stale_decode, _half_batch,
                                   _altered_token],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = run_cell(root, seed=5, fault=fault)
    assert not res["correct"], res["check"]


def test_ticks_that_bypass_the_spans_fail_the_run(root):
    """A decode call that no longer goes through the benchmark's wrapper
    (as a fused tick would) stops the run instead of leaving the span
    metrics out."""
    def bypass(eng):
        eng._run_decode = type(eng)._run_decode.__get__(eng)
    with pytest.raises(RuntimeError, match="no decode span"):
        run_cell(root, seed=9, fault=bypass)


def test_the_control_is_not_correct(root):
    """The reference computed in fp8 (e4m3, wherever the program rounds to
    bf16), put in the program's place on the same prompts and served
    tokens, fails the limit."""
    res = run_cell(root, seed=7, control="fp8")
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("broken", [{}, {"fault": _altered_token},
                                    {"control": "fp8"}],
                         ids=["sound", "token_altered", "control"])
def test_a_new_family_runs_from_new_files_alone(root, broken):
    """The tiny MoE cell, whose model family, reference, configuration and
    traffic mix the fixture drops in as new files, goes through the whole
    run: correct as it is; not correct with the timed path broken, or
    with the reference in fp8 in the program's place. (Its limit, 0.15,
    lies between the widest gaps of sound runs on the CPU, 0.009-0.069 on
    10 seeds, and the fp8 control's, 0.36-0.92 on 4.)"""
    res = run_cell(root, seed=13, workload="tiny-moe.chat", **broken)
    assert res["correct"] is (not broken), res["check"]
    assert res["attempted"] > 10 and res["failed"] == 0
