"""Tests of the readers of the program's tick phases and named scopes
(``harness/scopes.py`` and the metrics built on it), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

from harness import scopes, trace  # noqa: E402
from harness.registry import Registry  # noqa: E402

SCOPE_METRICS = {
    "decode_attention_device_ms": ("decode_tick", "attn"),
    "decode_ffn_device_ms": ("decode_tick", "ffn"),
    "decode_unscoped_device_ms": ("decode_tick", scopes.UNSCOPED),
    "extend_attention_device_ms": ("extend_tick", "attn"),
    "extend_ffn_device_ms": ("extend_tick", "ffn"),
}

# a compiled decode tick, cut to the instruction lines the reader reads
DECODE_HLO = """\
HloModule jit__decode_tick, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[2]) -> f32[2] {
  ROOT %neg.9 = f32[2]{0} negate(%param_0), metadata={op_name="jit(_decode_tick)/attn/neg"}
}

ENTRY %main.1 () -> () {
  %gather_fusion = bf16[8,4096]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_tick)/embed/jit(_take)/gather" stack_frame_id=1}
  %while.2 = (s32[]) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(_decode_tick)/while"}
  %fusion.3 = bf16[8,4096]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_tick)/while/body/closed_call/attn/dot_general" stack_frame_id=2}
  %closed_call.4 = f32[8,1792]{1,0} custom-call(%x, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_tick)/while/body/closed_call/ffn/pallas_call"}
  %fusion.5 = bf16[2,16]{1,0} fusion(%b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_tick)/while/body/kv_layer/dynamic_update_slice"}
  %copy.6 = bf16[64,16,8,128]{3,2,1,0} copy(%pool)
  %fusion.7 = f32[8,49152]{1,0} fusion(%h), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_tick)/head/dot_general"}
  %sort.8 = f32[8,49152]{1,0} sort(%l), dimensions={1}, metadata={op_name="jit(_decode_tick)/sample/sort"}
  ROOT %select.9 = s32[8]{0} select(%m, %n, %o), metadata={op_name="jit(_decode_tick)/merge/select_n"}
}
"""
# ns each operation takes in every one of the tick's two runs
TOOK = {"gather_fusion": 5, "fusion.3": 40, "closed_call.4": 100,
        "fusion.5": 7, "copy.6": 30, "fusion.7": 11, "sort.8": 13,
        "select.9": 2}


def _decode_trace(runs=2) -> trace.Trace:
    ops, modules = [], []
    t = 0.0
    for _ in range(runs):
        start = t
        for name, ns in TOOK.items():
            if name == "fusion.3":       # the layer loop holds these three
                loop = sum(TOOK[n] for n in ("fusion.3", "closed_call.4",
                                             "fusion.5"))
                ops.append(trace.Op(t, t + loop, "while.2", ""))
            ops.append(trace.Op(t, t + ns, name, ""))
            t += ns
        modules.append(trace.Op(start, t, "jit__decode_tick",
                                "jit__decode_tick"))
        t += 50                          # idle between ticks
    ops.sort(key=lambda o: (o.start, -o.end))
    trace.mark_containers(ops)
    for o in ops:
        o.module = "jit__decode_tick"
    return trace.Trace({0: ops}, {0: modules}, [])


def test_scope_of_takes_the_innermost_scope():
    assert scopes.scope_of("jit(_decode_tick)/while/body/attn/dot") == "attn"
    assert scopes.scope_of("jit(t)/kv_layer/attn/x") == "attn"
    assert scopes.scope_of("jit(t)/attn/kv_layer/x") == "kv_layer"
    assert scopes.scope_of("jit(t)/while/body/closed_call/add") == \
        scopes.UNSCOPED
    table = scopes.instruction_scopes(DECODE_HLO)
    assert table["copy.6"] == scopes.UNSCOPED
    assert table["closed_call.4"] == "ffn" and table["select.9"] == "merge"


def test_scopes_partition_the_tick_leaf_time_exactly():
    run = SimpleNamespace(trace=_decode_trace(), hlo={
        "decode_tick": DECODE_HLO, "extend_tick": DECODE_HLO})
    per = {s: scopes.device_ms(run, "decode_tick", s)
           for s in scopes.SCOPES + (scopes.UNSCOPED,)}
    # ns per run -> ms; the while loop holds others and is not charged
    assert per["attn"] == pytest.approx(40e-6)
    assert per["ffn"] == pytest.approx(100e-6)
    assert per["unscoped"] == pytest.approx(30e-6)
    assert per["rec"] == per["ssm"] == 0.0
    assert sum(per.values()) == pytest.approx(sum(TOOK.values()) * 1e-6)
    reg = Registry(BENCH.parent)
    for name, (tick, scope) in SCOPE_METRICS.items():
        got = reg.reader("per_layer", name).read(run)
        if tick == "decode_tick":
            assert got == pytest.approx(per[scope]), name
        else:                            # no extend tick ran on the trace
            assert got is None, name


@pytest.mark.parametrize("sub", ["experts", "ffn/experts"])
def test_a_scope_outside_SCOPES_reads_its_subset(sub):
    """A reader may name a part of a scope (the MoE's experts inside
    ``ffn``): it reads the operations under it, and every scope of
    ``SCOPES`` reads as before."""
    hlo = DECODE_HLO.replace("/ffn/pallas_call", "/ffn/experts/pallas_call")
    hlo = hlo.replace("/attn/dot_general", "/experts/attn/dot_general")
    plain = SimpleNamespace(trace=_decode_trace(), hlo={"decode_tick":
                                                        DECODE_HLO})
    run = SimpleNamespace(trace=_decode_trace(), hlo={"decode_tick": hlo})
    assert scopes.device_ms(run, "decode_tick", sub) == pytest.approx(100e-6)
    for s in scopes.SCOPES + (scopes.UNSCOPED,):
        assert scopes.device_ms(run, "decode_tick", s) == \
            scopes.device_ms(plain, "decode_tick", s), s
    assert scopes.device_ms(run, "decode_tick", "ffn") == pytest.approx(100e-6)
    assert scopes.device_ms(run, "decode_tick", "attn/experts") == 0.0
    assert scopes.scope_of("jit(t)/ffn/experts/dot", "ffn/experts") == \
        "ffn/experts"
    assert scopes.scope_of("jit(t)/experts/ffn/dot", "ffn/experts") == "ffn"


def test_scope_metrics_are_absent_for_a_program_without_scopes():
    """A program whose ticks name no scope (as before the scopes were
    added) reads nothing, rather than all of its time as unscoped."""
    bare = "\n".join(line.split(", metadata=")[0]
                     for line in DECODE_HLO.splitlines())
    run = SimpleNamespace(trace=_decode_trace(),
                          hlo={"decode_tick": bare, "extend_tick": bare})
    reg = Registry(BENCH.parent)
    for name in SCOPE_METRICS:
        assert reg.reader("per_layer", name).read(run) is None, name
    assert scopes.device_ms(SimpleNamespace(trace=None, hlo={}),
                            "decode_tick", "attn") is None


def test_scopes_partition_a_recorded_trace():
    """On a trace recorded on a v5e, whatever scope each operation is
    charged to, the buckets add up to the tick's leaf operation time."""
    tr = trace.load(str(DATA / "tiny_tpu.xplane.pb"))
    names = sorted({o.name for o in tr.ops[0]
                    if trace.module_matches(o.module, "decode_tick")})
    kinds = scopes.SCOPES + ("while/body",)
    hlo = "\n".join(
        f'  %{n} = f32[2]{{0}} add(%a, %b), metadata={{op_name='
        f'"jit(_decode_tick)/{kinds[i % len(kinds)]}/add"}}'
        for i, n in enumerate(names))
    run = SimpleNamespace(trace=tr, hlo={"decode_tick": hlo})
    leaf = sum(o.end - o.start for o in tr.ops[0] if o.leaf
               and trace.module_matches(o.module, "decode_tick"))
    runs = sum(trace.module_matches(m.module, "decode_tick")
               for m in tr.modules[0])
    total = sum(scopes.device_ms(run, "decode_tick", s)
                for s in scopes.SCOPES + (scopes.UNSCOPED,))
    assert runs and total == pytest.approx(1e3 * leaf / 1e9 / runs)


def _stats(ticks, **phase_s):
    return {"work_ticks": ticks, "tick_phase_s": dict(phase_s)}


def test_tick_host_ms_reads_the_phase_counters_as_window_deltas():
    read = Registry(BENCH.parent).reader("per_layer", "tick_host_ms").read
    s0 = _stats(10, **{"admission": 0.01, "decode.prepare": 0.02,
                       "decode.launch": 0.01, "decode.wait": 0.5,
                       "decode.emit": 0.01})
    s1 = _stats(20, **{"admission": 0.02, "decode.prepare": 0.04,
                       "decode.launch": 0.03, "decode.wait": 1.1,
                       "decode.emit": 0.03})
    # host phases grew by 0.01 + 0.02 + 0.02 + 0.02 s over 10 ticks
    got = read(SimpleNamespace(stats0=s0, stats1=s1))
    assert got == pytest.approx(7.0)
    assert read(SimpleNamespace(stats0=s0, stats1=s0)) is None
    parent = {"work_ticks": 3}
    assert read(SimpleNamespace(stats0=parent, stats1=parent)) is None
