"""Roofline terms from a compiled dry-run artifact.

  compute    = HLO_FLOPs / (chips x peak)      [cost_analysis is per-device
  memory     = HLO_bytes / (chips x HBM bw)     post-SPMD, so the division by
  collective = coll_bytes / (chips x link bw)   chips is already done]

collective bytes come from parsing the optimized (partitioned) HLO text:
we sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, with a 2x ring factor for
all-reduce (reduce-scatter + all-gather phases).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<type>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind transferred bytes (per device) from partitioned HLO."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async pair: count the -start only
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _type_bytes(m.group("type"))
        factor = 2.0 if op == "all-reduce" else 1.0
        out[op] = out.get(op, 0.0) + factor * nbytes
    return out


@dataclasses.dataclass
class RooflineTerms:
    chip: hw.ChipPeaks            # the peaks the times are taken against
    flops: float                  # per-device float ops
    bytes_hbm: float              # per-device
    bytes_coll: float             # per-device
    coll_breakdown: Dict[str, float]
    int_ops: float = 0.0          # per-device INTEGER-domain ops (int8
    # MACs / XNOR-popcount bit positions) — HLO cost_analysis reports
    # integer dots and bitwise work as zero FLOPs, so the integer
    # compute paths would otherwise look free; callers attach the
    # analytic count (``integer_dense_ops``) via ``analyze_compiled``'s
    # int_ops argument or construct the terms directly

    @property
    def t_compute(self) -> float:
        return self.flops / self.chip.flops_bf16

    @property
    def t_int(self) -> float:
        return self.int_ops / self.chip.ops_int8

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.bytes_coll / self.chip.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "int": self.t_int,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_int, self.t_memory,
                   self.t_collective)

    def as_dict(self) -> dict:
        return dict(
            flops=self.flops,
            int_ops=self.int_ops,
            bytes_hbm=self.bytes_hbm,
            bytes_coll=self.bytes_coll,
            t_compute=self.t_compute,
            t_int=self.t_int,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            dominant=self.dominant,
            coll_breakdown=self.coll_breakdown,
        )


def integer_dense_ops(
    m: int, n_in: int, r: int, compute_path: str = "xnor"
) -> float:
    """Analytic integer-op count for one tiled dense apply (u = x . T^T).

    HLO cost_analysis counts these as zero FLOPs, so the dry-run/roofline
    needs the analytic number:

    * ``int8``: 2 * m * n_in * r — one int8 MAC per (row, bit) pair,
      MAC = multiply + add.
    * ``xnor``: each of the m*r outputs reads ceil(n_in/32) packed words
      at ~2 word ops each (XOR + popcount); one 32-lane word op covers
      32 bit positions, so the count is normalized to MAC-equivalents at
      the int8 rate: 2 * m * r * ceil(n_in/32).

    ``float`` contributes nothing here (its MACs already land in HLO
    flops).
    """
    if compute_path == "int8":
        return 2.0 * m * n_in * r
    if compute_path == "xnor":
        return 2.0 * m * r * ((n_in + 31) // 32)
    if compute_path == "float":
        return 0.0
    raise ValueError(f"unknown compute_path {compute_path!r}")


def analyze_compiled(compiled, device_kind: str,
                     int_ops: float = 0.0) -> RooflineTerms:
    """Roofline terms from a compiled artifact, against the published
    peaks of ``device_kind`` (roofline/hw.py; unlisted kinds raise).

    ``int_ops`` attaches the analytic integer-op count (see
    ``integer_dense_ops``) for programs using the integer compute paths
    — cost_analysis reports those ops as zero FLOPs.
    """
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    try:
        text = compiled.as_text()
    except Exception:
        text = ""
    coll = collective_bytes(text)
    return RooflineTerms(
        chip=hw.peaks(device_kind),
        flops=flops,
        bytes_hbm=nbytes,
        bytes_coll=sum(coll.values()),
        coll_breakdown=coll,
        int_ops=int_ops,
    )


def combine_extrapolated(
    base: RooflineTerms, delta: RooflineTerms, extra_trips: int
) -> RooflineTerms:
    """total = base + extra_trips * delta  (scan trip-count correction)."""
    add = lambda a, b: a + extra_trips * b
    coll = dict(base.coll_breakdown)
    for k, v in delta.coll_breakdown.items():
        coll[k] = coll.get(k, 0.0) + extra_trips * v
    return RooflineTerms(
        chip=base.chip,
        flops=add(base.flops, delta.flops),
        bytes_hbm=add(base.bytes_hbm, delta.bytes_hbm),
        bytes_coll=add(base.bytes_coll, delta.bytes_coll),
        coll_breakdown=coll,
        int_ops=add(base.int_ops, delta.int_ops),
    )


def subtract(a: RooflineTerms, b: RooflineTerms) -> RooflineTerms:
    coll = {k: max(0.0, v - b.coll_breakdown.get(k, 0.0))
            for k, v in a.coll_breakdown.items()}
    return RooflineTerms(
        chip=a.chip,
        flops=max(0.0, a.flops - b.flops),
        bytes_hbm=max(0.0, a.bytes_hbm - b.bytes_hbm),
        bytes_coll=max(0.0, a.bytes_coll - b.bytes_coll),
        coll_breakdown=coll,
        int_ops=max(0.0, a.int_ops - b.int_ops),
    )


def model_flops(cfg, cell, n_params_active: int) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference forward), global."""
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_params_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_params_active * tokens
    tokens = cell.global_batch * 1
    return 2.0 * n_params_active * tokens
