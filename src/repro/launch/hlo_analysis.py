"""Post-compile HLO analysis: collective-byte extraction with while-loop
trip-count propagation, plus the three-term roofline.

The compiled module text (post SPMD partitioning) contains per-device shapes.
Collectives inside scan bodies appear once in the text but execute
`known_trip_count` times — XLA annotates the while op's backend_config with
the trip count, which we propagate down the call graph (nested scans
multiply).

Byte convention per device per execution:
  all-gather        : result bytes x (n-1)/n        ~ result bytes
  reduce-scatter    : operand bytes ~ result x n    -> result bytes x (n-1)
  all-reduce        : 2 x payload (ring RS+AG)
  all-to-all        : result bytes x (n-1)/n
  collective-permute: result bytes
We conservatively use the simple forms below and report per-op detail so any
convention can be recomputed.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s, chip-to-chip


# Per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
# HBM).  ici_bw is one link's share of the chip's 1,600 Gbit/s ICI total: a
# ring or all_to_all over one mesh axis does not get every link.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peak table's row for ``device_kind``; a kind it lacks is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[16,1024,128]{...}' -> bytes. Tuple shapes are summed."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveRecord:
    kind: str
    bytes_per_exec: int
    executions: int
    computation: str

    @property
    def total_bytes(self) -> float:
        mult = 2.0 if self.kind == "all-reduce" else 1.0
        return mult * self.bytes_per_exec * self.executions


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*.*)?\{", line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
            if line.lstrip().startswith("ENTRY") or line.startswith("ENTRY"):
                comps["__entry__"] = comps[cur]
            continue
        if cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _find_entry(hlo_text: str, comps: Dict[str, List[str]]) -> str:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo_text, re.M)
    return m.group(1) if m else next(iter(comps))


def parse_collectives(hlo_text: str) -> List[CollectiveRecord]:
    comps = _split_computations(hlo_text)
    entry = _find_entry(hlo_text, comps)

    # call graph edges with multipliers
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for line in lines:
            wm = re.search(r"while\(.*?body=%?([\w\.\-]+)", line)
            if wm:
                trip = 1
                tm = re.search(r'known_trip_count[^}]*?"n"\s*:\s*"?(\d+)"?', line)
                if tm:
                    trip = int(tm.group(1))
                edges[name].append((wm.group(1), trip))
                cm = re.search(r"condition=%?([\w\.\-]+)", line)
                if cm:
                    edges[name].append((cm.group(1), trip))
                continue
            for cm in re.finditer(r"(?:calls=|to_apply=)%?([\w\.\-]+)", line):
                edges[name].append((cm.group(1), 1))
            bm = re.findall(r"branch_computations=\{([^}]*)\}", line)
            for group in bm:
                for c in re.findall(r"%?([\w\.\-]+)", group):
                    edges[name].append((c, 1))

    # propagate multipliers from entry
    mult: Dict[str, int] = defaultdict(int)
    mult[entry] = 1
    stack = [entry]
    seen_pairs = set()
    while stack:
        cur = stack.pop()
        for child, k in edges.get(cur, []):
            if (cur, child) in seen_pairs:
                continue
            seen_pairs.add((cur, child))
            mult[child] += mult[cur] * k
            stack.append(child)

    records: List[CollectiveRecord] = []
    for name, lines in comps.items():
        m = mult.get(name, 0)
        if m == 0:
            continue
        for line in lines:
            cm = re.search(
                r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\](?:\{[^}]*\})?))\s+"
                r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
                r"(?:-start)?\(", line)
            if not cm:
                continue
            if re.search(r"(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)-done", line):
                continue
            shape_str, kind = cm.group(1), cm.group(2)
            records.append(CollectiveRecord(kind, _shape_bytes(shape_str), m, name))
    return records


def collective_bytes(hlo_text: str) -> Tuple[float, Dict[str, float]]:
    recs = parse_collectives(hlo_text)
    by_kind: Dict[str, float] = defaultdict(float)
    for r in recs:
        by_kind[r.kind] += r.total_bytes
    return sum(by_kind.values()), dict(by_kind)


def max_collective_buffer_bytes(hlo_text: str, kind: str) -> int:
    """Largest single lowered buffer (shape bytes of one op execution) of a
    collective kind — the peak per-op buffer the schedule materializes, e.g.
    the all-to-all send buffer that bucketed p2p caps shrink or the
    all-gather table that feature chunking shrinks."""
    return max((r.bytes_per_exec for r in parse_collectives(hlo_text)
                if r.kind == kind), default=0)


def executable_summary(compiled) -> Dict[str, object]:
    """Static telemetry facts for ONE compiled executable: collective wire
    bytes (total + by kind) parsed from the optimized HLO, the largest
    single collective buffer, and XLA's per-device peak memory.  Feed the
    result to ``Telemetry.attach_executable(name, ...)`` so a run summary
    is self-describing: measured spans/counters next to the compiler-static
    numbers they should explain."""
    text = compiled.as_text()
    total, by_kind = collective_bytes(text)
    out: Dict[str, object] = {
        "collective_bytes_per_device": int(total),
        "collective_bytes_by_kind": {k: int(v) for k, v in by_kind.items()},
        "max_collective_buffer_bytes": max(
            (int(r.bytes_per_exec) for r in parse_collectives(text)),
            default=0),
    }
    try:
        out["peak_memory_bytes"] = int(
            compiled.memory_analysis().peak_memory_in_bytes)
    except Exception:  # pragma: no cover — backend without memory stats
        pass
    return out


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_raw: float
    analytic_flops: float
    useful_ratio: float  # MODEL_FLOPS / analytic flops
    dominant: str

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(*, device_kind: str, analytic_flops: float, chips: int,
                   hbm_bytes_per_chip: float,
                   collective_bytes_per_chip: float, model_flops: float,
                   hlo_flops_raw: float) -> Roofline:
    pk = peaks_for(device_kind)
    compute_s = analytic_flops / (chips * pk.flops_bf16)
    memory_s = hbm_bytes_per_chip / pk.hbm_bw
    coll_s = collective_bytes_per_chip / pk.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    return Roofline(compute_s, memory_s, coll_s, model_flops, hlo_flops_raw,
                    analytic_flops, model_flops / max(analytic_flops, 1.0), dominant)
