"""Reduce a JAX profiler trace to what the per-layer readers need.

A TPU trace holds, per chip, an "XLA Ops" line: one event per executed HLO
instruction, named by the instruction's text (``%name = type opcode(...),
attrs``); a ``while`` event encloses the events of its body.  The host's
"python" line holds the harness's `jax.profiler.TraceAnnotation` spans.
Both use one clock.

Each top-level instruction gets one class, and the instructions nested in
it inherit it:

  collective   opcode all-to-all, all-gather, all-reduce, reduce-scatter or
               collective-permute (and their -start/-done halves)
  agg          a Pallas kernel (custom-call to ``tpu_custom_call``: every
               kernel on the GNN step is an ELL aggregation kernel), or an
               instruction with an int32 operand or result shaped like the
               ELL slot table: ``rows x k`` for the chip's row count and k
               within the slot widths given (flat or 2-D) — the XLA
               gathers and scatter-adds of the aggregation and its backward
  dense        everything else (matrix products, elementwise, loss, SGD)

Self time is an event's duration less that of the events nested in it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

HEAD = re.compile(r"^%?(?P<name>[\w.\-]+) = ")
S32 = re.compile(r"s32\[(?P<dims>[\d,]*)\]")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


def parse(text: str):
    """(instruction name, opcode) of an HLO instruction's text."""
    m = HEAD.match(text)
    if not m:
        return text.split(" ")[0], ""
    rest = text[m.end():]
    if rest.startswith("("):  # tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return m.group("name"), rest.lstrip().split("(", 1)[0]


def classify(text: str, rows: int, slot_widths) -> str:
    name, op = parse(text)
    base = re.sub(r"-(start|done|update)$", "", op)
    if base in COLLECTIVES or any(name.startswith(c) for c in COLLECTIVES):
        return "collective"
    if op == "custom-call" and 'custom_call_target="tpu_custom_call"' in text:
        return "agg"
    for m in S32.finditer(text):
        dims = [int(d) for d in m.group("dims").split(",") if d]
        n = 1
        for d in dims:
            n *= d
        if (len(dims) == 2 and dims[0] == rows and dims[1] in slot_widths) \
                or (len(dims) == 1 and n % rows == 0
                    and n // rows in slot_widths):
            return "agg"
    return "dense"


@dataclasses.dataclass
class Op:
    text: str
    start: int  # ns
    end: int
    cls: str = "dense"
    self_ns: int = 0


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _measure(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the sorted disjoint intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def nest(ops):
    """Give each op its self time and its top-level ancestor's class;
    returns the top-level ops.  ``ops`` must be classified already; an op
    that starts inside another but ends after it is a top-level op."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    top, stack = [], []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        op.self_ns = op.end - op.start
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
            op.cls = stack[0].cls
        else:  # a root, or an op of another stream that only overlaps
            top.append(op)
            stack = []
        stack.append(op)
    return top


@dataclasses.dataclass
class Summary:
    """A traced window, reduced; times are seconds averaged over chips."""

    window_s: float
    busy_s: float
    class_s: dict  # self seconds per class
    exposed_collective_s: float
    top_ops: list  # [[label, seconds]] by self time
    idle_gaps: list  # [[host span, seconds]] longest first


def reduce(device_ops: dict, host_spans, window, rows: int, slot_widths,
           top: int = 10) -> Summary:
    """``device_ops``: {chip: [(text, start_ns, dur_ns)]}; ``host_spans``:
    [(name, start_ns, dur_ns)]; ``window``: (start_ns, end_ns)."""
    w0, w1 = window
    spans = [Span(n, s, s + d) for n, s, d in host_spans]
    n_chips = max(len(device_ops), 1)
    busy = expo = 0
    cls_ns: dict = {}
    by_label: dict = {}
    gaps = []
    for chip, events in device_ops.items():
        ops = []
        for text, s, d in events:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            ops.append(Op(text, max(s, w0), min(e, w1),
                          classify(text, rows, slot_widths)))
        tops = nest(ops)
        for op in ops:
            cls_ns[op.cls] = cls_ns.get(op.cls, 0) + op.self_ns
            name, opcode = parse(op.text)
            label = f"{name} {opcode}".strip()
            by_label[label] = by_label.get(label, 0) + op.self_ns
        busy_iv = _union([(o.start, o.end) for o in tops])
        busy += _measure(busy_iv)
        coll = _union([(o.start, o.end) for o in tops
                       if o.cls == "collective"])
        other = _union([(o.start, o.end) for o in tops
                        if o.cls != "collective"])
        expo += _measure(_subtract(coll, other))
        for s, e in _subtract([[w0, w1]], busy_iv):
            gaps.append((_host_label(spans, s, e), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / n_chips / 1e9,
        class_s={k: v / n_chips / 1e9 for k, v in cls_ns.items()},
        exposed_collective_s=expo / n_chips / 1e9,
        top_ops=[[k, v / n_chips / 1e9] for k, v in ops_sorted],
        idle_gaps=[[label, s] for label, s in gaps[:top]])


def _host_label(spans, s, e) -> str:
    """The harness span that covers most of [s, e), the innermost (the
    shortest) among equals."""
    best, key = "host: outside any harness span", (0, 0)
    for sp in spans:
        ov = min(e, sp.end) - max(s, sp.start)
        if ov > 0 and (ov, sp.start - sp.end) > key:
            best, key = sp.name, (ov, sp.start - sp.end)
    return best


def load(trace_dir: str, span_prefix: str = "bench."):
    """(device_ops, host_spans) from the newest ``.xplane.pb`` under
    ``trace_dir``; device planes are the TPU chips' "XLA Ops" lines."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}, []
    pd = ProfileData.from_file(files[-1])
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    return device_ops, spans
