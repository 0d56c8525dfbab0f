"""Split a traced step's device time by the names the program gives it.

The program names the parts of its jitted steps with `jax.named_scope`:
``layer<l>`` around each GNN layer and, inside it, ``aggregate`` (the
neighbour exchange and gather-sum), ``combine`` (the dense transforms) and,
under the asynchronous protocols, ``history``; then ``loss``, ``grad_sync``
and ``sgd``; and ``exchange`` around every collective that moves rows.
Its Pallas kernels are named by ``pallas_call(name=...)`` (``gather_sum``,
``gather_dot``, ``sddmm``).  The compiled step's HLO text keeps each
instruction's scope path in ``metadata={op_name="..."}``: the forward reads
``jvp(layer0)/aggregate/gather_sum``, the backward
``transpose(jvp(layer0))/aggregate/...``.

`op_names` maps the instruction names of that text to their paths;
`split` gives each traced op (`tracing.Op`) the path of its top-level
ancestor (`tracing.nest`) and sums self time by what the path names.  The
aggregation's own exchange is counted apart from it, as `tracing` counts
collectives apart from its ``agg`` class.
"""
from __future__ import annotations

import dataclasses
import re

import tracing

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = .*?"
                   r"\bop_name=\"(?P<path>[^\"]*)\"")
AGGREGATE = re.compile(r"(^|/)aggregate(/|$)")
EXCHANGE = re.compile(r"(^|/)exchange(/|$)")
SCOPED = re.compile(r"(^|[/(])(layer\d+|history|loss|grad_sync|sgd|exchange)"
                    r"([/)]|$)")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name scope path} of a compiled module's text;
    instructions without metadata are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m:
            out[m.group("name")] = m.group("path")
    return out


@dataclasses.dataclass
class Split:
    """Self seconds in the window, averaged over chips."""

    agg_fwd_s: float  # under aggregate, outside any transpose(
    agg_bwd_s: float  # under aggregate, inside transpose( (the backward)
    exchange_s: float  # under exchange, forward and backward
    unscoped_s: float  # under none of the program's scopes
    total_s: float  # all of it

    @property
    def unscoped_share(self) -> float:
        return self.unscoped_s / self.total_s if self.total_s > 0 else 0.0


def split(device_ops: dict, window, names: dict) -> Split:
    """``device_ops``: {chip: [(text, start_ns, dur_ns)]} as `tracing.load`
    gives it; ``window``: (start_ns, end_ns); ``names``: `op_names` of the
    module the ops ran."""
    w0, w1 = window
    fwd = bwd = wire = unscoped = total = 0
    for events in device_ops.values():
        ops = [tracing.Op(text, max(s, w0), min(s + d, w1),
                          names.get(tracing.parse(text)[0], ""))
               for text, s, d in events if s + d > w0 and s < w1]
        tracing.nest(ops)  # a nested op takes its top-level op's path
        for op in ops:
            total += op.self_ns
            if EXCHANGE.search(op.cls):
                wire += op.self_ns
            elif AGGREGATE.search(op.cls):
                if "transpose(" in op.cls:
                    bwd += op.self_ns
                else:
                    fwd += op.self_ns
            elif not SCOPED.search(op.cls):
                unscoped += op.self_ns
    n = max(len(device_ops), 1) * 1e9
    return Split(fwd / n, bwd / n, wire / n, unscoped / n, total / n)
