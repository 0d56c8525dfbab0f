"""The engine names the parts of its jitted steps with `jax.named_scope`
(``layer<l>`` holding ``aggregate`` and ``combine``, then ``loss``,
``grad_sync``, ``sgd``; ``exchange`` around every collective that moves
rows) and its Pallas kernels with ``pallas_call(name=...)``.  The compiled
module keeps each instruction's scope path in ``metadata={op_name=...}``,
which is how a profiler trace's device ops are read back by layer.  These
tests compile the steps on the CPU and read that text."""
import os
import re

import pytest

from conftest import run_with_devices

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = (?P<rest>.*)$")
OP_NAME = re.compile(r'\bop_name="(?P<path>[^"]*)"')
DOT = re.compile(r"\b(dot|convolution)\(")
SCOPED = re.compile(r"(^|[/(])(layer\d+|history|loss|grad_sync|sgd|exchange)"
                    r"([/)]|$)")


def instructions(hlo_text):
    """(computation, instruction name, text, op_name) of every instruction
    that carries an op_name (tuples and their elements carry none)."""
    comp = None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" "):
            comp = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
            continue
        m = INSTR.match(line)
        op = OP_NAME.search(line) if m else None
        if op:
            yield comp, m.group("name"), m.group("rest"), op.group("path")


def _engine(**cfg):
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(256, avg_degree=4, feature_dim=8, num_classes=4, seed=0)
    return DistGNNEngine(g, cfg=EngineConfig(hidden=16, num_layers=2, **cfg))


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_full_graph_step_scopes(model):
    eng = _engine(model=model)
    text = eng.lower_step().compile().as_text()
    ell = re.compile(r"s32\[%d,%d\]|s32\[%d\]" % (eng.Vp, eng.K,
                                                 eng.Vp * eng.K))
    fwd = bwd = 0
    for comp, name, rest, op in instructions(text):
        if "parameter(" in rest:
            continue
        if ell.search(rest):  # reads the ELL slot table: the aggregation
            assert "/aggregate/" in op, (name, op)
        if DOT.search(rest):  # gat's attention vectors are aggregation
            want = ("/combine/", "/aggregate/") if model == "gat" \
                else ("/combine/",)
            assert any(w in op for w in want), (name, op)
        if comp == "ENTRY":  # every top-level op is under a program scope
            assert SCOPED.search(op), (name, op)
        if "/aggregate/" in op:
            bwd += "transpose(" in op
            fwd += "/gather_sum/" in op
    assert fwd and bwd  # the kernel forward, and its backward apart


def test_minibatch_and_infer_step_scopes():
    eng = _engine(batching="node_wise", batch_size=4, fanouts=(3, 3))
    text = eng.lower_minibatch_step().compile().as_text()
    paths = [op for comp, _, rest, op in instructions(text)
             if comp == "ENTRY" and "parameter(" not in rest]
    for scope in (r"layer0\)?/aggregate/", r"layer1\)?/combine/", "loss/",
                  "grad_sync/", "sgd/"):
        assert any(re.search(scope, p) for p in paths), scope
    eng = _engine()
    eng.make_infer_step()
    text = eng._jit_infer.lower(eng.init_state()["params"], eng.X,
                                eng._infer_consts).compile().as_text()
    paths = [op for _, _, _, op in instructions(text)]
    assert any("layer0/aggregate/gather_sum" in p for p in paths)
    assert any("layer1/combine" in p for p in paths)


COLLECTIVES_CODE = r"""
import re
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from test_step_scopes import instructions

g = sbm_graph(96, num_blocks=4, p_in=0.2, p_out=0.05, feature_dim=8,
              num_classes=4, seed=0)
WIRE = {"p2p": "all-to-all", "broadcast": "all-gather",
        "ring": "collective-permute"}
for execution, opcode in WIRE.items():
    for family in ("edge_cut", "vertex_cut"):
        eng = DistGNNEngine(g, cfg=EngineConfig(
            execution=execution, partition_family=family, hidden=16,
            num_layers=2))
        text = eng.lower_step().compile().as_text()
        seen = 0
        for comp, name, rest, op in instructions(text):
            base = re.search(r"\b(all-to-all|all-gather|collective-permute|"
                             r"all-reduce)(-start|-done)?\(", rest)
            if base is None:
                continue
            if base.group(1) == "all-reduce":  # the psums of loss and grads
                assert re.search(r"(^|/)(loss|grad_sync|history)/", op), (
                    execution, family, name, op)
            else:  # rows on the wire: the exchange, forward and backward
                assert "/exchange/" in op, (execution, family, name, op)
                seen += base.group(1) == opcode
        assert seen, (execution, family)
print("SCOPES_OK")
"""


def test_collectives_are_under_exchange_4dev():
    here = os.path.dirname(os.path.abspath(__file__))
    out = run_with_devices(f"import sys; sys.path.insert(0, {here!r})\n"
                           + COLLECTIVES_CODE, n_devices=4)
    assert "SCOPES_OK" in out
