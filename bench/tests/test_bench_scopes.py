"""The scope reduction: instruction names to op_name paths, and device time
split into the aggregation's forward and backward and the unscoped rest."""
import gzip
import json
import os

import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import scopes
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = '''\
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="consts_[\\'X\\']"}
  %gather_sum.1 = f32[64,256]{1,0} custom-call(s32[640]{0} %r), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layer0)/aggregate/gather_sum/pallas_call" source_file="x.py"}
  %while.2 = (s32[], f32[66,256]{1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/transpose(jvp(layer1))/aggregate/while"}
  %fusion.71 = f32[66,256]{1,0} fusion(f32[66,256]{1,0} %a), kind=kCustom, calls=%f, metadata={op_name="jit(step)/transpose(jvp(layer1))/aggregate/while/body/closed_call/scatter-add"}
  %convolution.1 = f32[64,256]{1,0} convolution(%x, %w), metadata={op_name="jit(step)/jvp(layer0)/combine/dot_general"}
  %all-to-all.2 = f32[4,30,256]{2,1,0} all-to-all(%s), metadata={op_name="jit(step)/jvp(layer1)/aggregate/exchange/all_to_all"}
  %subtract.3 = f32[256]{0} subtract(%a, %b), metadata={op_name="jit(step)/sgd/sub"}
  %copy.9 = f32[256]{0} copy(%a)
  ROOT %tuple.1 = (f32[8]{0}) tuple(%p.1)
}
'''


def _ev(name, opcode, start, dur):
    return (f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %a)", start, dur)


def test_op_names_reads_each_instruction_path():
    names = scopes.op_names(HLO)
    assert names["gather_sum.1"] == ("jit(step)/jvp(layer0)/aggregate/"
                                     "gather_sum/pallas_call")
    assert names["while.2"] == "jit(step)/transpose(jvp(layer1))/aggregate/while"
    assert names["p.1"] == "consts_[\\'X\\']"
    assert "copy.9" not in names and "tuple.1" not in names


def test_split_on_a_synthetic_window():
    names = scopes.op_names(HLO)
    # chip 0: gather [0,40); the scatter loop [40,70) holding its body op
    #         [45,65), which takes the loop's path; the all-to-all [70,80)
    #         (the aggregation's exchange, counted apart); the matmul
    #         [80,90); sgd [90,95); an unnamed copy [95,100)
    # chip 1: the gather [0,50) and the copy [50,60); the rest idle
    ops = {"c0": [_ev("gather_sum.1", "custom-call", 0, 40),
                  _ev("while.2", "while", 40, 30),
                  _ev("fusion.71", "fusion", 45, 20),
                  _ev("all-to-all.2", "all-to-all", 70, 10),
                  _ev("convolution.1", "convolution", 80, 10),
                  _ev("subtract.3", "subtract", 90, 5),
                  _ev("copy.9", "copy", 95, 5)],
           "c1": [_ev("gather_sum.1", "custom-call", 0, 50),
                  _ev("copy.9", "copy", 50, 10)]}
    s = scopes.split(ops, (0, 120), names)
    assert s.agg_fwd_s == pytest.approx((40 + 50) / 2 * 1e-9)
    assert s.agg_bwd_s == pytest.approx(30 / 2 * 1e-9)
    assert s.exchange_s == pytest.approx(10 / 2 * 1e-9)
    assert s.unscoped_s == pytest.approx((5 + 10) / 2 * 1e-9)
    assert s.total_s == pytest.approx(160 / 2 * 1e-9)
    assert s.unscoped_share == pytest.approx(15 / 160)


def test_split_clips_to_the_window():
    names = scopes.op_names(HLO)
    ops = {"c0": [_ev("gather_sum.1", "custom-call", -20, 40),
                  _ev("while.2", "while", 90, 30)]}
    s = scopes.split(ops, (0, 100), names)
    assert s.agg_fwd_s == pytest.approx(20e-9)
    assert s.agg_bwd_s == pytest.approx(10e-9)
    assert scopes.split({}, (0, 100), names).unscoped_share == 0.0


def test_recorded_trace_split():
    """Recorded on one TPU v5e by `bench/run.py --trace 1` of gcn-paper.full.c1
    cut to 8,192 vertices of average degree 8 (ELL width 21), with the job
    keeping the compiled step's op_names: 8 steps in a 0.2 s window, its
    "XLA Ops" events, the window and the op_names of the instructions in
    it."""
    with gzip.open(os.path.join(DATA, "trace_gcn_small_scopes.json.gz"),
                   "rt") as f:
        d = json.load(f)
    window = tuple(d["window"])
    s = scopes.split(d["device_ops"], window, d["op_names"])
    t = tracing.reduce(d["device_ops"], [("bench.window", window[0],
                                          window[1] - window[0])],
                       window, rows=8192, slot_widths=range(21, 21 + 129))
    assert s.total_s == pytest.approx(t.busy_s, rel=1e-6)
    # the scopes and the class rule agree on the aggregation's time
    assert s.agg_fwd_s + s.agg_bwd_s == pytest.approx(t.class_s["agg"],
                                                      rel=0.02)
    # three gather kernels a step against two scatter-add loops
    assert 4 * s.agg_bwd_s < s.agg_fwd_s < 6 * s.agg_bwd_s
    assert s.unscoped_share < 0.01
    kernels = {p for p in d["op_names"].values() if "/gather_sum/" in p}
    assert kernels == {f"jit(step)/jvp(layer{l})/aggregate/gather_sum/"
                       "pallas_call" for l in range(3)}
