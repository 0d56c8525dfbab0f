"""The trace reduction: classes, busy union, idle, exposed collectives and
gap labels."""
import gzip
import json
import os

import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
IDS = "s32[64,9]{1,0}"
GATHER = ('%jvp__.3 = f32[64,256]{1,0} custom-call(s32[640]{0} %r, f32[64,10]'
          '{1,0} %w, f32[66,1,256]{2,1,0} %t), custom_call_target='
          '"tpu_custom_call"')
LOOP = (f"%while.9 = (s32[]{{:T(128)}}, f32[66,256]{{1,0}}, {IDS}) "
        f"while((s32[], f32[66,256], {IDS}) %tuple.1), condition=%c")
SCATTER_BODY = ("%fusion.71 = f32[66,256]{1,0} fusion(f32[66,256]{1,0} %a, "
                "s32[64]{0} %b), kind=kCustom, calls=%f")
DOT = ("%convolution.1 = f32[64,256]{1,0} convolution(f32[64,256]{1,0} %x, "
       "f32[256,256]{1,0} %w), dim_labels=bf_io->bf")
A2A = ("%all-to-all.2 = f32[4,30,256]{2,1,0} all-to-all(f32[4,30,256]{2,1,0} "
       "%s), replica_groups={{0,1,2,3}}, dimensions={0}")
AR_DONE = "%all-reduce-done.1 = f32[256,256]{1,0} all-reduce-done(%ar-start)"


@pytest.mark.parametrize("text,cls", [
    (GATHER, "agg"), (LOOP, "agg"), (DOT, "dense"), (A2A, "collective"),
    (AR_DONE, "collective"), (SCATTER_BODY, "dense"),
    ("%fusion.12 = f32[576]{0} fusion(f32[66]{0} %s, s32[576]{0} %i)",
     "agg"),
])
def test_classes(text, cls):
    assert tracing.classify(text, rows=64, slot_widths=range(9, 20)) == cls


def test_parse_skips_tuple_types():
    assert tracing.parse(LOOP) == ("while.9", "while")
    assert tracing.parse(A2A) == ("all-to-all.2", "all-to-all")


def test_reduce_on_a_synthetic_window():
    # chip 0: gather [0,40); scatter loop [50,80) holding a body op [55,75)
    #         that inherits the loop's class; all-to-all [80,95); matmul
    #         [95,100); idle [40,50) and [100,120)
    # chip 1: only the matmul [0,60); idle [60,120)
    ops = {
        "c0": [(GATHER, 0, 40), (LOOP, 50, 30), (SCATTER_BODY, 55, 20),
               (A2A, 80, 15), (DOT, 95, 5)],
        "c1": [(DOT, 0, 60)],
    }
    spans = [("bench.window", 0, 120), ("bench.dispatch", 0, 50),
             ("bench.loss_read", 100, 20)]
    s = tracing.reduce(ops, spans, (0, 120), rows=64,
                       slot_widths=range(9, 20))
    assert s.window_s == pytest.approx(120e-9)
    assert s.busy_s == pytest.approx((90 + 60) / 2 * 1e-9)
    assert s.class_s["agg"] == pytest.approx(70 / 2 * 1e-9)
    assert s.class_s["collective"] == pytest.approx(15 / 2 * 1e-9)
    assert s.class_s["dense"] == pytest.approx(65 / 2 * 1e-9)
    assert s.exposed_collective_s == pytest.approx(15 / 2 * 1e-9)
    gaps = sorted((round(sec * 1e9), name) for name, sec in s.idle_gaps)
    assert gaps == [(10, "bench.dispatch"), (20, "bench.loss_read"),
                    (60, "bench.window")]
    assert s.top_ops[0] == ["convolution.1 convolution",
                            pytest.approx(65 / 2 * 1e-9)]


def test_exposed_collective_leaves_out_what_other_ops_cover():
    # an all-gather [0,30) on one line with a matmul [20,40) beside it (as
    # two lines of one chip would give): 20 of its 30 are exposed
    ag = ("%all-gather.1 = f32[8,4]{1,0} all-gather(f32[2,4]{1,0} %x), "
          "dimensions={0}")
    s = tracing.reduce({"c0": [(ag, 0, 30), (DOT, 20, 20)]},
                       [("bench.window", 0, 40)], (0, 40), rows=64,
                       slot_widths=range(9, 20))
    assert s.exposed_collective_s == pytest.approx(20e-9)


def test_ops_outside_the_window_are_clipped():
    s = tracing.reduce({"c0": [(DOT, -50, 100), (GATHER, 90, 50)]},
                       [("bench.window", 0, 100)], (0, 100), rows=64,
                       slot_widths=range(9, 20))
    assert s.busy_s == pytest.approx(60e-9)
    assert s.class_s["agg"] == pytest.approx(10e-9)


def _recorded():
    """A trace recorded on one TPU v5e by `bench/run.py --trace 1` of
    gcn-paper.full.c1 cut to 8,192 vertices of average degree 8 (ELL width
    21, padded to 24 in the kernels): 8 steps in a 0.2 s window, reduced by
    `tracing.load` to its "XLA Ops" events and harness spans."""
    with gzip.open(os.path.join(DATA, "trace_gcn_small.json.gz"), "rt") as f:
        d = json.load(f)
    return d["device_ops"], [tuple(s) for s in d["host_spans"]]


def test_recorded_trace():
    ops, spans = _recorded()
    (w0, wlen), = [(s, d) for n, s, d in spans if n == "bench.window"]
    s = tracing.reduce(ops, spans, (w0, w0 + wlen), rows=8192,
                       slot_widths=range(21, 21 + 129))
    assert s.window_s == pytest.approx(wlen / 1e9)
    assert 0.8 * s.window_s < s.busy_s < s.window_s
    # three gather kernels and two scatter-add loops a step hold most of it
    assert s.class_s["agg"] > 0.8 * s.busy_s
    assert "collective" not in s.class_s
    assert sum(s.class_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    kernels = [n for n, _ in s.top_ops if n.endswith("custom-call")]
    assert len(kernels) == 3
    assert all(label.startswith("bench.") for label, _ in s.idle_gaps)
    busy_by_hand = tracing._measure(tracing._union(
        [(max(st, w0), min(st + d, w0 + wlen)) for _, st, d in
         ops["/device:TPU:0"] if st < w0 + wlen and st + d > w0]))
    assert s.busy_s == pytest.approx(busy_by_hand / 1e9)


def test_recorded_four_chip_trace():
    """Recorded on a 2x2 TPU v5e host by `bench/run.py --trace 1` of
    a four-chip GCN cell (gcn-paper's graph at 2^21 vertices, range-split
    over four chips) cut to 8,192 vertices of average degree 8 (2,048
    rows a chip, ELL width 21), cut to the window's first two steps."""
    with gzip.open(os.path.join(DATA, "trace_gcn4_small.json.gz"),
                   "rt") as f:
        d = json.load(f)
    spans = [tuple(s) for s in d["host_spans"]]
    (w0, wlen), = [(s, dd) for n, s, dd in spans if n == "bench.window"]
    s = tracing.reduce(d["device_ops"], spans, (w0, w0 + wlen), rows=2048,
                       slot_widths=range(21, 21 + 129))
    assert len(d["device_ops"]) == 4
    # the p2p halo all_to_alls are synchronous on the op line: all exposed
    assert s.class_s["collective"] > 0
    assert s.exposed_collective_s == pytest.approx(s.class_s["collective"])
    assert s.class_s["agg"] > s.class_s["dense"] > 0
    assert sum(s.class_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert any(n.endswith("all-to-all") for n, _ in s.top_ops)
    assert 0 < s.busy_s < s.window_s
