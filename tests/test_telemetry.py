"""Telemetry tier: tracer core, metric registry, exporters, the profiler
timeline, and the engine integration contract.

In-process tests cover the stdlib-only `core.telemetry` module: span
nesting/ordering, thread-interleaved spans landing in distinct lanes,
exact histogram percentiles (bit-identical to numpy), the disabled-mode
no-op identity + bounded overhead, and the spans' round-trip through the
JAX profiler's trace (its Chrome trace-event JSON and its host plane):
an enabled span is a profiler annotation, on the device ops' clock.

The subprocess test (4 forced-host devices) locks the run-wide contract: a
traced mini-batch pipelined epoch + serving flush where the summed
exchange-span bytes equal ``CommStats.total()`` EXACTLY, every CommStats
field is mirrored into ``comm.*`` counters, spans cover every configured
step, the prefetch and trainer threads appear as distinct lanes, and —
satellite 1's regression — a held ``CommStats`` reference keeps observing
traffic across the in-place ``reset()`` the engine now performs instead of
re-instantiating.
"""
import glob
import gzip
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from conftest import run_with_devices
from repro.core.sampling.distributed import CommStats
from repro.core.telemetry import (
    DEFAULT_LATENCY_BUCKETS,
    NULL_METRIC,
    NULL_SPAN,
    NULL_TELEMETRY,
    MetricRegistry,
    Telemetry,
    Tracer,
    exact_percentile,
)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

class FakeClock:
    """Deterministic clock: each call advances by `dt`."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def test_span_nesting_and_ordering():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer", step=0):
        with tr.span("inner_a", device=1):
            pass
        with tr.span("inner_b", device=2):
            pass
    spans = tr.spans()  # ordered by start time
    assert [s.name for s in spans] == ["outer", "inner_a", "inner_b"]
    outer, a, b = spans
    assert outer.depth == 0 and a.depth == 1 and b.depth == 1
    # children start after the parent and fit inside its interval
    assert outer.t0 < a.t0 < b.t0
    assert a.t0 + a.dur <= outer.t0 + outer.dur
    assert b.t0 + b.dur <= outer.t0 + outer.dur
    assert a.labels == {"device": 1}
    # set() attaches labels mid-span
    with tr.span("late") as sp:
        sp.set(rows=7)
    assert tr.spans()[-1].labels["rows"] == 7


def test_instant_spans_are_zero_duration():
    tr = Tracer(clock=FakeClock())
    tr.instant("exchange", bytes=128, device=3)
    (sp,) = tr.spans()
    assert sp.dur == 0.0 and sp.labels["bytes"] == 128


def test_thread_interleaved_spans_get_distinct_lanes():
    tel = Telemetry()
    barrier = threading.Barrier(2)

    def work(tag):
        barrier.wait()
        for i in range(5):
            with tel.span("stage", lane=tag, step=i):
                time.sleep(0.001)

    threads = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tel.trace.spans()
    assert len(spans) == 10
    lanes_by_tid = {}
    for s in spans:
        lanes_by_tid.setdefault(s.tid, set()).add(s.labels["lane"])
    assert len(lanes_by_tid) == 2  # two OS threads -> two lanes
    # each lane carries exactly one producer thread's spans
    assert all(len(v) == 1 for v in lanes_by_tid.values())


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_percentiles_exact_vs_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 100, 1001):
        draws = rng.lognormal(mean=-5.0, sigma=2.0, size=n)
        reg = MetricRegistry()
        h = reg.histogram("lat")
        for d in draws:
            h.record(d)
        for q in (0.0, 1.0, 25.0, 50.0, 75.0, 90.0, 99.0, 99.9, 100.0):
            assert h.percentile(q) == float(np.percentile(draws, q)), (n, q)
            assert exact_percentile(draws, q) == float(np.percentile(draws, q))
    assert exact_percentile([], 50.0) == 0.0


def test_histogram_bucket_counts():
    reg = MetricRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.7, 3.0, 100.0):
        h.record(v)
    assert h.counts == [1, 2, 1, 1]  # last bucket is the +inf overflow
    assert h.count == 5 and h.total == pytest.approx(106.7)
    assert DEFAULT_LATENCY_BUCKETS[0] == 1e-4


def test_registry_get_or_create_and_aggregation():
    reg = MetricRegistry()
    c0 = reg.counter("comm.pull_bytes", device=0)
    assert reg.counter("comm.pull_bytes", device=0) is c0  # same label set
    assert reg.counter("comm.pull_bytes", device=1) is not c0
    c0.add(10).add(5)
    reg.counter("comm.pull_bytes", device=1).add(3)
    reg.counter("comm.pull_bytes").add(2)  # unlabeled variant
    assert reg.counter_total("comm.pull_bytes") == 20
    assert reg.per_device("comm.pull_bytes") == {0: 15, 1: 3}
    reg.gauge("occ", device=2).set(7.5)
    d = reg.as_dict()
    assert d["counters"]["comm.pull_bytes"]["device=0"] == 15
    assert d["gauges"]["occ"]["device=2"] == 7.5


def test_imbalance_report_ratios():
    tel = Telemetry()
    for dev, v in ((0, 30), (1, 10), (2, 10), (3, 10)):
        tel.counter("comm.pull_bytes", device=dev).add(v)
    rec = tel.imbalance_report()["metrics"]["comm.pull_bytes"]
    assert rec["max"] == 30 and rec["mean"] == pytest.approx(15.0)
    assert rec["max_over_mean"] == pytest.approx(2.0)
    assert rec["per_device"] == {"0": 30, "1": 10, "2": 10, "3": 10}


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_mode_is_noop_identity():
    tel = Telemetry(enabled=False)
    # identity-stable singletons: the disabled path allocates nothing per call
    assert tel.span("x", step=1) is NULL_SPAN
    assert tel.counter("c") is NULL_METRIC
    assert tel.gauge("g") is NULL_METRIC
    assert tel.histogram("h") is NULL_METRIC
    with tel.span("x") as sp:
        sp.set(bytes=1)  # chainable no-op
    tel.instant("x", bytes=1)
    tel.log_step(step=0)
    tel.attach_executable("e", {"a": 1})
    assert tel.trace.spans() == []
    assert tel.run_summary()["spans"]["count"] == 0
    assert tel.span_seconds() == {}
    assert tel.imbalance_report() == {"spans": {}, "metrics": {}}
    assert NULL_TELEMETRY.span("y") is NULL_SPAN


def test_disabled_mode_overhead_bounded():
    tel = Telemetry(enabled=False)
    n = 10000
    t0 = time.perf_counter()
    for i in range(n):
        with tel.span("s", step=i):
            pass
        tel.counter("c", device=0).add(1)
    per_call = (time.perf_counter() - t0) / n
    # generous absolute bound (~50x the measured cost) so loaded CI passes:
    # the point is "no hidden allocation/locking", not a microbench race
    assert per_call < 50e-6, f"disabled telemetry costs {per_call*1e6:.1f}us"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _profile(log_dir, fn):
    """Run ``fn`` under the JAX profiler (host annotations only) and return
    the trace's directory."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), create_perfetto_trace=True,
                            profiler_options=opts):
        fn()
    (run,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*"))
    return run


def test_chrome_trace_schema_roundtrip(tmp_path):
    tel = Telemetry()

    def record():
        with tel.span("sample", step=0, device=1):
            with tel.span("extract", step=0, device=1):
                pass
        tel.instant("exchange", stage="extract", bytes=64, device=2)

    run = _profile(tmp_path, record)
    # the profiler's Chrome trace-event JSON, round-tripped through real JSON
    with gzip.open(os.path.join(run, "perfetto_trace.json.gz"), "rt") as f:
        trace = json.load(f)
    xev = [e for e in trace["traceEvents"]
           if e.get("ph") == "X" and e["name"] in ("sample", "extract")]
    assert len(xev) == 2
    for e in xev:
        assert set(("name", "ph", "ts", "dur", "pid", "tid")) <= set(e)
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        # the span's labels ride along as the event's arguments
        assert e["args"] == {"step": "0", "device": "1"}
    sample, extract = sorted(xev, key=lambda e: (e["ts"], -e["dur"]))
    assert sample["name"] == "sample" and sample["tid"] == extract["tid"]
    assert sample["ts"] <= extract["ts"]
    assert extract["ts"] + extract["dur"] <= sample["ts"] + sample["dur"]
    # an instant marks bytes on the tracer alone
    exch = [s for s in tel.trace.spans() if s.name == "exchange"]
    assert len(exch) == 1
    assert exch[0].labels["bytes"] == 64 and exch[0].dur == 0.0


def _host_events(run):
    """{name: [seconds]} of the events on the trace's host python line."""
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(run, "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(ev.duration_ns / 1e9)
    return out


@pytest.mark.parametrize("enabled", [True, False])
def test_engine_spans_on_the_profiler_host_line(tmp_path, enabled):
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(128, avg_degree=4, feature_dim=8, num_classes=4, seed=0)
    tel = Telemetry(enabled=enabled)

    def run_engine():
        eng = DistGNNEngine(g, cfg=EngineConfig(hidden=16, num_layers=2),
                            telemetry=tel)
        assert eng.telemetry is tel
        eng.train(2)

    events = _host_events(_profile(tmp_path, run_engine))
    names = ("train", "layout.partition", "layout.vertex_blocks",
             "layout.store", "layout.exchange_plan", "step.place_consts")
    if not enabled:
        assert not set(names) & set(events)
        assert tel.trace.spans() == []
        return
    assert len(events["train"]) == 2
    spans = tel.trace.spans()
    for name in names:
        mine = [s.dur for s in spans if s.name == name]
        assert len(events[name]) == len(mine) >= 1, name
        # one interval, read on two clocks
        for d_prof, d_tel in zip(events[name], mine):
            assert abs(d_prof - d_tel) < 0.01 + 0.1 * d_tel, (name, d_prof,
                                                              d_tel)


def test_step_log_jsonl(tmp_path):
    tel = Telemetry()
    tel.log_step(step=0, loss=0.5, comm_total_bytes=128)
    tel.log_step(step=1, loss=0.25, comm_total_bytes=256)
    path = tmp_path / "steps.jsonl"
    tel.write_step_log(str(path))
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert recs == [{"step": 0, "loss": 0.5, "comm_total_bytes": 128},
                    {"step": 1, "loss": 0.25, "comm_total_bytes": 256}]
    summary = tel.run_summary()
    assert summary["steps"] == recs


# ---------------------------------------------------------------------------
# satellite 1: CommStats.reset() keeps held references live
# ---------------------------------------------------------------------------

def test_commstats_reset_in_place():
    stats = CommStats()
    held = stats  # e.g. a bench accumulating per-epoch deltas
    stats.pull_bytes += 100
    stats.cache_hit_bytes += 40
    assert stats.reset() is stats
    assert held.total() == 0 and held.requested() == 0
    stats.push_bytes += 7  # post-reset traffic still visible through `held`
    assert held.total() == 7


# ---------------------------------------------------------------------------
# engine integration: the run-wide contract on 4 forced-host devices
# ---------------------------------------------------------------------------

ENGINE_TRACE_CODE = r"""
import dataclasses, json
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.serving import GNNQueryEngine

g = sbm_graph(96, num_blocks=4, p_in=0.2, p_out=0.05, feature_dim=8,
              num_classes=4, seed=0)
cfg = EngineConfig(batching="node_wise", execution="p2p", batch_size=4,
                   fanouts=(3, 3), cache_policy="static_degree",
                   cache_capacity=8, seed=0)
eng = DistGNNEngine(g, cfg=cfg)
held = eng.comm_stats  # satellite 1: must survive the engine's resets
tel = eng.enable_telemetry()
NB = 4
state, losses, times = eng.run_epoch_minibatch(NB, schedule="pipelined")
assert held is eng.comm_stats, "engine re-instantiated CommStats"
assert held.total() > 0, "held CommStats reference detached from traffic"

qe = GNNQueryEngine(eng, state["params"])
qe.submit([1, 2, 3]); qe.submit([3, 4])
qe.flush()

# exchange accounting: summed exchange-span bytes == CommStats.total()
spans = tel.trace.spans()
exch = sum(s.labels["bytes"] for s in spans if s.name == "exchange")
assert exch == eng.comm_stats.total(), (exch, eng.comm_stats.total())

# every CommStats field mirrors into a comm.* counter, exactly
for f in dataclasses.fields(eng.comm_stats):
    mirrored = tel.metrics.counter_total("comm." + f.name)
    assert mirrored == getattr(eng.comm_stats, f.name), (f.name, mirrored)

# spans cover every configured step in every pipeline stage
for stage in ("sample", "extract", "train"):
    steps = {s.labels.get("step") for s in spans if s.name == stage}
    assert set(range(NB)) <= steps, (stage, steps)

# prefetch producer and trainer threads are distinct lanes
assert len({s.tid for s in spans}) >= 2, "expected >= 2 lanes"

# imbalance report sees per-device bytes, layout gauges, occupancy
rep = tel.imbalance_report()["metrics"]
for name in ("comm.pull_bytes", "layout.owned_vertices",
             "frontier_occupancy", "store.overlay_hit"):
    assert name in rep and len(rep[name]["per_device"]) == 4, name
    assert rep[name]["max_over_mean"] >= 1.0

# serving instrumented: flush latency histogram + coalescing counters
assert tel.histogram("serve.flush_latency_s").count == 1
assert tel.metrics.counter_total("serve.queries") == 2
assert tel.metrics.counter_total("serve.targets_requested") == 5

# run summary is JSON-serializable end to end
json.dumps(tel.run_summary())
print("TRACED_ENGINE_OK")
"""


def test_traced_engine_contract_4dev():
    out = run_with_devices(ENGINE_TRACE_CODE, n_devices=4)
    assert "TRACED_ENGINE_OK" in out
