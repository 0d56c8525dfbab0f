"""GNN-side benchmarks — one per survey table/figure analog.

Each function returns (rows, derived_summary): rows are printable dicts; the
summary is one line for the CSV contract in run.py.

``python benchmarks/bench_gnn.py --json`` seeds the step-pipeline perf
trajectory: it writes BENCH_step_pipeline.json (blocking vs thread-pipelined
vs PROCESS-pipelined epoch wall-clock, chunked vs monolithic exchange peak
bytes + step time, measured on forced-host 4/8-device subprocesses).  The
thread pipeline's wall comparison is capacity-gated (it needs a spare core
for the sampler thread); the process pipeline's is NOT — its workers hold
their own GILs and its finished-batch LRU reuses the deterministic batches
across epochs, so process-pipelined <= blocking is asserted on any host.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core import full_graph_train, powerlaw_graph, sbm_graph
from repro.core.partition import PARTITIONERS
from repro.core.protocols import PROTOCOL_COSTS
from repro.core.sampling import (
    FIFOCache,
    analysis_cache,
    csp_sample,
    importance_cache,
    node_wise_sample,
    presampling_cache,
    pull_based_sample,
    simulate_hit_ratio,
    skewed_weighted_sample,
    static_degree_cache,
)


def bench_partition() -> Tuple[List[Dict], str]:
    """Survey §4.2 table: partition quality (cut, balance, train balance,
    comm volume) per partitioner, on a community graph and a power-law graph."""
    rows = []
    for gname, g in (("sbm", sbm_graph(400, num_blocks=8, p_in=0.06, p_out=0.003, seed=0)),
                     ("powerlaw", powerlaw_graph(400, avg_degree=10, seed=0))):
        for name in ("hash", "range", "ldg", "pagraph", "block", "bytegnn", "metis_like"):
            t0 = time.perf_counter()
            part = PARTITIONERS[name](g, 8)
            dt = time.perf_counter() - t0
            rows.append(dict(graph=gname, partitioner=name,
                             cut=round(part.edge_cut_fraction(g), 4),
                             balance=round(part.vertex_balance(), 3),
                             train_balance=round(part.train_balance(g), 3),
                             comm_rows=part.communication_volume(g),
                             seconds=round(dt, 3)))
    balanced = [r for r in rows if r["graph"] == "sbm" and r["balance"] < 1.5]
    best = min(balanced, key=lambda r: r["cut"])
    return rows, f"best_balanced_sbm_cut={best['partitioner']}:{best['cut']}"


def bench_cache() -> Tuple[List[Dict], str]:
    """Survey §5.1: hit ratio per cache policy (PaGraph/AliGraph/GNNLab/
    SALIENT++/BGL claims) at several capacities on a power-law graph."""
    g = powerlaw_graph(600, avg_degree=12, seed=1)
    rng = np.random.default_rng(0)
    train = np.where(g.train_mask)[0]

    def stream(seed=0):
        r = np.random.default_rng(seed)
        for _ in range(30):
            batch = r.choice(train, 16, replace=False)
            yield node_wise_sample(g, batch, (4, 4), r).layer_vertices[0]

    rows = []
    for cap_frac in (0.05, 0.15, 0.3):
        cap = int(cap_frac * g.num_vertices)
        random_ids = rng.choice(g.num_vertices, cap, replace=False)
        policies = {
            "random": lambda: random_ids,
            "degree(PaGraph)": lambda: static_degree_cache(g, cap),
            "importance(AliGraph)": lambda: importance_cache(g, cap),
            "presampling(GNNLab)": lambda: presampling_cache(g, cap),
            "analysis(SALIENT++)": lambda: analysis_cache(g, cap),
        }
        for name, fn in policies.items():
            hr = simulate_hit_ratio(fn(), stream())
            rows.append(dict(capacity=cap, policy=name, hit_ratio=round(hr, 4)))
        fifo = FIFOCache(cap)
        rows.append(dict(capacity=cap, policy="fifo(BGL)",
                         hit_ratio=round(fifo.run(stream()), 4)))
    top = max(rows, key=lambda r: r["hit_ratio"])
    return rows, f"best={top['policy']}@{top['capacity']}:{top['hit_ratio']}"


def bench_distributed_sampling() -> Tuple[List[Dict], str]:
    """Survey §5.1: DSP's CSP vs pull-based bytes; skewed-sampling locality."""
    g = powerlaw_graph(600, avg_degree=12, seed=2)
    part = PARTITIONERS["hash"](g, 8)
    rng = np.random.default_rng(0)
    targets = np.arange(256)
    rows = []
    _, pull = pull_based_sample(g, part, 0, targets, fanout=5, rng=rng)
    _, push = csp_sample(g, part, 0, targets, fanout=5, rng=rng)
    rows.append(dict(method="pull(DistDGL)", bytes=pull.total()))
    rows.append(dict(method="csp(DSP)", bytes=push.total(),
                     reduction=round(1 - push.total() / max(pull.total(), 1), 3)))
    for s in (1.0, 2.0, 4.0, 8.0):
        _, st, loc = skewed_weighted_sample(g, part, 0, targets, 5, s,
                                            np.random.default_rng(1))
        rows.append(dict(method=f"skewed(s={s})", bytes=st.total(),
                         locality=round(loc, 3)))
    return rows, f"csp_reduction={rows[1]['reduction']}"


def bench_protocol_costs() -> Tuple[List[Dict], str]:
    """Survey §7.1: per-protocol communication volume per layer."""
    g = powerlaw_graph(500, avg_degree=10, seed=3)
    part = PARTITIONERS["metis_like"](g, 8)
    rows = []
    for name, fn in PROTOCOL_COSTS.items():
        c = fn(g, part, 64)
        rows.append(dict(protocol=name, bytes_per_layer=c.bytes_per_layer,
                         messages=c.messages_per_layer))
    b = next(r for r in rows if r["protocol"] == "broadcast")["bytes_per_layer"]
    p = next(r for r in rows if r["protocol"] == "p2p")["bytes_per_layer"]
    return rows, f"p2p_vs_broadcast={p / max(b, 1):.3f}"


def bench_staleness() -> Tuple[List[Dict], str]:
    """Survey §7.2 / Table 3: accuracy + bytes pushed per staleness model
    (PipeGCN/SANCUS claim: bounded staleness ~ sync accuracy, less comm)."""
    g = sbm_graph(250, num_blocks=4, p_in=0.08, p_out=0.004, seed=4)
    rows = []
    sync = full_graph_train(g, epochs=50)
    rows.append(dict(protocol="sync", test_acc=round(sync.test_acc, 4),
                     final_loss=round(sync.losses[-1], 4), mbytes_pushed="n/a"))
    for proto, kw in (("epoch_fixed", dict(staleness=2)),
                      ("epoch_fixed", dict(staleness=4)),
                      ("epoch_adaptive", dict(staleness=4)),
                      ("variation", dict(eps_v=0.05)),
                      ("pipegcn", dict(lr=0.3))):
        r = full_graph_train(g, protocol=proto, epochs=50, **kw)
        rows.append(dict(protocol=f"{proto}:{kw}", test_acc=round(r.test_acc, 4),
                         final_loss=round(r.losses[-1], 4),
                         mbytes_pushed=round(r.bytes_pushed / 1e6, 3)))
    gap = max(abs(r["test_acc"] - rows[0]["test_acc"]) for r in rows[1:])
    return rows, f"max_acc_gap_vs_sync={gap:.4f}"


def bench_trainable_embeddings() -> Tuple[List[Dict], str]:
    """ISSUE 6: the wire cost of making layer-0 rows TRAINABLE embeddings.

    Full-graph: `embedding_grad_bytes_per_step` (the transpose of one
    layer-0-width exchange) per execution model x partitioner — p2p returns
    each halo cotangent to its owner once, so its advantage over the
    broadcast/ring reduce-scatter grows with partition quality.  Mini-batch:
    `embedding_update_bytes` with and without the hot-row cache overlay —
    cached rows stop costing per-miss fetches but start costing the fixed
    2*overlay refresh/grad rows per step, so the overlay only pays for
    itself once the hit rows it absorbs exceed that rent."""
    from repro.core.partition.cost_models import embedding_grad_bytes_per_step
    from repro.core.sampling.distributed import embedding_update_bytes

    g = powerlaw_graph(600, avg_degree=12, seed=5)
    k, D = 8, 64
    nb = -(-g.num_vertices // k)
    rows = []
    for pname in ("hash", "metis_like"):
        part = PARTITIONERS[pname](g, k)
        per_exec = {
            ex: embedding_grad_bytes_per_step(g, ex, (D,), k=k, part=part,
                                              nb=nb)
            for ex in ("broadcast", "ring", "p2p")}
        for ex, b in per_exec.items():
            rows.append(dict(mode="full_graph", partitioner=pname,
                             execution=ex, embed_grad_bytes=b,
                             vs_broadcast=round(
                                 b / max(per_exec["broadcast"], 1), 3)))

    part = PARTITIONERS["metis_like"](g, k)
    train = np.where(g.train_mask)[0]
    rng = np.random.default_rng(0)
    frontiers = []
    for _ in range(30):
        batch = rng.choice(train, 16, replace=False)
        frontiers.append(node_wise_sample(g, batch, (4, 4),
                                          rng).layer_vertices[0])
    for cap_frac in (0.0, 0.05, 0.15):
        cap = int(cap_frac * g.num_vertices)
        cached = (frozenset(int(v) for v in static_degree_cache(g, cap))
                  if cap else frozenset())
        total = sum(embedding_update_bytes(part, 0, f, D, cached_ids=cached,
                                           overlay_rows=cap)
                    for f in frontiers)
        rows.append(dict(mode="node_wise", partitioner="metis_like",
                         cache_capacity=cap,
                         embed_grad_bytes=total // len(frontiers)))
    fg = {r["execution"]: r["embed_grad_bytes"] for r in rows
          if r["mode"] == "full_graph" and r["partitioner"] == "metis_like"}
    mb = {r["cache_capacity"]: r["embed_grad_bytes"] for r in rows
          if r["mode"] == "node_wise"}
    best_cap = min(mb, key=mb.get)
    return rows, (f"p2p_vs_broadcast={fg['p2p'] / max(fg['broadcast'], 1):.3f}"
                  f" best_overlay_cap={best_cap}")


# ---------------------------------------------------------------------------
# ISSUE 4: the pipelined hot path — blocking vs pipelined epoch wall-clock
# and chunked vs monolithic exchange, measured for real on forced-host
# devices (fresh subprocesses so the parent keeps its single device).
# ---------------------------------------------------------------------------

_PIPELINE_PROBE = r"""
import json, os, time
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.execution.minibatch_pipeline import pipelined_wall_model
from repro.core.execution.pipeline_exchange import gathered_table_peak_bytes
from repro.core.graph import sbm_graph

n_dev = len(jax.devices())
g = sbm_graph(256, num_blocks=8, p_in=0.06, p_out=0.01, seed=0)

# -- blocking vs thread-pipelined vs process-pipelined epoch ---------------
cfg = EngineConfig(execution="broadcast", batching="node_wise", batch_size=16,
                   fanouts=(4, 4), hidden=32, lr=0.3, exchange_chunks=4,
                   prefetch_depth=2, num_sample_workers=2)
eng = DistGNNEngine(g, cfg=cfg)
# warm the one jit compile, the host caches, and every schedule path — the
# process warm-up also starts the persistent worker pool + shm ring, so
# pool startup is paid OUTSIDE the timed region (as in real training, where
# one pool serves the whole run)
eng.run_epoch_minibatch(2)
eng.run_epoch_minibatch(2, schedule="pipelined")
eng.run_epoch_minibatch(2, schedule="pipelined", prefetch_mode="process")
NB, TRIALS = 12, 3
trials = []
for _ in range(TRIALS):  # interleaved: all arms see the same machine load
    _, lb, tb = eng.run_epoch_minibatch(NB, schedule="conventional")
    _, lt, tt = eng.run_epoch_minibatch(NB, schedule="pipelined")
    _, lp, tp = eng.run_epoch_minibatch(NB, schedule="pipelined",
                                        prefetch_mode="process")
    assert lt == lb, "thread-pipelined epoch must be bitwise-identical"
    assert lp == lb, "process-pipelined epoch must be bitwise-identical"
    trials.append((tb, tt, tp))
eng.close_prefetch_pool()
blocking = min((b for b, _, _ in trials), key=lambda t: t.wall)
threaded = min((t for _, t, _ in trials), key=lambda t: t.wall)
processed = min((p for _, _, p in trials), key=lambda t: t.wall)
model = pipelined_wall_model(threaded, NB)

# The thread pipeline's lanes really ran concurrently: the measured wall
# must sit below the serial sum of the run's OWN measured stage times.
# This is the machine-independent overlap evidence; the thread wall-vs-
# blocking comparison additionally needs a spare core beyond the forced
# host devices (an oversubscribed host serializes the lanes through GIL +
# core contention and can make the thread pipeline slower than blocking —
# recorded either way, gated by overlap_capacity_limited).
assert threaded.wall <= 0.95 * threaded.busy(), (
    "no measured overlap", threaded.wall, threaded.busy())
thread_capacity_limited = (os.cpu_count() or 1) < n_dev + 1
if not thread_capacity_limited:
    assert threaded.wall <= blocking.wall, (
        "thread-pipelined epoch slower than blocking with spare cores",
        threaded.wall, blocking.wall)
# The PROCESS pipeline has no capacity escape hatch: its producers hold
# their own GILs, the trainer defers every device sync to epoch end, and
# the persistent pool's finished-batch LRU serves repeat epochs without
# resampling (batches are deterministic in (seed, step, device) — pure
# functions of the step), so it must beat the per-step-syncing blocking
# epoch on ANY host, 1 core up.
assert processed.wall <= blocking.wall, (
    "process-pipelined epoch slower than blocking",
    processed.wall, blocking.wall)

# -- chunked vs monolithic full-graph broadcast exchange ------------------
steps = {}
for chunks in (1, 4):
    e = DistGNNEngine(g, cfg=EngineConfig(execution="broadcast", hidden=32,
                                          lr=0.3, exchange_chunks=chunks))
    step = e.make_step()
    state = e.init_state()
    state, m, _ = step(state)
    jax.block_until_ready(m["loss"])  # compile + first step
    t0 = time.perf_counter()
    for _ in range(5):
        state, m, _ = step(state)
    jax.block_until_ready(m["loss"])
    steps[chunks] = dict(
        step_seconds=(time.perf_counter() - t0) / 5,
        gathered_table_peak_bytes=gathered_table_peak_bytes(
            e.Vp, max(e.dims[:-1]), chunks))

print("BENCH_JSON " + json.dumps(dict(
    devices=n_dev, num_batches=NB, host_cores=os.cpu_count(),
    blocking_epoch_seconds=blocking.wall,
    thread_pipelined=dict(
        epoch_seconds=threaded.wall,
        busy_seconds=threaded.busy(),
        overlap_ratio=threaded.wall / max(threaded.busy(), 1e-9),
        lane_seconds=dict(sample=threaded.sample, extract=threaded.extract,
                          train=threaded.train),
        wall_model_seconds=model,
        overlap_capacity_limited=thread_capacity_limited),
    process_pipelined=dict(
        epoch_seconds=processed.wall,
        busy_seconds=processed.busy(),
        lane_seconds=dict(sample=processed.sample, extract=processed.extract,
                          train=processed.train),
        num_sample_workers=2,
        overlap_capacity_limited=False),
    exchange=dict(monolithic=steps[1], chunked_4=steps[4]))))
"""


def bench_step_pipeline(out_dir: str = "experiments/dryrun"
                        ) -> Tuple[List[Dict], str]:
    """ISSUE 4 + ISSUE 9 perf trajectory: measure blocking vs
    thread-pipelined vs PROCESS-pipelined epochs (and the chunked exchange
    against the monolithic one) on forced-host 4/8-device subprocesses;
    write BENCH_step_pipeline.json.

    Asserted per device count: both pipelined epochs' losses == blocking
    losses bitwise, the thread pipeline's wall sits below the serial sum of
    its own measured lanes (real overlap), and — on hosts with at least one
    spare core beyond the forced devices — thread-pipelined wall <=
    blocking wall.  On an oversubscribed host (cores <= devices) the XLA
    compute threads, the collective spin-waits, and the sampler thread
    fight for the same cores, so the thread wall comparison is recorded
    with ``overlap_capacity_limited: true`` instead of asserted.  The
    PROCESS pipeline carries no such gate: its sampler workers hold their
    own GILs, the trainer syncs once per epoch instead of per step, and the
    persistent pool's finished-batch LRU exploits the engine's
    deterministic sampling to serve repeat epochs without resampling, so
    process-pipelined wall <= blocking wall is asserted unconditionally."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = dict(graph="sbm_256", devices={})
    rows = []
    for n_dev in (4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", _PIPELINE_PROBE],
                              capture_output=True, text=True, timeout=900,
                              env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"pipeline probe failed on {n_dev} devices:\n"
                f"{proc.stdout}\n{proc.stderr[-3000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("BENCH_JSON ")][-1]
        entry = json.loads(line[len("BENCH_JSON "):])
        result["devices"][str(n_dev)] = entry
        ex = entry["exchange"]
        th, pr = entry["thread_pipelined"], entry["process_pipelined"]
        rows.append(dict(
            devices=n_dev,
            blocking_s=round(entry["blocking_epoch_seconds"], 4),
            thread_s=round(th["epoch_seconds"], 4),
            process_s=round(pr["epoch_seconds"], 4),
            thread_speedup=round(entry["blocking_epoch_seconds"]
                                 / max(th["epoch_seconds"], 1e-9), 3),
            process_speedup=round(entry["blocking_epoch_seconds"]
                                  / max(pr["epoch_seconds"], 1e-9), 3),
            overlap_ratio=round(th["overlap_ratio"], 3),
            thread_capacity_limited=th["overlap_capacity_limited"],
            process_capacity_limited=pr["overlap_capacity_limited"],
            chunk_peak_reduction=round(
                ex["monolithic"]["gathered_table_peak_bytes"]
                / ex["chunked_4"]["gathered_table_peak_bytes"], 2),
            chunked_step_s=round(ex["chunked_4"]["step_seconds"], 5),
            monolithic_step_s=round(ex["monolithic"]["step_seconds"], 5)))
    # write the artifact BEFORE asserting so a failed claim leaves evidence
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_step_pipeline.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=float)
    for r in rows:
        assert r["overlap_ratio"] <= 0.95, (
            f"pipelined lanes did not overlap on {r['devices']} devices: {r}")
        if not r["thread_capacity_limited"]:
            assert r["thread_s"] <= r["blocking_s"], (
                f"thread-pipelined epoch must not be slower than blocking "
                f"on {r['devices']} devices: {r}")
        assert not r["process_capacity_limited"], r
        assert r["process_s"] <= r["blocking_s"], (
            f"process-pipelined epoch must not be slower than blocking "
            f"on {r['devices']} devices (no capacity escape hatch): {r}")
        assert r["chunk_peak_reduction"] >= 2, r
    best = max(rows, key=lambda r: r["process_speedup"])
    return rows, (f"process_speedup@{best['devices']}dev="
                  f"{best['process_speedup']} artifact={path}")


# ---------------------------------------------------------------------------
# ISSUE 8: run-wide telemetry — traced vs untraced epoch wall (the overhead
# contract), the per-stage wall breakdown, workload-imbalance ratios, and the
# trace-accounting cross-checks, measured on a forced-host 4-device
# subprocess.  Artifact: BENCH_telemetry.json, written before any assertion.
# ---------------------------------------------------------------------------

_TELEMETRY_PROBE = r"""
import json, time
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.serving import GNNQueryEngine
from repro.core.telemetry import Telemetry
from repro.launch.hlo_analysis import executable_summary

n_dev = len(jax.devices())
g = sbm_graph(256, num_blocks=8, p_in=0.06, p_out=0.01, seed=0)
cfg = EngineConfig(execution="p2p", batching="node_wise", batch_size=16,
                   fanouts=(4, 4), hidden=32, lr=0.3,
                   cache_policy="static_degree", cache_capacity=32)
eng = DistGNNEngine(g, cfg=cfg)
eng.run_epoch_minibatch(2)  # warm: the one jit compile + host caches
NB, TRIALS = 10, 5
untraced, traced = [], []
tel = state = None
for _ in range(TRIALS):  # interleaved arms: both see the same machine load
    eng.enable_telemetry(Telemetry(enabled=False))
    t0 = time.perf_counter()
    eng.run_epoch_minibatch(NB)
    untraced.append(time.perf_counter() - t0)
    tel = eng.enable_telemetry(Telemetry())  # fresh trace per traced trial
    t0 = time.perf_counter()
    state, _, times = eng.run_epoch_minibatch(NB)
    traced.append(time.perf_counter() - t0)

# serve through the SAME trace: flush latency histogram + coalescing stats
# (comm_stats keeps accumulating — the trace contract must still balance)
qe = GNNQueryEngine(eng, state["params"])
for q in ([1, 2, 3], [3, 4], [10, 11, 12, 13]):
    qe.submit(q)
qe.flush()
qe.query([5, 6])

# static executable facts enrich the run summary (hlo_analysis)
tel.attach_executable("minibatch_train_step",
                      executable_summary(eng.lower_minibatch_step().compile()))

# microbench the tracer itself: the per-span bookkeeping cost in isolation
N = 20000
t0 = time.perf_counter()
for i in range(N):
    with tel.span("microbench", step=i, device=0):
        pass
span_cost = (time.perf_counter() - t0) / N

spans = tel.trace.spans()
spans_ok = all(s.t0 >= tel.trace.origin and s.dur >= 0.0 for s in spans)
exchange_bytes = sum(s.labels.get("bytes", 0) for s in spans
                     if s.name == "exchange")
summary = tel.run_summary()
u, t = min(untraced), min(traced)
print("BENCH_JSON " + json.dumps(dict(
    devices=n_dev, num_batches=NB, trials=TRIALS,
    untraced_epoch_seconds=u, traced_epoch_seconds=t,
    overhead_ratio=max(0.0, t - u) / u,
    span_cost_seconds=span_cost,
    spans_per_epoch=summary["spans"]["count"],
    stage_seconds=summary["spans"]["seconds_by_name"],
    stage_times=dict(sample=times.sample, extract=times.extract,
                     train=times.train, wall=times.wall),
    imbalance=summary["imbalance"],
    exchange_span_bytes=exchange_bytes,
    comm_total_bytes=eng.comm_stats.total(),
    span_count=len(spans), spans_ok=spans_ok,
    serve=dict(
        flush_p50_ms=tel.histogram("serve.flush_latency_s").percentile(50)
        * 1e3,
        flush_p99_ms=tel.histogram("serve.flush_latency_s").percentile(99)
        * 1e3,
        queries=tel.metrics.counter_total("serve.queries"),
        rounds=tel.metrics.counter_total("serve.rounds"),
        targets_requested=tel.metrics.counter_total(
            "serve.targets_requested"),
        targets_unique=tel.metrics.counter_total("serve.targets_unique")),
    executables=summary["executables"]), default=float))
"""


def bench_telemetry(out_dir: str = "experiments/dryrun"
                    ) -> Tuple[List[Dict], str]:
    """ISSUE 8 observability contract, measured on a forced-host 4-device
    subprocess and written to BENCH_telemetry.json BEFORE any assertion:

    - telemetry overhead: min traced epoch wall vs min untraced epoch wall
      over interleaved trials, asserted < 5% (plus the isolated per-span
      bookkeeping cost for context);
    - per-stage wall breakdown (span seconds by stage) and the workload-
      imbalance report (max/mean per stage across devices);
    - trace accounting: summed exchange-span bytes == CommStats.total()
      EXACTLY, and every span lies after the tracer's origin."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _TELEMETRY_PROBE],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"telemetry probe failed:\n{proc.stdout}\n"
                           f"{proc.stderr[-3000:]}")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("BENCH_JSON ")][-1]
    entry = json.loads(line[len("BENCH_JSON "):])
    # write the artifact BEFORE asserting so a failed claim leaves evidence
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_telemetry.json")
    with open(path, "w") as f:
        json.dump(entry, f, indent=1, default=float)
    assert entry["overhead_ratio"] < 0.05, (
        f"traced epoch must cost < 5% over untraced: "
        f"{entry['overhead_ratio']:.3f} "
        f"(untraced {entry['untraced_epoch_seconds']:.3f}s, "
        f"traced {entry['traced_epoch_seconds']:.3f}s)")
    assert entry["exchange_span_bytes"] == entry["comm_total_bytes"], entry
    assert entry["spans_ok"] and entry["span_count"] > 0
    stages = entry["imbalance"]["metrics"]
    assert stages, "imbalance report is empty"
    for name, rec in stages.items():
        assert rec["max_over_mean"] >= 1.0 or rec["mean"] == 0, (name, rec)
    rows = [dict(
        devices=entry["devices"],
        untraced_s=round(entry["untraced_epoch_seconds"], 4),
        traced_s=round(entry["traced_epoch_seconds"], 4),
        overhead=round(entry["overhead_ratio"], 4),
        span_cost_us=round(entry["span_cost_seconds"] * 1e6, 2),
        spans=entry["spans_per_epoch"],
        exchange_bytes=entry["exchange_span_bytes"],
        imbalance_stages=len(stages))]
    return rows, (f"telemetry_overhead={rows[0]['overhead']}"
                  f" artifact={path}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="run the step-pipeline bench and write "
                    "BENCH_step_pipeline.json")
    ap.add_argument("--telemetry", action="store_true",
                    help="run the telemetry bench and write "
                    "BENCH_telemetry.json")
    ap.add_argument("--bench-partition-families", action="store_true",
                    help="run the partition-families cost bench (edge-cut "
                    "halo vs vertex-cut replica-sync vs hybrid degree-"
                    "threshold sweep across graphs x chips) and write "
                    "BENCH_partition_families.json — asserts vertex-cut "
                    "beats edge-cut critical path on the base power-law "
                    "256-chip point and the best hybrid threshold beats "
                    "BOTH pure families on the double-size one")
    ap.add_argument("--vertices", type=int, default=2048,
                    help="partition-families bench: base synthetic graph "
                    "size (the hybrid regime point doubles it)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if not (args.json or args.telemetry or args.bench_partition_families):
        ap.error("pass --json, --telemetry and/or --bench-partition-families "
                 "(the CSV benches run via benchmarks/run.py)")
    if args.bench_partition_families:
        from repro.configs.gcn_paper import CONFIG as GNN_CFG
        from repro.launch.dryrun_gnn import bench_partition_families

        dims = ([GNN_CFG.feature_dim]
                + [GNN_CFG.hidden_dim] * (GNN_CFG.num_layers - 1)
                + [GNN_CFG.num_classes])
        path = bench_partition_families(args.out, dims,
                                        vertices=args.vertices)
        print(f"partition-families bench -> {path}")
    if args.json:
        rows, derived = bench_step_pipeline(args.out)
        for r in rows:
            print(r)
        print(derived)
    if args.telemetry:
        rows, derived = bench_telemetry(args.out)
        for r in rows:
            print(r)
        print(derived)


if __name__ == "__main__":
    main()
