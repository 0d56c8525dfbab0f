#!/usr/bin/env bash
# CI entrypoints (see tests/README.md for the tier matrix).
#
#   scripts/ci.sh           tier-1: the full suite (the repo's contract)
#   scripts/ci.sh --smoke   fast subset: kernels + a 4-device engine smoke
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# tier-matrix completeness: every tests/test_*.py must have a row in
# tests/README.md — fail FAST (before any pytest run) so a new test module
# can't silently ship undocumented / untiered
python - <<'EOF'
import pathlib, re, sys

tests = pathlib.Path("tests")
readme = (tests / "README.md").read_text()
listed = set(re.findall(r"test_\w+\.py", readme))
present = {p.name for p in tests.glob("test_*.py")}
missing = sorted(present - listed)
if missing:
    sys.exit("tests/README.md tier matrix is missing rows for: "
             + ", ".join(missing))
stale = sorted(listed - present)
if stale:
    sys.exit("tests/README.md lists test modules that do not exist: "
             + ", ".join(stale))
print(f"tier matrix complete: {len(present)} test modules all listed")
EOF

if [[ "${1:-}" == "--smoke" ]]; then
    python -m pytest -x -q tests/test_kernels.py tests/test_exec_protocols.py
    # 4-device engine smoke: one exec model x {sync, async} vs the oracle
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
for proto in ("sync", "epoch_adaptive"):
    eng = DistGNNEngine(g, cfg=EngineConfig(execution="p2p", protocol=proto,
                                            hidden=16, lr=0.3))
    ld, _ = eng.train(3)
    lr_, _ = eng.train(3, reference=True)
    err = max(abs(a - b) for a, b in zip(ld, lr_))
    assert err < 1e-4, (proto, err)
    print(f"smoke OK p2p/{proto}: oracle err {err:.2e}")
EOF
    # 4-device node-wise MINI-BATCH engine smoke (budget < 60 s): sampled
    # batches + resident cache vs the oracle, one compile per fanout config
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="p2p", batching="node_wise", batch_size=8, fanouts=(3, 3),
    hidden=16, lr=0.3, cache_policy="static_degree", cache_capacity=12))
ld, _ = eng.train(3)
lr_, _ = eng.train(3, reference=True)
err = max(abs(a - b) for a, b in zip(ld, lr_))
assert err < 1e-4, err
assert eng._jit_mb_step._cache_size() == 1, eng._jit_mb_step._cache_size()
print(f"smoke OK node_wise minibatch p2p+cache: oracle err {err:.2e}, "
      f"1 compile, {eng.comm_stats.cache_hit_bytes} cache-hit bytes")
EOF
    # 4-device PIPELINED node-wise minibatch smoke: prefetch depth 2 +
    # chunked broadcast exchange; the pipelined epoch must be bitwise-
    # identical to the blocking one (losses, params, CommStats)
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import os
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="broadcast", batching="node_wise", batch_size=8,
    fanouts=(3, 3), hidden=16, lr=0.3, exchange_chunks=4, prefetch_depth=2))
s1, l1, t1 = eng.run_epoch_minibatch(4, schedule="conventional")
stats1 = eng.comm_stats
s2, l2, t2 = eng.run_epoch_minibatch(4, schedule="pipelined")
assert l1 == l2, (l1, l2)
eq = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                            s1["params"], s2["params"])
assert all(jax.tree_util.tree_leaves(eq)), eq
assert eng.comm_stats == stats1
assert eng._jit_mb_step._cache_size() == 1
if (os.cpu_count() or 1) >= 2:  # overlap needs a core for the sampler lane
    assert t2.busy() > t2.wall, (t2.busy(), t2.wall)
print(f"smoke OK pipelined node_wise broadcast+chunks: bitwise == blocking, "
      f"wall {t2.wall:.3f}s vs lanes {t2.busy():.3f}s")
EOF
    # 4-device PROCESS-prefetch pipelined smoke (ISSUE 9): the GIL-free
    # sampler pool + shared-memory batch ring; the process-pipelined epoch
    # must be bitwise-identical to the blocking one, and closing the pool
    # must leave /dev/shm clean
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import dataclasses, os
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="broadcast", batching="node_wise", batch_size=8,
    fanouts=(3, 3), hidden=16, lr=0.3, exchange_chunks=4, prefetch_depth=2,
    num_sample_workers=2))
s1, l1, t1 = eng.run_epoch_minibatch(4, schedule="conventional")
stats1 = dataclasses.replace(eng.comm_stats)
s2, l2, t2 = eng.run_epoch_minibatch(4, schedule="pipelined",
                                     prefetch_mode="process")
assert l1 == l2, (l1, l2)
eq = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                            s1["params"], s2["params"])
assert all(jax.tree_util.tree_leaves(eq)), eq
assert eng.comm_stats == stats1
assert eng._jit_mb_step._cache_size() == 1
eng.close_prefetch_pool()
litter = [f for f in os.listdir("/dev/shm") if f.startswith("repro-")]
assert litter == [], litter
print(f"smoke OK process-prefetch pipelined: bitwise == blocking, "
      f"shm clean, wall {t2.wall:.3f}s")
EOF
    # streaming-partition smoke (ISSUE 9): chunked edge ingest must rebuild
    # the engine's in-memory edge-cut layout array-for-array
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import numpy as np
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.partition.streaming import (
    GraphEdgeChunks,
    build_streaming_layout,
)

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(hidden=8))
lay = build_streaming_layout(
    GraphEdgeChunks(g, 64), eng.part.assignment, eng.k, g.num_vertices,
    features=g.features, labels=g.labels, train_mask=g.train_mask,
    test_mask=g.test_mask)
assert (lay.nb, lay.Vp, lay.K) == (eng.nb, eng.Vp, eng.K)
np.testing.assert_array_equal(lay.new_of_old, eng.new_of_old)
np.testing.assert_array_equal(lay.ids, eng.ids_global)
np.testing.assert_array_equal(lay.mask, np.asarray(eng.mask))
np.testing.assert_array_equal(lay.X, np.asarray(eng.store._table))
np.testing.assert_array_equal(lay.bmask, np.asarray(eng.bmask))
print(f"smoke OK streaming partition: chunk=64 identical to in-memory "
      f"build, peak_transient={lay.peak_transient_bytes} bytes")
EOF
    # 4-device MODEL-AXIS smoke: SAGE (edge-cut p2p — self features resident)
    # and GAT (vertex-cut broadcast — SDDMM logits + two-pass max/sum replica
    # softmax sync) vs their extended single-device oracles
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
for model, kw in (("sage", dict(execution="p2p")),
                  ("gat", dict(execution="broadcast",
                               partition_family="vertex_cut",
                               vertex_cut="cartesian2d"))):
    eng = DistGNNEngine(g, cfg=EngineConfig(model=model, hidden=16, lr=0.3,
                                            **kw))
    ld, _ = eng.train(3)
    lr_, _ = eng.train(3, reference=True)
    err = max(abs(a - b) for a, b in zip(ld, lr_))
    assert err < 1e-4, (model, err)
    assert eng._jit_step._cache_size() == 1
    print(f"smoke OK model={model} {kw}: oracle err {err:.2e}, 1 compile")
EOF
    # 4-device TRAINABLE-FEATURES smoke: layer-0 rows as learnable embedding
    # store rows — node-wise p2p with the cache as a live hot-row overlay,
    # row-sparse AdamW vs the dense-table oracle, embed-grad bytes accounted
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="p2p", batching="node_wise", batch_size=8, fanouts=(3, 3),
    hidden=16, lr=0.3, cache_policy="static_degree", cache_capacity=12,
    trainable_features=True, embed_lr=0.05))
ld, _ = eng.train(3)
lr_, _ = eng.train(3, reference=True)
err = max(abs(a - b) for a, b in zip(ld, lr_))
assert err < 1e-4, err
assert eng._jit_mb_step._cache_size() == 1, eng._jit_mb_step._cache_size()
assert eng.comm_stats.embed_grad_bytes > 0
print(f"smoke OK trainable node_wise p2p+overlay: oracle err {err:.2e}, "
      f"1 compile, {eng.comm_stats.embed_grad_bytes} embed-grad bytes")
EOF
    # 4-device VERTEX-CUT engine smoke: cartesian2d 2x2 cut, sync protocol,
    # replica-sync p2p GAS exchange vs the oracle + bytes accounting
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    partition_family="vertex_cut", vertex_cut="cartesian2d",
    execution="p2p", protocol="sync", hidden=16, lr=0.3))
ld, _ = eng.train(3)
lr_, _ = eng.train(3, reference=True)
err = max(abs(a - b) for a, b in zip(ld, lr_))
assert err < 1e-4, err
assert eng._jit_step._cache_size() == 1, eng._jit_step._cache_size()
assert eng.comm_stats.replica_sync_bytes > 0
print(f"smoke OK vertex_cut cartesian2d 2x2 p2p/sync: oracle err {err:.2e}, "
      f"1 compile, replication {eng.layout.replication_factor():.2f}, "
      f"{eng.comm_stats.replica_sync_bytes} replica-sync bytes")
EOF
    # 4-device SERVING smoke (ISSUE 7): one layer-wise full-graph sweep vs
    # the oracle with the wire bytes cross-checked against the engine's own
    # cost model, then a few K-target queries through the GNNQueryEngine vs
    # the single-device reference round — one serve compile total
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
import numpy as np
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.serving import GNNQueryEngine

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="p2p", batching="node_wise", batch_size=8, fanouts=(3, 3),
    hidden=16, lr=0.3, cache_policy="static_degree", cache_capacity=12))
state, _, _ = eng.run_epoch_minibatch(3)
params = state["params"]
emb = eng.global_embeddings(eng.infer_full_graph(params=params))
ref = eng.global_embeddings(eng.infer_full_graph(params=params,
                                                 reference=True))
err = float(np.max(np.abs(emb - ref)))
assert err < 1e-4, err
assert eng.comm_stats.inference_bytes == eng.inference_bytes_per_sweep()
qe = GNNQueryEngine(eng, params)
rng = np.random.default_rng(0)
for _ in range(3):
    targets = rng.choice(g.num_vertices, 6, replace=False)
    per_dev = [[] for _ in range(eng.k)]
    for v in targets:
        per_dev[int(eng.part.assignment[v])].append(int(v))
    batch = qe.build_round([np.asarray(x, np.int64) for x in per_dev])
    H = np.asarray(qe.serve_round(batch))
    R = np.asarray(qe.reference_round(batch))
    for d, tg in enumerate(per_dev):
        if tg:
            qerr = float(np.max(np.abs(H[d, :len(tg)] - R[d, :len(tg)])))
            assert qerr < 1e-4, (d, qerr)
assert qe.num_compiles() == 1, qe.num_compiles()
print(f"smoke OK serving: sweep oracle err {err:.2e}, "
      f"{eng.comm_stats.inference_bytes} inference bytes == cost model, "
      f"{qe.stats.rounds} query rounds, 1 serve compile")
EOF
    # 4-device TELEMETRY smoke: traced train + serve — the spans
    # cover every configured step on the profiler trace, and the per-step
    # CommStats fields equal the mirrored MetricRegistry counter totals
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import dataclasses, glob, os, tempfile
import jax
from jax.profiler import ProfileData
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.serving import GNNQueryEngine

g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="p2p", batching="node_wise", batch_size=8, fanouts=(3, 3),
    hidden=16, lr=0.3, cache_policy="static_degree", cache_capacity=12))
tel = eng.enable_telemetry()
NB = 4
out = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
with jax.profiler.trace(out, profiler_options=opts):
    state, _, _ = eng.run_epoch_minibatch(NB, schedule="pipelined")
    qe = GNNQueryEngine(eng, state["params"])
    qe.query([1, 2, 3])
# the spans are annotations on the profiler's host lines, labels as stats
(pb,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
host = [ev for plane in ProfileData.from_file(pb).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events]
for stage in ("sample", "extract", "train"):
    steps = {dict(ev.stats).get("step") for ev in host if ev.name == stage}
    assert set(range(NB)) <= steps, (stage, steps)
spans = tel.trace.spans()
for f in dataclasses.fields(eng.comm_stats):
    mirrored = tel.metrics.counter_total("comm." + f.name)
    assert mirrored == getattr(eng.comm_stats, f.name), (f.name, mirrored)
exch = sum(s.labels["bytes"] for s in spans if s.name == "exchange")
assert exch == eng.comm_stats.total(), (exch, eng.comm_stats.total())
print(f"smoke OK telemetry: {len(spans)} spans, all {NB} steps on the "
      f"profiler's host lines, comm counters == CommStats, exchange bytes "
      f"{exch} == total()")
EOF
    # 4-device HYBRID-CUT engine smoke (ISSUE 10): PowerLyra-style degree-
    # threshold family — low-degree halo exchange + hub replica-sync GAS —
    # vs the oracle, with the wire bytes cross-checked against the
    # standalone hybrid cost model
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
import jax
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import powerlaw_graph
from repro.core.partition.cost_models import hybrid_bytes_per_step

g = powerlaw_graph(96, avg_degree=8, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    partition_family="hybrid", execution="p2p", hidden=16, lr=0.3))
ld, _ = eng.train(3)
lr_, _ = eng.train(3, reference=True)
err = max(abs(a - b) for a, b in zip(ld, lr_))
assert err < 1e-4, err
assert eng._jit_step._cache_size() == 1, eng._jit_step._cache_size()
lay = eng.playout
wire = eng.comm_stats.halo_bytes + eng.comm_stats.replica_sync_bytes
assert wire == 3 * hybrid_bytes_per_step(
    lay.halo_rows_exec if lay.halo_active else 0,
    lay._vc_rows_per_layer if lay.sync_active else 0, eng.dims)
print(f"smoke OK hybrid p2p thr={lay.cut.threshold:.1f}: oracle err "
      f"{err:.2e}, 1 compile, {int(lay.cut.hub.sum())} hubs, "
      f"{wire} wire bytes == cost model")
EOF
    # 4-device AUTOTUNER smoke (ISSUE 10): enumerate -> choose -> validate;
    # the chosen plan's predicted step bytes must reproduce EXACTLY in the
    # traced dryrun (ratio 1.0) or the planner raises PlanRejected
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python - <<'EOF'
from repro.core.graph import powerlaw_graph
from repro.core.partition.autotune import autotune

g = powerlaw_graph(96, avg_degree=8, seed=0)
dims = [g.features.shape[1], 16, int(g.labels.max()) + 1]
plan, report = autotune(g, 4, dims, "gcn")
assert report["validation"]["ratio"] == 1.0, report["validation"]
assert len(report["candidates"]) >= 12
print(f"smoke OK autotune: chose {plan.label()} of "
      f"{len(report['candidates'])} candidates, "
      f"{plan.predicted_step_bytes} B/step validated at ratio 1.0")
EOF
else
    python -m pytest -x -q
fi
