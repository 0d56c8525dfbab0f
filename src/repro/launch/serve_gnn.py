"""GNN serving driver: the two inference tiers over a trained engine.

* THROUGHPUT — ``DistGNNEngine.infer_full_graph``: one O(L) layer-wise
  sweep produces final-layer embeddings for EVERY vertex (the production
  answer to neighbor explosion), wire bytes accounted into
  CommStats.inference_bytes and cross-checked against the engine's own
  ``inference_bytes_per_sweep``.
* LATENCY — ``GNNQueryEngine`` (core/serving.py): a persistent K-target
  query server on the padded node-wise sampler path; one compile, request
  coalescing, resident feature cache as the hot set.  Reports qps and
  p50/p99 per-query latency over a synthetic query stream.

Run with forced host devices to see real collectives on CPU:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve_gnn --exec p2p --queries 32
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compat import make_mesh
from repro.core.engine import (
    EXECUTION_MODELS,
    GNN_MODELS,
    DistGNNEngine,
    EngineConfig,
)
from repro.core.graph import sbm_graph
from repro.core.serving import GNNQueryEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.utils import get_logger

log = get_logger("repro.serve_gnn")


def build_engine(args, g):
    # vertex_cut mini-batch sampling is a ROADMAP follow-up: the latency tier
    # (node-wise query serving) is edge-cut; the layer-wise sweep runs under
    # BOTH families via the full-graph exchange plan.
    vc = args.partition_family == "vertex_cut"
    cfg = EngineConfig(execution=args.exec, model=args.model,
                       partition_family=args.partition_family,
                       vertex_cut=args.vertex_cut,
                       batching="full_graph" if vc else "node_wise",
                       batch_size=args.batch_size,
                       fanouts=tuple(int(x) for x in args.fanouts.split(",")),
                       cache_policy="none" if vc else args.cache,
                       cache_capacity=0 if vc else args.cache_capacity)
    n_dev = len(jax.devices())
    k = args.parts or n_dev
    assert k <= n_dev, f"need {k} devices, have {n_dev} (set XLA_FLAGS)"
    mesh = make_mesh((k,), ("w",))
    return DistGNNEngine(g, mesh=mesh, cfg=cfg)


def run_sweep(eng, params, *, oracle_check=False):
    """Throughput tier: timed layer-wise full-graph sweep."""
    t0 = time.perf_counter()
    H = eng.infer_full_graph(params=params)
    wall = time.perf_counter() - t0
    emb = eng.global_embeddings(H)
    bytes_model = eng.inference_bytes_per_sweep()
    log.info("layer-wise sweep: %d vertices -> [%d, %d] embeddings in %.3fs "
             "(%.3f MB/sweep on the wire, CommStats.inference_bytes=%.3f MB)",
             eng.g.num_vertices, emb.shape[0], emb.shape[1], wall,
             bytes_model / 1e6, eng.comm_stats.inference_bytes / 1e6)
    if oracle_check:
        ref = eng.global_embeddings(eng.infer_full_graph(params=params,
                                                         reference=True))
        err = float(np.max(np.abs(emb - ref)))
        log.info("sweep oracle gap (max |dist - ref|) = %.2e", err)
        assert err <= 1e-4, f"sweep diverged from reference: {err}"
    return emb, wall


def run_query_stream(qe, *, num_queries, targets_per_query, seed=0):
    """Latency tier: a stream of K-target queries through the query engine
    (each flush answers one request here; coalescing is exercised by the
    serving test tier)."""
    rng = np.random.default_rng(seed)
    V = qe.engine.g.num_vertices
    qe.query(rng.choice(V, size=targets_per_query, replace=False))  # warmup
    qe.stats.latencies_s.clear()
    qe.stats.queries = 0
    for _ in range(num_queries):
        qe.query(rng.choice(V, size=targets_per_query, replace=False))
    s = qe.stats
    log.info("query stream: %d queries x %d targets -> %.1f qps, "
             "p50=%.2fms p99=%.2fms (%d serve rounds, %d compiles)",
             num_queries, targets_per_query, s.qps(),
             s.percentile_ms(50), s.percentile_ms(99), s.rounds,
             qe.num_compiles())
    assert qe.num_compiles() == 1, "serve step recompiled"
    return s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exec", default="p2p", choices=list(EXECUTION_MODELS))
    ap.add_argument("--model", default="gcn", choices=list(GNN_MODELS))
    ap.add_argument("--partition-family", default="edge_cut",
                    choices=["edge_cut", "vertex_cut"])
    ap.add_argument("--vertex-cut", default="cartesian2d",
                    choices=["random", "cartesian2d", "libra"])
    ap.add_argument("--batch-size", type=int, default=16,
                    help="per-device query-round target cap")
    ap.add_argument("--fanouts", default="4,4")
    ap.add_argument("--cache", default="static_degree",
                    help="serving hot-set policy (engine cache policies)")
    ap.add_argument("--cache-capacity", type=int, default=32)
    ap.add_argument("--parts", type=int, default=0, help="0 = all devices")
    ap.add_argument("--vertices", type=int, default=512)
    ap.add_argument("--train-steps", type=int, default=10,
                    help="mini-batch steps to get non-trivial params")
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--targets-per-query", type=int, default=8)
    ap.add_argument("--oracle-check", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    g = sbm_graph(args.vertices, num_blocks=8, p_in=0.05, p_out=0.003, seed=0)
    eng = build_engine(args, g)
    log.info("engine: model=%s exec=%s family=%s k=%d (nb=%d, caps=%s)",
             args.model, args.exec, args.partition_family, eng.k, eng.nb,
             getattr(eng, "caps", "-"))
    if eng.cfg.batching == "node_wise":
        state, losses, _ = eng.run_epoch_minibatch(args.train_steps)
        params = state["params"]
    else:  # vertex_cut: full-graph steps (sweep tier only)
        step = eng.make_step()
        state = eng.init_state()
        losses = []
        for _ in range(args.train_steps):
            state, metrics, _ = step(state)
            losses.append(float(metrics["loss"]))
        params = state["params"]
    log.info("trained %d steps: loss %.4f -> %.4f",
             args.train_steps, losses[0], losses[-1])

    run_sweep(eng, params, oracle_check=args.oracle_check)
    if eng.cfg.batching == "node_wise":
        qe = GNNQueryEngine(eng, params)
        run_query_stream(qe, num_queries=args.queries,
                         targets_per_query=args.targets_per_query)
    else:
        log.info("query tier skipped: vertex_cut mini-batch sampling is a "
                 "ROADMAP follow-up (latency tier is edge-cut)")


if __name__ == "__main__":
    main()
