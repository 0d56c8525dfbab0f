"""End-to-end distributed GNN training driver (the paper's workload).

Two modes:

* ``--engine`` (default): the DistGNNEngine — edge-cut partition plan +
  Pallas-ELL local multiply + selectable exchange execution model
  (broadcast | ring | p2p halo exchange) + sync/async-historical protocol,
  all inside ONE jitted shard_map train step.  Reports loss/accuracy, the
  collective bytes of the chosen model, and the oracle gap vs the
  single-device reference.  ``--batching node_wise|layer_wise|subgraph``
  switches to sampled mini-batches (survey §5): per-device targets from the
  owned partition block, statically padded sampled blocks, a device-resident
  feature cache (``--cache`` / ``--cache-capacity``), and the §6.1 stage
  schedules (``--schedule``); reports feature-fetch bytes + cache hits.
  With ``--schedule pipelined``, ``--prefetch-mode process`` moves the
  sampler into a GIL-free pool of ``--num-sample-workers`` worker processes
  over a shared-memory batch ring (bitwise-identical epochs, survey §6.1):
  process mode pays a one-time pool start-up, then wins whenever the
  thread sampler would fight XLA's dispatch for the GIL (no spare core) or
  epochs repeat — deterministic batches are served from the pool's LRU
  without resampling.  Thread mode remains the zero-setup default.
  ``--partition-family vertex_cut --vertex-cut random|cartesian2d|libra``
  switches the §4 partition family: edges are partitioned, vertices
  replicate, and the exchange becomes the replica-sync combine (partial
  aggregations over owned edges, master-masked loss); reports the
  replication factor and replica-sync bytes.
  ``--partition-family hybrid`` is the PowerLyra-style degree-threshold
  cut: low-degree vertices stay edge-cut-local behind the halo exchange
  while hubs (in-degree >= ``--hub-threshold``, default auto p95)
  replicate through the replica-sync combine; reports the threshold, hub
  count, and both wire legs.
* ``--no-engine``: the legacy dense-block SpMM execution models (survey
  Table 2) over a device mesh, kept as the survey-taxonomy reference.

Run with forced host devices to see real collectives on CPU:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/train_gnn_distributed.py --exec p2p --protocol epoch_adaptive

Reading a trace (``--trace-out DIR``, engine path):

Pass ``--trace-out DIR`` to record run-wide telemetry and run the engine
under ``jax.profiler.trace(DIR)`` — open the trace in TensorBoard's profile
plugin or Perfetto (https://ui.perfetto.dev).  What you see:

* the engine's spans on the host's thread lines, on the device ops' clock:
  the layout build (``layout.partition`` / ``.vertex_blocks`` / ``.store``
  / ``.exchange_plan``), ``step.place_consts``, then one ``train`` per
  step; with ``--schedule pipelined`` the prefetch thread's
  ``sample``/``extract`` spans overlap the trainer's ``train`` spans — the
  §6.1 overlap is directly visible as stacked rows;
* per-device ``sample_device`` child spans under each ``sample`` span, so a
  straggler partition shows up as one long bar (the workload-imbalance
  challenge, survey §2);
* each device op named by its scope inside the jitted step: ``layer<l>/
  aggregate`` (the ``gather_sum`` gather loop; its backward under
  ``transpose(...)``), ``layer<l>/combine``, ``loss``, ``grad_sync``,
  ``sgd``, and ``exchange`` around every collective;
* click any span: its arguments hold the step / device labels.

A step log (one JSON line per step: loss, cumulative comm bytes) is written
to ``DIR/steps.jsonl``, and a run summary —
per-stage seconds, per-device imbalance ratios (max/mean), metric totals,
and the compiled step's static collective bytes + peak memory from
``hlo_analysis.executable_summary`` — prints at exit.  Telemetry is
off-by-default and adds <5% overhead when on (asserted by
``benchmarks/bench_gnn.py --telemetry``).
"""
import argparse
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.configs import gcn_paper
from repro.core.engine import (
    BATCHING_MODES,
    ENGINE_CACHE_POLICIES,
    EXECUTION_MODELS,
    GNN_MODELS,
    PROTOCOLS,
    DistGNNEngine,
    EngineConfig,
)
from repro.core.execution.spmm_models import SPMM_MODELS
from repro.core.graph import sbm_graph
from repro.core.models.gnn import accuracy, full_graph_forward, init_gnn_params, softmax_xent
from repro.core.partition import PARTITIONERS
from repro.core.telemetry import Telemetry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import collective_bytes, executable_summary


def run_engine(args, g):
    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    layer_sizes = tuple(int(x) for x in args.layer_sizes.split(","))
    fields = dict(execution=args.exec, protocol=args.protocol,
                  partition_family=args.partition_family,
                  vertex_cut=args.vertex_cut,
                  hub_threshold=args.hub_threshold,
                  batching=args.batching, batch_size=args.batch_size,
                  fanouts=fanouts, layer_sizes=layer_sizes,
                  walk_length=args.walk_length,
                  cache_policy=args.cache,
                  cache_capacity=args.cache_capacity,
                  exchange_chunks=args.exchange_chunks,
                  p2p_buckets=args.p2p_buckets,
                  prefetch_depth=args.prefetch_depth,
                  prefetch_mode=args.prefetch_mode,
                  num_sample_workers=args.num_sample_workers,
                  trainable_features=args.trainable_features,
                  embed_lr=args.embed_lr)
    given = dict(model=args.model, partitioner=args.partition, lr=args.lr)
    fields.update({k: v for k, v in given.items() if v is not None})
    if args.config:  # widths, depth, model, partitioner, lr of the config
        cfg = gcn_paper.engine_config(gcn_paper.CONFIG, **fields)
    else:
        cfg = EngineConfig(**fields)
    n_dev = len(jax.devices())
    k = args.parts or n_dev
    assert k <= n_dev, f"need {k} devices, have {n_dev} (set XLA_FLAGS)"
    mesh = make_mesh((k,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=cfg,
                        telemetry=Telemetry() if args.trace_out else None)
    tel = eng.telemetry
    minibatch = args.batching != "full_graph"
    lowered = eng.lower_minibatch_step() if minibatch else eng.lower_step()
    compiled = lowered.compile()
    coll, kinds = collective_bytes(compiled.as_text())
    tel.attach_executable("minibatch_train_step" if minibatch else
                          "train_step", executable_summary(compiled))
    if args.partition_family == "vertex_cut":
        cut = (f"vertex_cut={args.vertex_cut} "
               f"(replication={eng.layout.replication_factor():.2f}, "
               f"nv={eng.nv})")
    elif args.partition_family == "hybrid":
        lay = eng.playout
        cut = (f"hybrid thr={lay.cut.threshold:g} "
               f"({int(lay.cut.hub.sum())} hubs, "
               f"replication={lay.layout.replication_factor():.2f})")
    else:
        cut = f"partition={cfg.partitioner}"
    print(f"engine: model={cfg.model} exec={args.exec} "
          f"protocol={args.protocol} "
          f"batching={args.batching} dims={eng.dims} {cut} k={k} "
          f"(nb={eng.nb}, halo cap={getattr(eng, 'cap', '-')}"
          + (f", frontier caps={eng.caps} fcap={eng.fcap}" if minibatch else "")
          + f") collective bytes/step = {coll / 1e6:.2f} MB  {kinds}")
    if minibatch:
        state, losses, times = eng.run_epoch_minibatch(
            args.epochs, schedule=args.schedule)
        eng.close_prefetch_pool()  # no-op unless --prefetch-mode process ran
        s = eng.comm_stats
        print(f"schedule={args.schedule}: wall={times.wall:.3f}s "
              f"(sample={times.sample:.3f} extract={times.extract:.3f} "
              f"train={times.train:.3f})")
        print(f"feature fetch: {s.pull_bytes / 1e6:.3f} MB pulled, "
              f"{s.cache_hit_bytes / 1e6:.3f} MB served by the "
              f"{args.cache!r} cache "
              f"({s.cache_hit_bytes / max(s.requested(), 1):.1%} hit bytes)")
        if args.trainable_features:
            print(f"trainable embeddings: {s.embed_grad_bytes / 1e6:.3f} MB "
                  f"gradient rows routed to owners (+ overlay refresh) over "
                  f"{args.epochs} steps")
        batch = eng.sample_minibatch(args.epochs - 1)
        _, _, logits = eng.make_minibatch_step()(state, batch)
        acc = eng.minibatch_accuracy(logits, batch)
        for e in range(0, args.epochs, max(args.epochs // 4, 1)):
            print(f"epoch {e:3d} loss {losses[e]:.4f}")
        print(f"final: batch train_acc={acc:.3f}")
    else:
        losses, logits = eng.train(args.epochs)
        for e in range(0, args.epochs, max(args.epochs // 4, 1)):
            print(f"epoch {e:3d} loss {losses[e]:.4f}")
        if args.partition_family == "vertex_cut":
            s = eng.comm_stats
            print(f"replica sync: {s.replica_sync_bytes / 1e6:.3f} MB over "
                  f"{args.epochs} steps ({args.exec} combine)")
        elif args.partition_family == "hybrid":
            s = eng.comm_stats
            print(f"hybrid wire: {s.halo_bytes / 1e6:.3f} MB halo (low-degree"
                  f" srcs) + {s.replica_sync_bytes / 1e6:.3f} MB replica sync"
                  f" (hubs) over {args.epochs} steps ({args.exec})")
        if args.trainable_features:
            print(f"trainable embeddings: "
                  f"{eng.comm_stats.embed_grad_bytes / 1e6:.3f} MB gradient "
                  f"rows routed to owners over {args.epochs} steps")
        print(f"final: train_acc={eng.accuracy(logits, 'train'):.3f} "
              f"test_acc={eng.accuracy(logits, 'test'):.3f}")
    if args.oracle_check:
        ref_losses, _ = eng.train(args.epochs, reference=True)
        gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
        print(f"oracle gap (max |loss_dist - loss_ref|) = {gap:.2e}")
    if args.infer:
        if minibatch:
            infer_state = state
        else:  # train() keeps its state internal: replay the same stream
            step = eng.make_step()
            infer_state = eng.init_state()
            for _ in range(args.epochs):
                infer_state, _, _ = step(infer_state)
        emb = eng.global_embeddings(eng.infer_full_graph(infer_state))
        ref = eng.global_embeddings(
            eng.infer_full_graph(infer_state, reference=True))
        err = float(np.max(np.abs(emb - ref)))
        print(f"layer-wise inference sweep: embeddings {emb.shape}, "
              f"{eng.inference_bytes_per_sweep() / 1e6:.3f} MB/sweep "
              f"({eng.comm_stats.inference_bytes / 1e6:.3f} MB accounted), "
              f"oracle gap {err:.2e}")
    if args.trace_out:
        os.makedirs(args.trace_out, exist_ok=True)
        step_log = os.path.join(args.trace_out, "steps.jsonl")
        tel.write_step_log(step_log)
        summary = tel.run_summary()
        secs = summary["spans"]["seconds_by_name"]
        print("telemetry: "
              + " ".join(f"{n}={s:.3f}s" for n, s in sorted(secs.items())))
        for name, rec in sorted(summary["imbalance"]["metrics"].items()):
            print(f"  imbalance {name}: max/mean={rec['max_over_mean']:.2f}")
        print(f"  trace -> {args.trace_out} "
              f"({summary['spans']['count']} spans), "
              f"step log -> {step_log}")


def run_legacy(args, g):
    n_dev = len(jax.devices())
    k = args.parts or n_dev
    assert k <= n_dev, f"need {k} devices, have {n_dev} (set XLA_FLAGS)"

    # partition + relabel so device row-blocks align with partitions
    part = PARTITIONERS[args.partition](g, k)
    order = np.argsort(part.assignment, kind="stable")
    A = jnp.asarray(g.to_dense_adj()[np.ix_(order, order)])
    X = jnp.asarray(g.features[order])
    y = jnp.asarray(g.labels[order].astype(np.int32))
    train_m = jnp.asarray(g.train_mask[order].astype(np.float32))
    test_m = jnp.asarray(g.test_mask[order].astype(np.float32))

    if args.exec in ("spmm_2d", "spmm_15d"):
        r = int(np.sqrt(k))
        while k % r:
            r -= 1
        mesh = make_mesh((r, k // r), ("r", "c"))
    else:
        mesh = make_mesh((k,), ("w",))
    spmm = SPMM_MODELS[args.exec]

    def aggregate(A_, H_):
        return spmm(mesh, A_, H_)

    dims = [g.features.shape[1], 32, int(g.labels.max()) + 1]
    params = init_gnn_params("gcn", dims, jax.random.PRNGKey(0))

    def loss_fn(p):
        logits = full_graph_forward("gcn", p, A, X, aggregate=aggregate)
        return softmax_xent(logits, y, train_m), logits

    @jax.jit
    def step(p):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        p = jax.tree_util.tree_map(lambda a, g_: a - 0.5 * g_, p, grads)
        return p, loss, logits

    comp = step.lower(params).compile()
    coll, kinds = collective_bytes(comp.as_text())
    print(f"execution model {args.exec} on {mesh.devices.shape} mesh: "
          f"collective bytes/step = {coll / 1e6:.2f} MB  {kinds}")

    logits = None
    for e in range(args.epochs):
        params, loss, logits = step(params)
        if e % 10 == 0:
            print(f"epoch {e:3d} loss {float(loss):.4f}")
    print(f"final: train_acc={float(accuracy(logits, y, train_m)):.3f} "
          f"test_acc={float(accuracy(logits, y, test_m)):.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="use the DistGNNEngine (ELL + halo exchange); "
                    "--no-engine runs the legacy dense-block SpMM models")
    ap.add_argument("--exec", default=None,
                    help=f"engine: {EXECUTION_MODELS} (default p2p); "
                    f"legacy: {list(SPMM_MODELS)} (default spmm_1d)")
    ap.add_argument("--protocol", default="sync", choices=list(PROTOCOLS))
    ap.add_argument("--config", default=None, choices=["gcn-paper"],
                    help="engine: take vertices, average degree, feature "
                    "and hidden widths, classes, layers, model, "
                    "partitioner and lr from configs/gcn_paper.py (graph "
                    "from er_graph); --model/--partition/--lr override")
    ap.add_argument("--model", default=None, choices=list(GNN_MODELS),
                    help="engine GNN layer program (default gcn; §3 model "
                    "axis): gcn | "
                    "sage | gat | gin — gat runs distributed edge-wise "
                    "attention (SDDMM logits + masked segment-softmax; "
                    "two-pass replica sync under vertex_cut)")
    ap.add_argument("--batching", default="full_graph",
                    choices=list(BATCHING_MODES),
                    help="engine §5 batch generation: full_graph partition "
                    "batches or sampled mini-batches")
    ap.add_argument("--batch-size", type=int, default=16,
                    help="per-device mini-batch targets / walk roots")
    ap.add_argument("--fanouts", default="4,4",
                    help="node_wise: comma-separated per-layer fanouts")
    ap.add_argument("--layer-sizes", default="32,32",
                    help="layer_wise: comma-separated per-layer sample sizes")
    ap.add_argument("--walk-length", type=int, default=4,
                    help="subgraph: random-walk length")
    ap.add_argument("--cache", default="none",
                    choices=list(ENGINE_CACHE_POLICIES),
                    help="device-resident feature cache policy")
    ap.add_argument("--cache-capacity", type=int, default=0,
                    help="remote feature rows cached per device")
    ap.add_argument("--schedule", default="conventional",
                    choices=["conventional", "factored", "operator_parallel",
                             "pipelined"],
                    help="mini-batch stage schedule (survey §6.1); "
                    "'pipelined' runs the REAL double-buffered sampler "
                    "(prefetch thread + async step dispatch)")
    ap.add_argument("--trainable-features",
                    action=argparse.BooleanOptionalAction, default=False,
                    help="layer-0 rows are learnable embedding-store rows "
                    "updated by row-sparse AdamW (requires protocol=sync)")
    ap.add_argument("--embed-lr", type=float, default=0.1,
                    help="sparse-AdamW learning rate for the embedding rows")
    ap.add_argument("--prefetch-mode", default="thread",
                    choices=["thread", "process"],
                    help="pipelined schedule's producer: 'thread' shares "
                    "the trainer's GIL (wins only with a spare core); "
                    "'process' runs sampling in a GIL-free worker-process "
                    "pool over a shared-memory batch ring — pays a "
                    "process-start + pickle cost up front, wins whenever "
                    "host sampling competes with XLA for the GIL or "
                    "epochs repeat (deterministic batches are LRU-cached)")
    ap.add_argument("--num-sample-workers", type=int, default=2,
                    help="worker processes for --prefetch-mode process")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="pipelined schedule: batches sampled ahead of the "
                    "device step (bounded queue depth)")
    ap.add_argument("--exchange-chunks", type=int, default=1,
                    help="feature-dim chunks overlapping the broadcast/p2p "
                    "collectives with the ELL multiply (1 = monolithic)")
    ap.add_argument("--p2p-buckets", type=int, default=1,
                    help="power-of-two installments splitting the p2p "
                    "all_to_all send caps (smaller lowered buffers)")
    ap.add_argument("--parts", type=int, default=0, help="0 = all devices")
    ap.add_argument("--partition", default=None,
                    help="edge-cut partitioner (default metis_like, or the "
                    "config's)")
    ap.add_argument("--partition-family", default="edge_cut",
                    choices=["edge_cut", "vertex_cut", "hybrid"],
                    help="engine §4 partition family: edge-cut halo exchange, "
                    "vertex-cut replica sync (replicated vertices, "
                    "master-masked loss), or the PowerLyra-style hybrid "
                    "degree-threshold cut (hubs replicate, the rest stay "
                    "edge-cut-local)")
    ap.add_argument("--vertex-cut", default="cartesian2d",
                    choices=["random", "cartesian2d", "libra"],
                    help="vertex-cut partitioner (with "
                    "--partition-family vertex_cut)")
    ap.add_argument("--hub-threshold", type=float, default=None,
                    help="hybrid: in-degree at/above which a vertex is a "
                    "replicated hub (default: auto 95th percentile; inf -> "
                    "pure edge-cut dataflow, 0 -> pure vertex-cut)")
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--vertices", type=int, default=512)
    ap.add_argument("--lr", type=float, default=None,
                    help="SGD step (default 0.5, or the config's)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="engine: enable run-wide telemetry and write a JAX "
                    "profiler trace here (see the module docstring for how "
                    "to read it) plus a steps.jsonl step log; prints "
                    "per-stage seconds and per-device imbalance ratios")
    ap.add_argument("--oracle-check", action="store_true",
                    help="engine: also run the single-device reference and "
                    "report the max loss gap")
    ap.add_argument("--infer", action="store_true",
                    help="engine: after training, run the layer-wise "
                    "full-graph inference sweep (embeddings for every "
                    "vertex in O(L) exchanges) and report its oracle gap; "
                    "K-target query serving lives in "
                    "`python -m repro.launch.serve_gnn`")
    args = ap.parse_args()

    if args.exec is None:
        args.exec = "p2p" if args.engine else "spmm_1d"
    elif args.exec not in set(EXECUTION_MODELS) | set(SPMM_MODELS):
        ap.error(f"--exec must be one of {EXECUTION_MODELS} (engine) or "
                 f"{list(SPMM_MODELS)} (legacy), got {args.exec!r}")
    if args.engine and args.exec in SPMM_MODELS:
        args.engine = False  # legacy exec name given: run the legacy path
    if not args.engine and args.exec not in SPMM_MODELS:
        ap.error(f"--no-engine requires a legacy exec name {list(SPMM_MODELS)}, "
                 f"got {args.exec!r}")
    if args.batching != "full_graph" and not args.engine:
        ap.error("mini-batch --batching modes run on the engine path only")
    if args.trace_out and not args.engine:
        ap.error("--trace-out instruments the engine path only")
    if args.partition_family != "edge_cut":
        if not args.engine:
            ap.error(f"--partition-family {args.partition_family} runs on "
                     "the engine path only")
        if args.batching != "full_graph":
            ap.error(f"{args.partition_family} supports --batching "
                     "full_graph only (replica-family mini-batch sampling "
                     "is a ROADMAP follow-up)")
    if args.config and not args.engine:
        ap.error("--config runs on the engine path only")
    if not args.engine:
        args.partition = args.partition or "metis_like"
    enable_compile_cache()
    if args.config:
        wl = gcn_paper.CONFIG
        g = gcn_paper.build_graph(wl)
        print(f"graph: {gcn_paper.GRAPH_GENERATOR}(V={wl.num_vertices}, "
              f"avg_degree={wl.avg_degree}) E={g.num_edges} "
              f"max in-degree={int(g.degree().max())} "
              f"features={wl.feature_dim} classes={wl.num_classes}")
    else:
        g = sbm_graph(args.vertices, num_blocks=8, p_in=0.05, p_out=0.003,
                      seed=0)
    if args.engine:
        with (jax.profiler.trace(args.trace_out) if args.trace_out
              else contextlib.nullcontext()):
            run_engine(args, g)
    else:
        run_legacy(args, g)


if __name__ == "__main__":
    main()
