import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Same contract as dryrun.py: placeholder devices before any other import.

"""Production-scale dry-run of the PAPER'S OWN workload: full-graph GCN
training (1M vertices, ELLPACK adjacency) on the 256-chip single-pod mesh
and the 512-chip multi-pod mesh.

Vertices (and their features/ELL rows) are sharded over every chip; the
neighbor aggregation H[ids] gather under GSPMD lowers to the broadcast-style
embedding exchange of the survey's §7.1.1 (all-gather of the row-sharded H) —
the paper-faithful 1D execution model at production scale. Records the same
memory/cost/collective artifacts as the transformer dry-run.

  PYTHONPATH=src python -m repro.launch.dryrun_gnn [--multi-pod]
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.configs.gcn_paper import CONFIG as GNN_CFG
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.utils import get_logger, human_bytes

log = get_logger("repro.dryrun_gnn")


def gcn_train_step_fn(cfg):
    """ELL full-graph GCN train step: params pytree, graph (ids, mask), X, y."""

    def loss_fn(params, ids, mask, X, y, train_w):
        H = X
        L = len(params["w"])
        for l in range(L):
            gathered = jnp.take(H, ids, axis=0)  # [V, K, D] — the §7.1 exchange
            agg = (mask[..., None] * gathered).sum(1)
            deg = jnp.maximum(mask.sum(1, keepdims=True), 1.0)
            H = (agg / deg + H) @ params["w"][l] + params["b"][l]
            if l < L - 1:
                H = jax.nn.relu(H)
        lse = jax.scipy.special.logsumexp(H, axis=-1)
        ll = jnp.take_along_axis(H, y[:, None], axis=-1)[:, 0]
        return ((lse - ll) * train_w).sum() / jnp.maximum(train_w.sum(), 1.0)

    def step(params, ids, mask, X, y, train_w):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, mask, X, y, train_w)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
        return params, loss

    return step


def gcn_p2p_step_fn(cfg, mesh, cap: int):
    """Selective-P2P full-graph GCN step (survey §7.1.2 at production scale):
    instead of all-gathering H, each device ships only `cap` boundary rows per
    destination (the plan arrays are ShapeDtypeStruct inputs — a real
    deployment builds them from the partitioner's boundary sets; `cap` is set
    from the measured edge-cut fraction). Aggregation looks rows up in
    concat(local H, received rows) via a pre-remapped ELL table."""
    axes = mesh.axis_names
    n_dev = int(np.prod(mesh.devices.shape))

    def loss_fn(params, ids_local, mask, X, y, train_w, send_plan):
        # all leaves arrive device-local under shard_map
        H = X
        L = len(params["w"])
        for l in range(L):
            send = jnp.take(H, send_plan[0], axis=0)  # [n_dev, cap, D]
            recv = jax.lax.all_to_all(send, axes, split_axis=0, concat_axis=0)
            table = jnp.concatenate([H, recv.reshape(-1, H.shape[1])], axis=0)
            gathered = jnp.take(table, ids_local, axis=0)  # [V_l, K, D]
            agg = (mask[..., None] * gathered).sum(1)
            deg = jnp.maximum(mask.sum(1, keepdims=True), 1.0)
            Hn = (agg / deg + H) @ params["w"][l] + params["b"][l]
            H = jax.nn.relu(Hn) if l < L - 1 else Hn
        lse = jax.scipy.special.logsumexp(H, axis=-1)
        ll = jnp.take_along_axis(H, y[:, None], axis=-1)[:, 0]
        loss = ((lse - ll) * train_w).sum()
        return jax.lax.psum(loss, axes) / jnp.maximum(
            jax.lax.psum(train_w.sum(), axes), 1.0)

    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    row = P(axes)
    rep = P()

    def step(params, ids_local, mask, X, y, train_w, send_plan):
        def lf(p):
            return loss_fn(p, ids_local, mask, X, y, train_w, send_plan)

        loss, grads = jax.value_and_grad(lf)(params)
        grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, axes), grads)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
        return params, loss

    return shard_map(
        step, mesh=mesh,
        in_specs=({"w": [rep, rep, rep], "b": [rep, rep, rep]},
                  row, row, row, row, row, P(axes, None, None)),
        out_specs=({"w": [rep, rep, rep], "b": [rep, rep, rep]}, rep),
        check_vma=False)


def _partition_families_entry(g, gname, chips, dims):
    """One BENCH_partition_families config row: edge-cut (metis_like / hash)
    vs vertex-cut (random / cartesian2d / libra) vs the hybrid
    degree-threshold sweep ({p90, p95, p99, inf} over metis_like masters),
    total + bottleneck bytes from the standalone cost models."""
    from repro.core.engine import EngineConfig
    from repro.core.partition.cost_models import (
        edge_cut_halo_bytes_per_step,
        edge_cut_halo_device_bytes,
        hybrid_bytes_per_step,
        replica_sync_bytes_per_step,
        replica_sync_device_bytes,
    )
    from repro.core.partition.edge_cut import PARTITIONERS
    from repro.core.partition.hybrid_cut import HybridLayout
    from repro.core.partition.vertex_cut import VERTEX_CUTS
    from repro.core.partition.vertex_layout import build_vertex_layout

    deg = g.degree().astype(np.float64)
    thresholds = dict(p90=float(np.percentile(deg, 90)),
                      p95=float(np.percentile(deg, 95)),
                      p99=float(np.percentile(deg, 99)), inf=np.inf)
    entry = dict(graph=gname, chips=chips, vertices=g.num_vertices,
                 edge_cut={}, vertex_cut={}, hybrid={})
    for pname in ("metis_like", "hash"):
        part = PARTITIONERS[pname](g, chips)
        dev = edge_cut_halo_device_bytes(g, part, dims)
        entry["edge_cut"][pname] = dict(
            total_bytes=edge_cut_halo_bytes_per_step(g, part, dims),
            bottleneck_bytes=int(dev.max()),
            vertex_balance=part.vertex_balance())
    for vname in VERTEX_CUTS:
        vc = VERTEX_CUTS[vname](g, chips)
        lay = build_vertex_layout(g, vc, chips)
        dev = replica_sync_device_bytes(lay, vc.masters, dims)
        entry["vertex_cut"][vname] = dict(
            replication_factor=lay.replication_factor(),
            total_bytes=replica_sync_bytes_per_step(
                lay.rep_count, chips, lay.nv, "p2p", dims),
            bottleneck_bytes=int(dev.max()))
    for tname, thr in thresholds.items():
        lay = HybridLayout(g, chips, EngineConfig(
            partition_family="hybrid", hub_threshold=thr, execution="p2p"))
        dev = lay.device_bytes_per_step("gcn", dims)
        entry["hybrid"][tname] = dict(
            threshold=thr, num_hubs=int(lay.cut.hub.sum()),
            total_bytes=hybrid_bytes_per_step(
                lay.halo_rows_exec if lay.halo_active else 0,
                lay._vc_rows_per_layer if lay.sync_active else 0, dims),
            bottleneck_bytes=int(dev.max()))
    # built-in cross-check: threshold=inf IS the edge-cut dataflow over the
    # same metis_like masters, so the two accountings must agree
    assert (entry["hybrid"]["inf"]["bottleneck_bytes"]
            == entry["edge_cut"]["metis_like"]["bottleneck_bytes"]), entry
    ec = min(v["bottleneck_bytes"] for v in entry["edge_cut"].values())
    vc = min(v["bottleneck_bytes"] for v in entry["vertex_cut"].values())
    hy = min(v["bottleneck_bytes"] for v in entry["hybrid"].values())
    entry["best_edge_cut_bottleneck"] = ec
    entry["best_vertex_cut_bottleneck"] = vc
    entry["best_hybrid_bottleneck"] = hy
    entry["vertex_cut_wins_bottleneck"] = vc < ec
    entry["hybrid_wins_bottleneck"] = hy <= min(ec, vc)
    log.info("%s V=%d %d chips: bottleneck edge-cut %s vs vertex-cut %s vs "
             "hybrid %s (%s)", gname, g.num_vertices, chips,
             human_bytes(ec), human_bytes(vc), human_bytes(hy),
             "hybrid wins" if hy <= min(ec, vc)
             else ("vertex-cut wins" if vc < ec else "edge-cut wins"))
    return entry


def bench_partition_families(out_dir, dims, vertices=2048):
    """Emit BENCH_partition_families.json: per-step comm bytes of the §4
    partition families — edge-cut halo exchange (metis_like / hash),
    vertex-cut replica sync (random / cartesian2d / libra, p2p GAS
    accounting), and the PowerLyra-style hybrid degree-threshold cut (a
    threshold sweep over {p90, p95, p99, inf}) — across {uniform,
    power-law} graphs at {8, 64, 256} chips, plus one double-size power-law
    point at 256 chips.

    Two metrics per config, both from the standalone cost models the engine's
    CommStats are cross-checked against:

      total_bytes       every row that crosses the wire per step.  Edge-cut
                        wins this everywhere: with receiver-side dedup the
                        halo ships each (vertex, consumer) pair once, while
                        GAS replica sync pays gather AND scatter — a
                        structural ~2x.  Reported honestly.
      bottleneck_bytes  max per-device (send+recv) bytes — the straggler
                        that sets the step time at scale.  On skewed
                        power-law graphs a hub's OWNER must ship its rows to
                        up to k-1 consumers; how to beat that depends on the
                        V/chips ratio, and the two assertions below pin one
                        regime each.

    At V/chips = 8 (the base grid's 256-chip power-law point) nearly every
    edge is remote for every vertex, so per-device degree concentration is
    diluted and what wins is bounding + load-balancing ALL traffic by the
    replication factor: the best vertex-cut must beat the best edge-cut (the
    PR-3 finding, still asserted).  At V/chips = 16 (the double-size
    power-law point) the straggler is the hub fan-in itself, and the hybrid
    cut peels exactly that: low-degree vertices keep edge-cut's dedup'd halo
    while only the hubs pay the replication tax — the best hybrid threshold
    must beat BOTH pure families (the ISSUE-10 assertion).  Built-in
    cross-check everywhere: hybrid@inf == edge_cut/metis_like exactly.  On
    the uniform graph there is no hub tail to peel, so hybrid degenerates to
    its edge-cut anchor and the hash partitioner's balance keeps edge-cut
    ahead — reported honestly, not asserted.
    """
    from repro.core.graph import er_graph, powerlaw_graph

    V = min(vertices, 2048)
    result = dict(vertices=V, avg_degree=16, dims=dims, configs=[])
    for gname, gfn in (("uniform", er_graph), ("power_law", powerlaw_graph)):
        g = gfn(V, avg_degree=16, seed=0)
        for chips in (8, 64, 256):
            result["configs"].append(
                _partition_families_entry(g, gname, chips, dims))
    # the hybrid regime point: double the vertices at max chips
    g2 = powerlaw_graph(2 * V, avg_degree=16, seed=0)
    hyb = _partition_families_entry(g2, "power_law", 256, dims)
    result["configs"].append(hyb)
    # write the artifact BEFORE asserting: a failed claim should leave the
    # per-config byte breakdown behind for diagnosis
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_partition_families.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=float)
    log.info("OK partition-families bench -> %s", path)
    plaw = [e for e in result["configs"]
            if e["graph"] == "power_law" and e["chips"] == 256
            and e["vertices"] == V][0]
    assert plaw["vertex_cut_wins_bottleneck"], (
        "vertex-cut must beat edge-cut critical-path comm volume on the "
        f"power-law 256-chip config: {plaw}")
    assert hyb["hybrid_wins_bottleneck"], (
        "the best hybrid threshold must beat BOTH pure families' "
        "critical-path comm volume on the double-size power-law 256-chip "
        f"config: {hyb}")
    return path


def run_autotune(args):
    """`--autotune`: enumerate (family, cut, threshold, execution, chunks,
    buckets) plans over the synthetic engine graph with the engines' own
    cost models, choose the predicted-bytes argmin, validate the choice
    against a traced dryrun (2 real train steps on `--autotune-chips`
    forced-host devices; PlanRejected if measured comm.* counters or layout
    imbalance gauges drift past the bound), and write AUTOTUNE_gnn.json."""
    from repro.core.graph import er_graph, powerlaw_graph
    from repro.core.partition.autotune import autotune

    cfg = GNN_CFG
    k = args.autotune_chips
    gfn = powerlaw_graph if args.engine_graph == "powerlaw" else er_graph
    V = min(args.engine_vertices, 4096)
    g = gfn(V, avg_degree=cfg.avg_degree, feature_dim=cfg.feature_dim,
            num_classes=cfg.num_classes, seed=0)
    dims = ([cfg.feature_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1)
            + [cfg.num_classes])
    mesh = make_mesh((k,), ("w",))
    t0 = time.time()
    plan, report = autotune(g, k, dims, args.engine_model, mesh=mesh)
    val = report["validation"]
    log.info("autotune %s V=%d k=%d model=%s: chose %s of %d candidates — "
             "predicted %s/step (bottleneck %s/device), measured/predicted "
             "ratio %.4f over %d validation steps, %.1fs",
             args.engine_graph, V, k, args.engine_model, plan.label(),
             len(report["candidates"]), human_bytes(plan.predicted_step_bytes),
             human_bytes(plan.predicted_bottleneck_bytes), val["ratio"],
             val["steps"], time.time() - t0)
    for name, b in sorted(val["balance"].items()):
        log.info("  balance %s: claimed %.3f measured %.3f", name,
                 b["claimed"], b["measured"])
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "AUTOTUNE_gnn.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=float)
    log.info("OK autotune -> %s", path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--protocol", choices=["broadcast", "p2p", "engine"],
                    default="broadcast")
    ap.add_argument("--cut", type=float, default=0.1,
                    help="p2p: boundary fraction per destination pair")
    ap.add_argument("--engine-exec", default="p2p",
                    help="engine: broadcast | ring | p2p")
    ap.add_argument("--engine-model", default="gcn",
                    choices=["gcn", "sage", "gat", "gin"],
                    help="engine: §3 GNN model axis — gat lowers the "
                    "distributed attention step (SDDMM logits + segment-"
                    "softmax; two-pass replica sync under vertex_cut) and "
                    "its exchange ships transformed rows + the attention-"
                    "coefficient column")
    ap.add_argument("--engine-family", default="edge_cut",
                    choices=["edge_cut", "vertex_cut", "hybrid"],
                    help="engine: §4 partition family (vertex_cut lowers the "
                    "replica-sync step and reports replication factor vs "
                    "edge-cut halo bytes; hybrid is the PowerLyra-style "
                    "degree-threshold cut — low-degree halo exchange + hub "
                    "replica sync)")
    ap.add_argument("--hub-threshold", type=float, default=None,
                    help="engine hybrid: degree threshold above which a "
                    "vertex replicates vertex-cut style (default: auto, the "
                    "95th degree percentile; inf = pure edge-cut dataflow, "
                    "0 = pure src-replicating vertex-cut)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the cost-model partition/execution autotuner "
                    "on the synthetic engine graph: enumerate (family, cut, "
                    "threshold, execution, chunks, buckets) plans, choose "
                    "the predicted-bytes argmin, validate it against a "
                    "traced dryrun (PlanRejected past the drift bound), "
                    "print chosen plan + measured/predicted ratio, write "
                    "AUTOTUNE_gnn.json, and exit")
    ap.add_argument("--autotune-chips", type=int, default=8,
                    help="autotune: device count the plan is scored and "
                    "validated for (the validation dryrun trains 2 real "
                    "steps on this many forced-host devices)")
    ap.add_argument("--engine-vertex-cut", default="cartesian2d",
                    choices=["random", "cartesian2d", "libra"],
                    help="engine vertex_cut: which cut builds the layout")
    ap.add_argument("--engine-graph", default="er", choices=["er", "powerlaw"],
                    help="engine: synthetic graph family for the plan build")
    ap.add_argument("--engine-vertices", type=int, default=1 << 14,
                    help="engine: synthetic graph size (the partition plan is "
                    "built host-side from a concrete graph)")
    ap.add_argument("--engine-batching", default="full_graph",
                    help="engine: full_graph | node_wise | layer_wise | "
                    "subgraph — mini-batch modes lower the sampled-batch "
                    "step (static fanout caps + feature cache) instead")
    ap.add_argument("--engine-batch-size", type=int, default=1024,
                    help="engine mini-batch: per-device targets / walk roots")
    ap.add_argument("--engine-cache-capacity", type=int, default=4096,
                    help="engine mini-batch: cached remote feature rows "
                    "per device (static_degree policy)")
    ap.add_argument("--engine-exchange-chunks", type=int, default=1,
                    help="engine: feature-dim chunks for comm/compute "
                    "overlap in the exchange — chunk c+1's collective is "
                    "issued while chunk c feeds the ELL multiply; peak "
                    "gathered-table bytes drop ~chunks/2 x (asserted >= 2x "
                    "on the 256-chip broadcast lowering with >= 4 chunks)")
    ap.add_argument("--engine-trainable-features", action="store_true",
                    help="engine mode: layer-0 rows are learnable embedding "
                    "store rows (sparse-AdamW state enters the lowered step)")
    ap.add_argument("--engine-p2p-buckets", type=int, default=1,
                    help="engine: power-of-two installments splitting the "
                    "p2p all_to_all send caps; the lowered all_to_all "
                    "buffer shrinks ~buckets x (asserted >= 2x when the cap "
                    "actually splits)")
    ap.add_argument("--bench-partition-families", action="store_true",
                    help="emit BENCH_partition_families.json (edge-cut halo "
                    "vs vertex-cut replica-sync vs hybrid degree-threshold "
                    "sweep across graphs x chips) and exit")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = GNN_CFG
    if args.bench_partition_families:
        dims = ([cfg.feature_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1)
                + [cfg.num_classes])
        bench_partition_families(args.out, dims,
                                 vertices=args.engine_vertices)
        return
    if args.autotune:
        run_autotune(args)
        return
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    axes = mesh.axis_names  # rows shard over every mesh axis
    row_sh = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    V, K, D, C = cfg.num_vertices, cfg.avg_degree, cfg.feature_dim, cfg.num_classes
    dims = [D] + [cfg.hidden_dim] * (cfg.num_layers - 1) + [C]
    params = {
        "w": [jax.ShapeDtypeStruct((a, b), jnp.float32) for a, b in zip(dims[:-1], dims[1:])],
        "b": [jax.ShapeDtypeStruct((b,), jnp.float32) for b in dims[1:]],
    }
    specs = dict(
        ids=jax.ShapeDtypeStruct((V, K), jnp.int32),
        mask=jax.ShapeDtypeStruct((V, K), jnp.float32),
        X=jax.ShapeDtypeStruct((V, D), jnp.float32),
        y=jax.ShapeDtypeStruct((V,), jnp.int32),
        train_w=jax.ShapeDtypeStruct((V,), jnp.float32),
    )
    in_sh = ({"w": [rep] * (len(dims) - 1), "b": [rep] * (len(dims) - 1)},
             row_sh, row_sh, row_sh, row_sh, row_sh)
    t0 = time.time()
    if args.protocol == "engine":
        # The unified DistGNNEngine step (partition plan + Pallas-ELL local
        # multiply + halo exchange + protocol), lowered on a 1D mesh over all
        # production chips.  The plan needs a concrete graph, so this mode
        # dry-runs a smaller synthetic instance end to end rather than
        # abstract ShapeDtypeStructs.
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import er_graph, powerlaw_graph

        gfn = powerlaw_graph if args.engine_graph == "powerlaw" else er_graph
        g = gfn(args.engine_vertices, avg_degree=cfg.avg_degree,
                feature_dim=cfg.feature_dim,
                num_classes=cfg.num_classes, seed=0)
        mesh1d = make_mesh((chips,), ("w",))
        minibatch = args.engine_batching != "full_graph"
        ecfg = EngineConfig(
            execution=args.engine_exec, model=args.engine_model,
            hidden=cfg.hidden_dim,
            num_layers=cfg.num_layers, batching=args.engine_batching,
            partition_family=args.engine_family,
            vertex_cut=args.engine_vertex_cut,
            hub_threshold=args.hub_threshold,
            batch_size=args.engine_batch_size,
            fanouts=(4,) * cfg.num_layers,
            layer_sizes=(2 * args.engine_batch_size,) * cfg.num_layers,
            cache_policy="static_degree" if minibatch else "none",
            cache_capacity=args.engine_cache_capacity if minibatch else 0,
            exchange_chunks=args.engine_exchange_chunks,
            p2p_buckets=args.engine_p2p_buckets,
            trainable_features=args.engine_trainable_features)
        eng = DistGNNEngine(g, mesh=mesh1d, cfg=ecfg)
        # run-summary exporter (ISSUE 8): the ad-hoc byte logs below stay for
        # humans; the artifact carries the structured telemetry summary —
        # static per-device layout gauges + the imbalance report + the
        # compiled executable's collective/peak-memory facts
        tel = eng.enable_telemetry()
        if minibatch and args.engine_exec == "p2p":
            # tightened halo cap (PR 2 follow-up): the all_to_all buffer is
            # sized by the MEASURED edge-cut halo, not the worst case caps[0]
            worst = eng.caps[0]
            shrink = worst / eng.fcap
            D = g.features.shape[1]
            log.info("p2p fcap %d (worst-case %d): all_to_all buffer "
                     "%s -> %s per device (%.1fx smaller)",
                     eng.fcap, worst, human_bytes(chips * worst * D * 4),
                     human_bytes(chips * eng.fcap * D * 4), shrink)
            if args.engine_graph == "powerlaw" and chips >= 256:
                assert shrink > 10, (
                    f"measured-halo fcap should shrink the 256-chip "
                    f"all_to_all buffer >10x on the power-law config, "
                    f"got {shrink:.1f}x")
        engine_extra = dict(engine_model=args.engine_model)
        if args.engine_trainable_features:
            engine_extra["trainable_features"] = True
            if not minibatch:
                engine_extra["embed_grad_bytes_per_step"] = \
                    eng._emb_bytes_per_step
                log.info("trainable embeddings: %s/step gradient rows "
                         "routed back to owner shards",
                         human_bytes(eng._emb_bytes_per_step))
            else:
                engine_extra["embed_touched_row_cap"] = eng.tcap
                log.info("trainable embeddings: sparse-AdamW over <= %d "
                         "touched rows per owner per step", eng.tcap)
        if args.engine_family == "vertex_cut":
            from repro.core.partition.cost_models import (
                edge_cut_halo_bytes_per_step,
                edge_cut_halo_device_bytes,
                replica_sync_bytes_per_step,
                replica_sync_device_bytes,
            )
            from repro.core.partition.edge_cut import PARTITIONERS

            dims_g = ([cfg.feature_dim]
                      + [cfg.hidden_dim] * (cfg.num_layers - 1)
                      + [cfg.num_classes])
            ec_part = PARTITIONERS["metis_like"](g, chips)
            m = args.engine_model
            halo = edge_cut_halo_bytes_per_step(g, ec_part, dims_g, model=m)
            halo_max = int(edge_cut_halo_device_bytes(
                g, ec_part, dims_g, model=m).max())
            sync_b = replica_sync_bytes_per_step(
                eng.layout.rep_count, chips, eng.nv, args.engine_exec,
                dims_g, model=m)
            sync_max = int(replica_sync_device_bytes(
                eng.layout, eng.vcut.masters, dims_g, model=m).max())
            engine_extra.update(
                partition_family="vertex_cut",
                vertex_cut=args.engine_vertex_cut,
                replication_factor=eng.layout.replication_factor(),
                replica_sync_bytes_per_step=sync_b,
                replica_sync_bottleneck_bytes=sync_max,
                edge_cut_halo_bytes_per_step=halo,
                edge_cut_halo_bottleneck_bytes=halo_max)
            log.info("vertex-cut %s: replication factor %.2f, replica sync "
                     "%s/step (bottleneck %s) vs edge-cut halo %s/step "
                     "(bottleneck %s)",
                     args.engine_vertex_cut,
                     engine_extra["replication_factor"],
                     human_bytes(sync_b), human_bytes(sync_max),
                     human_bytes(halo), human_bytes(halo_max))
        if args.engine_family == "hybrid":
            from repro.core.partition.cost_models import hybrid_bytes_per_step

            lay = eng.playout
            dims_g = ([cfg.feature_dim]
                      + [cfg.hidden_dim] * (cfg.num_layers - 1)
                      + [cfg.num_classes])
            dev = lay.device_bytes_per_step(args.engine_model, dims_g)
            halo_rows = lay.halo_rows_exec if lay.halo_active else 0
            sync_rows = lay._vc_rows_per_layer if lay.sync_active else 0
            hb = hybrid_bytes_per_step(halo_rows, sync_rows, dims_g,
                                       model=args.engine_model)
            engine_extra.update(
                partition_family="hybrid",
                hub_threshold=float(lay.cut.threshold),
                num_hubs=int(lay.cut.hub.sum()),
                replication_factor=lay.layout.replication_factor(),
                halo_rows_per_pass=int(halo_rows),
                sync_rows_per_layer=int(sync_rows),
                hybrid_bytes_per_step=hb,
                hybrid_bottleneck_bytes=int(dev.max()))
            log.info("hybrid cut thr=%.1f: %d hubs (replication %.2f), "
                     "%d halo rows/pass + %d sync rows/layer -> %s/step "
                     "(bottleneck %s/device)", lay.cut.threshold,
                     engine_extra["num_hubs"],
                     engine_extra["replication_factor"], halo_rows,
                     sync_rows, human_bytes(hb),
                     human_bytes(int(dev.max())))
        compiled = (eng.lower_minibatch_step() if minibatch
                    else eng.lower_step()).compile()
        # --- pipelined-exchange artifacts (ISSUE 4): chunked gathered-table
        # peak + bucketed all_to_all buffer, measured on the LOWERED module
        from repro.core.execution.pipeline_exchange import (
            gathered_table_peak_bytes,
        )
        from repro.launch.hlo_analysis import (
            executable_summary,
            max_collective_buffer_bytes,
        )

        tel.attach_executable(
            "minibatch_train_step" if minibatch else "train_step",
            executable_summary(compiled))
        engine_extra["telemetry"] = tel.run_summary()

        C = args.engine_exchange_chunks
        Dmax = (g.features.shape[1] if minibatch
                else max(eng.dims[:-1]))
        if C > 1 and args.engine_exec == "broadcast":
            mono = gathered_table_peak_bytes(eng.Vp, Dmax, 1)
            chunked = gathered_table_peak_bytes(eng.Vp, Dmax, C)
            red = mono / chunked
            ag = max_collective_buffer_bytes(compiled.as_text(), "all-gather")
            engine_extra.update(
                exchange_chunks=C,
                gathered_table_peak_bytes_monolithic=mono,
                gathered_table_peak_bytes_chunked=chunked,
                gathered_table_reduction=red,
                max_all_gather_buffer_bytes=ag)
            log.info("chunked broadcast exchange (%d chunks): gathered-table "
                     "peak %s -> %s (%.1fx smaller); largest lowered "
                     "all-gather buffer %s", C, human_bytes(mono),
                     human_bytes(chunked), red, human_bytes(ag))
            if C >= 4 and chips >= 256:
                assert red >= 2, (
                    f"chunked broadcast exchange must cut peak gathered-table "
                    f"bytes >= 2x at 256-chip lowering: {red:.2f}x")
        if args.engine_p2p_buckets > 1 and args.engine_exec == "p2p":
            cap_mono = w = None
            if args.engine_family == "vertex_cut":
                cap_mono = max(eng._vc_p2p_caps)
                w = max(eng._vc_plan["send1"].shape[-1],
                        eng._vc_plan["send2"].shape[-1])
            elif minibatch:
                # the frontier fetch rides the same power-of-two installment
                # schedule (ISSUE 5 satellite: no more monolithic fcap send)
                cap_mono, w = eng.fcap, eng.fcap_widths[0]
            elif args.engine_family == "hybrid":
                # the halo leg buckets its caps; the sync leg is accounted
                # under vertex_cut above
                if eng.playout.halo_active:
                    cap_mono = sum(eng.playout.halo_widths)
                    w = eng.playout.halo_widths[0]
            else:
                cap_mono, w = eng.cap, eng.p2p_widths[0]
            if cap_mono is not None:
                mono_buf = chips * cap_mono * Dmax * 4
                a2a = max_collective_buffer_bytes(
                    compiled.as_text(), "all-to-all")
                engine_extra.update(
                    p2p_buckets=args.engine_p2p_buckets,
                    p2p_cap_monolithic=int(cap_mono),
                    p2p_cap_bucketed=int(w),
                    all_to_all_buffer_bytes_monolithic=mono_buf,
                    max_all_to_all_buffer_bytes=a2a)
                log.info("bucketed p2p caps: %d -> %d rows/pair; lowered "
                         "all_to_all buffer %s (monolithic %s)", cap_mono, w,
                         human_bytes(a2a), human_bytes(mono_buf))
                # the cap actually split (hybrid lowers a second, sync-leg
                # all_to_all that the halo-cap model does not bound, so the
                # buffer assert holds for the pure families only)
                if 2 * w <= cap_mono and args.engine_family != "hybrid":
                    assert a2a * 2 <= mono_buf, (
                        f"bucketed p2p caps must shrink the lowered "
                        f"all_to_all buffer >= 2x: {a2a} vs {mono_buf}")
        V = eng.Vp
        K = eng.K
    elif args.protocol == "p2p":
        n_dev = chips
        v_l = V // n_dev
        cap = max(int(args.cut * v_l), 8)  # boundary rows shipped per dest pair
        send_plan = jax.ShapeDtypeStruct((n_dev, n_dev, cap), jnp.int32)
        jitted = jax.jit(gcn_p2p_step_fn(cfg, mesh, cap))
        lowered = jitted.lower(params, specs["ids"], specs["mask"], specs["X"],
                               specs["y"], specs["train_w"], send_plan)
        compiled = lowered.compile()
    else:
        step = gcn_train_step_fn(cfg)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=(in_sh[0], None))
        lowered = jitted.lower(params, specs["ids"], specs["mask"], specs["X"],
                               specs["y"], specs["train_w"])
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    coll, kinds = collective_bytes(compiled.as_text())
    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    # analytic: per layer 2*E*D (aggregation) + 2*V*D_in*D_out, x3 for train
    fl = 0.0
    for a, b in zip(dims[:-1], dims[1:]):
        fl += 2.0 * V * K * a + 2.0 * V * a * b
    fl *= 3.0
    rl = roofline_terms(device_kind=PRODUCTION_DEVICE_KIND, analytic_flops=fl, chips=chips,
                        hbm_bytes_per_chip=(V * D * 4 * 3) / chips,
                        collective_bytes_per_chip=coll,
                        model_flops=fl, hlo_flops_raw=float(ca.get("flops", 0)))
    result = dict(arch="gcn-paper", shape=f"fullgraph_V{V}", mesh=mesh_name,
                  tag=args.protocol if args.protocol != "broadcast" else "",
                  status="ok", chips=chips,
                  memory=dict(argument_bytes_per_device=ma.argument_size_in_bytes,
                              temp_bytes_per_device=ma.temp_size_in_bytes,
                              output_bytes_per_device=ma.output_size_in_bytes,
                              peak_bytes_per_device=ma.peak_memory_in_bytes,
                              alias_bytes_per_device=ma.alias_size_in_bytes),
                  cost_analysis={k: ca[k] for k in ("flops", "bytes accessed") if k in ca},
                  collective_bytes_per_device=coll, collective_by_kind=kinds,
                  analytic_flops=fl, model_flops_6nd=fl,
                  hbm_traffic_bytes_per_chip=(V * D * 4 * 3) / chips,
                  roofline=rl.as_dict())
    if args.protocol == "engine" and engine_extra:
        result.update(engine_extra)
    os.makedirs(args.out, exist_ok=True)
    suffix = f"__{args.protocol}" if args.protocol != "broadcast" else ""
    if args.protocol == "engine" and args.engine_model != "gcn":
        suffix += f"_{args.engine_model}"
    if args.protocol == "engine" and args.engine_batching != "full_graph":
        suffix += f"_{args.engine_batching}"
    if args.protocol == "engine" and args.engine_family == "vertex_cut":
        suffix += f"_vertexcut_{args.engine_vertex_cut}"
    if args.protocol == "engine" and args.engine_family == "hybrid":
        suffix += "_hybrid"
    path = os.path.join(args.out, f"gcn-paper__fullgraph__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=float)
    log.info("OK gcn-paper fullgraph %s %.1fs args=%s temp=%s coll=%s dom=%s",
             mesh_name, time.time() - t0, human_bytes(ma.argument_size_in_bytes),
             human_bytes(ma.temp_size_in_bytes), human_bytes(coll), rl.dominant)


if __name__ == "__main__":
    main()
