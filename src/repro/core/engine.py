"""DistGNNEngine: the survey's four technique families composed into ONE
jitted shard_map training step.

  model (§3)       a selectable `model` axis — {gcn, sage, gat, gin} — the
                   GNN layer program every jitted path (full-graph and
                   mini-batch, edge-cut and vertex-cut, all execution
                   models) runs.  The survey's challenges are
                   model-dependent and the axis makes that concrete:
                   sage/gin's self-feature terms read the RESIDENT block
                   (zero extra wire bytes over gcn); gat's edge-wise
                   attention changes what crosses the wire — the exchange
                   ships TRANSFORMED rows plus a per-row attention
                   coefficient (a_src . Hw), per-edge logits ride the
                   Pallas SDDMM kernel over the ELL structure, and the
                   masked segment-softmax keeps pad slots inert; under
                   vertex_cut the softmax normalizer is exactified across
                   replicas by a two-pass (max, then sum) replica sync.
  partition (§4)   a selectable `partition_family` axis:
                     edge_cut   — a partitioner assigns VERTICES to devices;
                                  the engine relabels vertices so device d
                                  owns the contiguous padded block
                                  [d*nb, (d+1)*nb) — the partition plan IS
                                  the device layout.  Neighbor values cross
                                  the wire (halo exchange).
                     vertex_cut — a cut assigns EDGES to devices; vertices
                                  replicate (partition/vertex_layout.py turns
                                  the cut into per-device owned-edge ELL
                                  blocks + replica slot tables).  Each device
                                  computes PARTIAL aggregations over its
                                  owned edges; partials are combined across
                                  replicas by the replica-sync exchange
                                  (execution/replica_sync.py) — broadcast /
                                  ring / master-based two-phase p2p GAS —
                                  and the loss (hence the weight-gradient
                                  psum) is masked to each vertex's MASTER
                                  replica so nothing double-counts.  The
                                  wire volume is bounded by the replication
                                  factor, the §4.2 lever for skewed graphs.
                     hybrid     — the PowerLyra-style degree-threshold cut
                                  (partition/hybrid_cut.py): low-degree
                                  vertices stay edge-cut-local behind a
                                  halo exchange while hubs (degree >=
                                  `hub_threshold`, default auto p95)
                                  replicate with the replica-sync GAS —
                                  only the heavy tail pays the replication
                                  tax.  threshold=inf/0 degenerate to the
                                  pure families exactly.
                   The families live behind partition/layout_api.py
                   (`PartitionLayout` owns slot tables, exchange constants,
                   master masking, reference wiring, byte accounting) and
                   execution/exchange_api.py (`ExchangeBackend` owns the
                   per-layer aggregate/attention/combine dataflow); the
                   engine itself is family-free dispatch, and a new family
                   is one layout class + one backend + a registry entry.
  batch (§5)       a selectable `batching` axis:
                     full_graph — each device's partition block is its batch
                                  (PSGD-style ownership, loss masked to owned
                                  train vertices and globally psum-reduced);
                     node_wise / layer_wise / subgraph — sampled mini-batches:
                                  each device draws targets from its OWNED
                                  partition block, expands them host-side with
                                  the §5 samplers, and pads the layered blocks
                                  to static caps derived from the fanout
                                  config, so the jitted shard_map step
                                  compiles ONCE per fanout config (not per
                                  batch).  Input features for the sampled
                                  frontier are fetched through the same
                                  execution models as the full-graph path,
                                  short-circuited by a device-resident
                                  feature cache (sampling/cache.py policies);
                                  hit/miss bytes are counted against
                                  CommStats via the standalone
                                  feature_fetch_bytes cost model.
  execution (§6)   the local multiply is the slot-wise ELL SpMM
                   (repro.kernels.ell_spmm, differentiable via transpose
                   scatter-add VJP); the neighbor exchange is a selectable
                   execution model:
                     broadcast — all_gather of the full H (CAGNET 1D),
                     ring      — ppermute rotation with per-source-block
                                 partial aggregation (SAR/chunk pipeline),
                     p2p       — halo exchange: only the boundary rows each
                                 destination actually needs cross the wire
                                 (all_to_all on a static partition plan,
                                 optionally split into power-of-two BUCKETED
                                 installments so the lowered send buffers
                                 stay small — cfg.p2p_buckets).
                   The exchange is PIPELINED two ways (§6-§7 overlap,
                   execution/pipeline_exchange.py): ``exchange_chunks`` > 1
                   feature-chunks the broadcast/p2p collectives so chunk
                   c+1's collective flies while chunk c feeds the ELL
                   multiply (peak gathered-table bytes O(V*D/chunks)), and
                   ``run_epoch_minibatch(schedule="pipelined")`` overlaps
                   host sampling/extraction with the device step through a
                   background prefetch worker (sampling/prefetch.py) —
                   bitwise-identical to the blocking path, faster on the
                   wall.
  protocol (§7)    sync (fresh embeddings every layer) or async historical
                   embeddings with a bounded-staleness model (epoch_fixed /
                   epoch_adaptive / variation), applied block-locally so the
                   SPMD step and the single-device oracle share the exact
                   same refresh math (protocols.async_hist.block_refresh).

Every configuration is oracle-checkable: `reference_step` runs the identical
math on one device (vmapping the per-block protocol over the block axis), so
multi-device runs must match it to float tolerance — the engine's contract,
enforced by tests/test_engine_distributed.py.  The mini-batch path has the
same contract: `reference_minibatch_step` consumes the exact same sampled,
padded batches (host sampling is deterministic in (seed, step, device)), so
every sampler x execution x cache combination must match it to <=1e-4 —
enforced by tests/test_engine_minibatch.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import interpret_default, make_mesh, shard_map
from repro.core.execution.exchange_api import make_backend
from repro.core.execution.pipeline_exchange import (
    bucketed_all_to_all,
    bucketed_cap_widths,
    chunked_overlap,
    zero_pad_row,
)
from repro.core.execution.replica_sync import (
    reference_combine,
    reference_combine_max,
)
from repro.core.feature_store import (
    FeatureStore,
    overlay_refresh_plan,
)
from repro.core.graph import Graph
from repro.core.models.gnn import init_gnn_params, padded_minibatch_forward
from repro.core.partition.edge_cut import Partition
from repro.core.partition.layout_api import (
    ENGINE_MIRROR_ATTRS,
    get_layout_builder,
)
from repro.core.protocols.async_hist import block_refresh
from repro.core.sampling.cache import CACHE_POLICIES, device_cache_ids
from repro.core.sampling.distributed import CommStats
from repro.core.sampling.host_batch import HostBatchBuilder
from repro.core.sampling.partition_batch import p2p_frontier_halo_cap
from repro.core.sampling.samplers import frontier_caps
from repro.core.telemetry import Telemetry
from repro.kernels.ell_spmm import ell_attend, ell_spmm
from repro.optim.sparse_optim import row_adamw_update, sparse_adamw_ids
from repro.kernels.ref import sddmm_ref
from repro.kernels.sddmm import sddmm_ell

EXECUTION_MODELS = ("broadcast", "ring", "p2p")
GNN_MODELS = ("gcn", "sage", "gat", "gin")
PROTOCOLS = ("sync", "epoch_fixed", "epoch_adaptive", "variation")
BATCHING_MODES = ("full_graph", "node_wise", "layer_wise", "subgraph")
PARTITION_FAMILIES = ("edge_cut", "vertex_cut", "hybrid")
ENGINE_CACHE_POLICIES = ("none",) + tuple(CACHE_POLICIES)


def _xent_numerator(logits, y, w):
    """The w-weighted cross-entropy summed over this device's rows."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return ((lse - ll) * w).sum()


def _sync_loss_and_grads(num, w, grads, ax):
    """(loss, den, grads): this device's loss numerator and gradients summed
    over the mesh axis and divided by the global weight ``den``."""
    with jax.named_scope("loss"):
        den = jnp.maximum(jax.lax.psum(w.sum(), ax), 1.0)
        loss = jax.lax.psum(num, ax) / den
    with jax.named_scope("grad_sync"):
        grads = jax.tree_util.tree_map(
            lambda g_: jax.lax.psum(g_, ax) / den, grads)
    return loss, den, grads


@dataclasses.dataclass
class EngineConfig:
    execution: str = "p2p"  # broadcast | ring | p2p
    protocol: str = "sync"  # sync | epoch_fixed | epoch_adaptive | variation
    model: str = "gcn"  # gcn | sage | gat | gin — the GNN layer program.
    #   sage/gin read their self features from the RESIDENT block (never on
    #   the wire); gat ships transformed rows + the per-row attention
    #   coefficient (a_src . Hw) through the exchange and runs a masked
    #   segment-softmax over the ELL slots (for vertex_cut: a two-pass
    #   max-then-sum replica sync so the normalizer is exact across replicas)
    partition_family: str = "edge_cut"  # edge_cut | vertex_cut | hybrid —
    #   each family is a partition/layout_api.py PartitionLayout paired with
    #   an execution/exchange_api.py backend (hybrid: PowerLyra-style
    #   degree-threshold cut, partition/hybrid_cut.py)
    partitioner: str = "metis_like"  # edge_cut/hybrid: any key of PARTITIONERS
    vertex_cut: str = "cartesian2d"  # vertex_cut: any key of VERTEX_CUTS
    hub_threshold: Optional[float] = None  # hybrid: vertices with in-degree
    #   >= threshold replicate (vertex-cut class); below it they stay
    #   edge-cut-local behind the halo.  None -> the 95th-percentile
    #   in-degree (partition/hybrid_cut.auto_hub_threshold); np.inf -> pure
    #   edge-cut dataflow, 0 -> pure (src-replicating) vertex-cut
    sorted_masters: bool = False  # vertex_cut: order each device's replica
    #   slots master-first (contiguous prefix), so master-masked host reads
    #   slice instead of scanning a boolean mask — a layout option the
    #   autotuner weighs; bitwise-equivalent training math
    batching: str = "full_graph"  # full_graph | node_wise | layer_wise | subgraph
    batch_size: int = 16  # per-device targets (node/layer-wise) or walk roots
    fanouts: Tuple[int, ...] = (4, 4)  # node_wise; len == num_layers
    layer_sizes: Tuple[int, ...] = (32, 32)  # layer_wise; len == num_layers
    walk_length: int = 4  # subgraph random walk
    cache_policy: str = "none"  # none | any key of sampling CACHE_POLICIES
    cache_capacity: int = 0  # remote feature rows resident per device
    exchange_chunks: int = 1  # feature-dim chunks: overlap collective c+1
    #   with the ELL multiply of chunk c (1 = monolithic exchange)
    p2p_buckets: int = 1  # power-of-two installments splitting the p2p
    #   all_to_all send caps (1 = single max-pairwise-need buffer); applies
    #   to the full-graph halo plan, the replica-sync plan, AND the
    #   mini-batch frontier fetch (per-batch occupancy rides a static
    #   bucket layout: row t of a pair's need list always lands in
    #   installment t // w, so shapes never change across batches)
    prefetch_depth: int = 2  # batches the pipelined epoch samples ahead
    prefetch_mode: str = "thread"  # thread | process — who runs the
    #   pipelined producer.  "thread": the in-process `PrefetchWorker`
    #   (overlap capacity-limited by the GIL).  "process": a
    #   `ProcPrefetchPool` of sampling processes feeding a shared-memory
    #   batch ring (sampling/proc_prefetch.py) — GIL-free, scales across
    #   cores, still bitwise-identical to the blocking schedules
    num_sample_workers: int = 2  # process-pool size for prefetch_mode=process
    trainable_features: bool = False  # layer-0 rows are LEARNABLE embeddings:
    #   the owner-sharded feature shard moves from the step's constants into
    #   its state and a row-sparse AdamW (optim/sparse_optim.py) updates ONLY
    #   the rows the step touched — all owned real rows under full_graph, the
    #   frontier's owner rows under mini-batch (master-masked under
    #   vertex_cut so replicas never double-update; the masters' deltas are
    #   re-broadcast through the replica sync so copies never drift).
    #   Requires protocol='sync' (historical embeddings of a moving layer-0
    #   table are a ROADMAP follow-up).
    embed_lr: float = 0.1  # sparse-AdamW hyperparams for the embedding rows
    embed_b1: float = 0.9
    embed_b2: float = 0.999
    embed_eps: float = 1e-8
    embed_weight_decay: float = 0.0
    hidden: int = 32
    num_layers: int = 2
    lr: float = 0.5
    staleness: int = 2
    eps_v: float = 0.05
    hard_bound: int = 4
    seed: int = 0
    # GAT's Pallas kernels (sddmm, the attention weights' gather-dot
    # gradient); False: their XLA forms (debug).  The gather-sum is XLA's.
    use_pallas: bool = True
    interpret: Optional[bool] = None  # Pallas interpret mode; None = auto


class DistGNNEngine:
    """Builds the device layout + exchange plan from (graph, mesh, config) and
    exposes a jitted distributed train step plus its single-device oracle."""

    def __init__(self, g: Graph, mesh: Optional[Mesh] = None,
                 cfg: Optional[EngineConfig] = None,
                 partition: Optional[Partition] = None,
                 telemetry: Optional[Telemetry] = None):
        self.cfg = cfg = cfg or EngineConfig()
        if cfg.execution not in EXECUTION_MODELS:
            raise ValueError(f"execution must be one of {EXECUTION_MODELS}")
        if cfg.model not in GNN_MODELS:
            raise ValueError(f"model must be one of {GNN_MODELS}")
        if cfg.protocol not in PROTOCOLS:
            raise ValueError(f"protocol must be one of {PROTOCOLS}")
        if cfg.batching not in BATCHING_MODES:
            raise ValueError(f"batching must be one of {BATCHING_MODES}")
        if cfg.cache_policy not in ENGINE_CACHE_POLICIES:
            raise ValueError(
                f"cache_policy must be one of {ENGINE_CACHE_POLICIES}")
        if cfg.batching != "full_graph" and cfg.protocol != "sync":
            raise ValueError(
                "mini-batch training supports protocol='sync' only: the "
                "historical-embedding protocols are full-graph state")
        if cfg.trainable_features and cfg.protocol != "sync":
            raise ValueError(
                "trainable_features requires protocol='sync': the "
                "historical-embedding protocols cache layer outputs of a "
                "FROZEN layer-0 table; staleness bounds for a moving "
                "embedding table are a ROADMAP follow-up")
        if cfg.exchange_chunks < 1:
            raise ValueError("exchange_chunks must be >= 1")
        if cfg.p2p_buckets < 1:
            raise ValueError("p2p_buckets must be >= 1")
        if cfg.prefetch_depth < 1:
            raise ValueError("prefetch_depth must be >= 1")
        if cfg.prefetch_mode not in ("thread", "process"):
            raise ValueError("prefetch_mode must be 'thread' or 'process'")
        if cfg.num_sample_workers < 1:
            raise ValueError("num_sample_workers must be >= 1")
        if cfg.partition_family not in PARTITION_FAMILIES:
            raise ValueError(
                f"partition_family must be one of {PARTITION_FAMILIES}")
        builder = get_layout_builder(cfg.partition_family)
        builder.validate(cfg, partition=partition)
        if mesh is None:
            mesh = make_mesh((len(jax.devices()),), ("w",))
        if len(mesh.axis_names) != 1:
            raise ValueError("DistGNNEngine wants a 1D mesh (one axis over "
                             f"all devices); got axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.k = int(np.prod(mesh.devices.shape))
        self.g = g
        self.interpret = (interpret_default() if cfg.interpret is None
                          else cfg.interpret)
        # the partition family builds its layout (slot tables, exchange-plan
        # constants, masking, accounting) behind the PartitionLayout
        # interface; the engine mirrors the engine-facing attributes so
        # downstream code (mini-batch planner, drivers, tests) keeps reading
        # eng.<attr>, and dispatches the traced exchange to the family's
        # ExchangeBackend
        # off by default: no-op spans/metrics until enable_telemetry(); a
        # telemetry= argument is enabled here, so the layout build is traced
        self.telemetry = (Telemetry(enabled=False) if telemetry is None
                          else telemetry)
        lay = self.playout = builder(g, self.k, cfg, partition=partition,
                                     telemetry=self.telemetry)
        for name in ENGINE_MIRROR_ATTRS:
            if hasattr(lay, name):
                setattr(self, name, getattr(lay, name))
        self.backend = make_backend(self)
        num_classes = int(g.labels.max()) + 1
        self.dims = ([g.features.shape[1]]
                     + [cfg.hidden] * (cfg.num_layers - 1) + [num_classes])
        # CommStats field -> wire bytes ONE full-graph step accrues (each
        # entry mirrors the family's standalone cost model exactly)
        self._wire_fields = lay.wire_fields_per_step(cfg.model, self.dims)
        if cfg.trainable_features and cfg.batching == "full_graph":
            # layer-0 gradient routing per step (the transpose of one
            # exchange pass at width dims[0]); mirrors the standalone
            # cost_models.embedding_grad_bytes_per_step exactly
            self._emb_bytes_per_step = lay.embed_grad_bytes(self.dims)
        self._step = None
        self._ref_step = None
        self._mb_step = None
        self._mb_ref_step = None
        self._infer_step = None
        self._ref_infer = None
        self.comm_stats = CommStats()
        if telemetry is not None:
            self.enable_telemetry(telemetry)
        if cfg.batching != "full_graph":
            self._build_minibatch_plan()

    # ------------------------------------------------------------------
    # shared layer math
    # ------------------------------------------------------------------

    def _ell(self, ids, mask, table):
        """sum_k mask[v,k] * table[ids[v,k]] — the ELL aggregation kernel:
        the local multiply AND the replica-combine reduction."""
        return ell_spmm(ids, mask, table, normalize=False)

    def _ell_attend(self, ids, w, table):
        """sum_k w[v,k] * table[ids[v,k]] with gradients to BOTH w and table —
        the GAT aggregation (`_ell`'s VJP treats the mask as structure, but
        attention coefficients are a function of the params)."""
        return ell_attend(ids, w, table, interpret=self.interpret,
                          use_pallas=self.cfg.use_pallas)

    def _sddmm(self, ids, mask, table, a_src, a_dst):
        """Masked GAT edge logits over the ELL structure (Pallas SDDMM or its
        jnp oracle); dst row v must be table row v (prefix contract)."""
        if self.cfg.use_pallas:
            return sddmm_ell(ids, mask, table, a_src, a_dst,
                             interpret=self.interpret)
        return sddmm_ref(ids, mask, table, a_src, a_dst)

    @staticmethod
    def _combine(model, p_l, nbr, h_self, last: bool):
        """Model-specific combine of the aggregated neighbor rows with the
        RESIDENT self rows — shared verbatim by the distributed step and the
        single-device oracle (gat has its own program: the aggregation
        itself is attention-weighted).  sage/gin read h_self straight from
        the local block, so the model axis adds ZERO exchange bytes over
        gcn — the §4 locality argument the cost models encode."""
        if model == "gcn":
            z = (nbr + h_self) @ p_l["w"] + p_l["b"]
        elif model == "sage":
            z = h_self @ p_l["w_self"] + nbr @ p_l["w_nbr"] + p_l["b"]
        elif model == "gin":
            z = jax.nn.relu(
                ((1.0 + p_l["eps"]) * h_self + nbr) @ p_l["w1"]) @ p_l["w2"]
        else:
            raise ValueError(model)
        return z if last else jax.nn.relu(z)

    @staticmethod
    def _gat_softmax(e_masked):
        """Masked segment-softmax pieces over ELL slots: (weights, den) from
        logits already masked to -1e30.  Rows with no real slots get
        den == 0 (the caller falls back to the self row — the same contract
        as the dense `gnn_layer` isolated-row fallback).  The stabilizer is
        stop_gradient'd: softmax is shift-invariant, so treating it as a
        constant gives the exact gradient without transposing the max."""
        m = jax.lax.stop_gradient(jnp.max(e_masked, axis=1, keepdims=True))
        pw = jnp.exp(e_masked - m) * (e_masked > -1e29)
        return pw, pw.sum(1, keepdims=True)

    def _place(self, consts, specs):
        """Commit the step's constants to the mesh once, with the shardings
        the step reads them with — uncommitted arrays would be resharded
        from device 0 on every call."""
        with self.telemetry.span("step.place_consts"):
            return jax.device_put(consts, {
                key: NamedSharding(self.mesh, spec)
                for key, spec in specs.items()})

    def _protocol_kwargs(self):
        c = self.cfg
        return dict(staleness=c.staleness, eps=c.eps_v, hard_bound=c.hard_bound)

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def init_state(self, key=None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(self.cfg.seed)
        params = init_gnn_params(self.cfg.model, self.dims, key)
        L = len(self.dims) - 1
        # historical embeddings only exist under the async protocols; sync
        # carries one placeholder row per device instead of [Vp, d] zeros
        hist_rows = self.k if self.cfg.protocol == "sync" else self.Vp
        state = dict(
            params=params,
            step=jnp.zeros((), jnp.int32),
            hist=tuple(jnp.zeros((hist_rows, d), jnp.float32)
                       for d in self.dims[1:]),
            age=jnp.zeros((L, self.k), jnp.int32),
        )
        # Pre-place with the step's output shardings so feeding the state
        # back in reuses the ONE compiled executable (same contract as
        # init_minibatch_state; enforced by the vertex-cut recompile guard).
        ax = self.axis
        rep = NamedSharding(self.mesh, P())
        row = NamedSharding(self.mesh, P(ax))  # == P(ax, None) for 2D, but
        shardings = dict(                      # spelled how the step emits it
            params=jax.tree_util.tree_map(lambda _: rep, params),
            step=rep,
            hist=tuple(row for _ in range(L)),
            age=NamedSharding(self.mesh, P(None, ax)),
        )
        if self.cfg.trainable_features:
            # the embedding table (the store's device view) and its owner-
            # sharded sparse-AdamW moments live in the STATE, not the consts
            state["embed"] = self.X
            state["emb_m"] = jnp.zeros_like(self.X)
            state["emb_v"] = jnp.zeros_like(self.X)
            state["emb_t"] = jnp.zeros((self.Vp,), jnp.int32)
            shardings.update(embed=row, emb_m=row, emb_v=row, emb_t=row)
        return jax.device_put(state, shardings)

    # ------------------------------------------------------------------
    # distributed step
    # ------------------------------------------------------------------

    def _model_layer_local(self, p_l, H, consts_local, last: bool):
        """One model-aware layer of the distributed forward (device-local
        under shard_map), dispatched to the partition family's
        ExchangeBackend (execution/exchange_api.py): gat runs the backend's
        attention program between its dense transforms, rows with no real
        slot falling back to their own transformed row; everyone else is
        the backend's exchange-aggregate + the shared `_combine`."""
        if self.cfg.model == "gat":
            with jax.named_scope("combine"):
                Hw = H @ p_l["w"]
            with jax.named_scope("aggregate"):
                num, den = self.backend.gat_attend(p_l, Hw, consts_local)
            with jax.named_scope("combine"):
                z = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), Hw)
                return z if last else jax.nn.relu(z)
        with jax.named_scope("aggregate"):
            nbr = self.backend.aggregate(H, consts_local)
        with jax.named_scope("combine"):
            return self._combine(self.cfg.model, p_l, nbr, H, last)

    def _forward_local(self, params, hist, age, step, consts_local, X=None):
        """Full local forward with protocol mixing; returns (logits_local,
        new_hist, new_age, rows_pushed).  ``X`` overrides the layer-0 rows
        (the trainable-embedding path differentiates through it)."""
        c = self.cfg
        ax = self.axis
        H = consts_local["X"] if X is None else X
        L = len(self.dims) - 1
        me = jax.lax.axis_index(ax)
        new_hist, new_age, pushed = [], [], jnp.zeros((), jnp.float32)
        for l, p_l in enumerate(params["layers"]):
            with jax.named_scope(f"layer{l}"):
                H = self._model_layer_local(p_l, H, consts_local,
                                            last=(l == L - 1))
                if c.protocol == "sync":
                    new_hist.append(hist[l])
                    new_age.append(age[l])
                    continue
                with jax.named_scope("history"):
                    H, h2, a2, rows = block_refresh(
                        c.protocol, hist[l], H, age[l][0], step,
                        consts_local["bmask"], me,
                        **self._protocol_kwargs())
                    new_hist.append(h2)
                    new_age.append(a2[None])
                    pushed = pushed + rows.astype(jnp.float32)
        return H, tuple(new_hist), jnp.stack(new_age), pushed

    def _embed_hparams(self):
        c = self.cfg
        return dict(lr=c.embed_lr, b1=c.embed_b1, b2=c.embed_b2,
                    eps=c.embed_eps, weight_decay=c.embed_weight_decay)

    def _embed_update_full(self, emb, g_emb, state, cl):
        """Full-graph sparse-AdamW embedding update (device-local under
        shard_map): masked-dense over the owned shard — the touched set is
        static (every real owned row; vertex masters under vertex_cut), so
        the mask form costs exactly the touched rows in moment traffic and
        leaves untouched rows (pads / non-masters) bitwise unchanged.

        Replica families (vertex_cut / hybrid with an active sync): g_emb is
        each replica's PARTIAL gradient; the backend's combine_rows turns it
        into the full vertex gradient, the update applies at MASTER slots
        only (moments live at masters), and the masters' deltas are
        re-broadcast through the same sync — a sum with one nonzero
        contribution, so every replica adds the bitwise-same delta and the
        copies never drift.  combine_rows is the identity for single-replica
        families, so the code is family-agnostic."""
        touched = cl["emb_touched"]
        g_emb = self.backend.combine_rows(g_emb, cl)
        emb2, m2, v2, t2 = row_adamw_update(
            emb, g_emb, state["emb_m"], state["emb_v"], state["emb_t"],
            touched, **self._embed_hparams())
        if self.backend.has_replicas:
            delta = (emb2 - emb) * touched[:, None]
            delta_all = self.backend.combine_rows(delta, cl)
            emb2 = emb + delta_all
        return dict(embed=emb2, emb_m=m2, emb_v=v2, emb_t=t2)

    def make_step(self):
        """The jitted distributed train step: state -> (state, metrics)."""
        if self._step is not None:
            return self._step
        ax = self.axis
        c = self.cfg
        L = len(self.dims) - 1

        consts = dict(X=self.X, y=self.y, w=self.train_w, bmask=self.bmask,
                      deg=self.deg)
        consts.update(self.playout.exchange_consts())
        if c.trainable_features:
            # layer-0 rows come from state["embed"]; the touched mask is the
            # static full-graph batch (real owned rows / vertex masters)
            del consts["X"]
            consts["emb_touched"] = jnp.asarray(self.emb_touched)
        # every const shards its LEADING axis (device-stacked plan tables or
        # owner-partitioned rows) and replicates the rest — the layout
        # contract every family's tables are built to
        shard = {key: P(*((ax,) + (None,) * (jnp.ndim(a) - 1)))
                 for key, a in consts.items()}
        state_specs = dict(
            params=P(), step=P(),
            hist=tuple(P(ax, None) for _ in range(L)),
            age=P(None, ax))
        if c.trainable_features:
            state_specs.update(embed=P(ax, None), emb_m=P(ax, None),
                               emb_v=P(ax, None), emb_t=P(ax))

        def local_step(state, consts_local):
            params, step_i = state["params"], state["step"]
            hist, age = state["hist"], state["age"]
            # squeeze the device axis off per-device-stacked plan tables
            cl = dict(consts_local)
            for key in self.playout.squeeze_keys:
                cl[key] = cl[key][0]
            age_l = [age[l] for l in range(L)]

            # Differentiate the LOCAL loss numerator only: the psum-normalized
            # loss is assembled outside the grad.  Transposing a psum under
            # shard_map is version-dependent (0.4.x transposes psum->psum and
            # double-counts by k; the check_vma rework transposes to identity);
            # the collectives inside the forward (all_gather / all_to_all /
            # ppermute) have stable, well-defined transposes on all supported
            # versions, so grads of the local numerator are portable.
            def num_fn(p, X_l):
                logits, new_hist, new_age, pushed = self._forward_local(
                    p, hist, age_l, step_i, cl, X=X_l)
                with jax.named_scope("loss"):
                    num = _xent_numerator(logits, cl["y"], cl["w"])
                return num, (logits, new_hist, new_age, pushed)

            if c.trainable_features:
                # Differentiating w.r.t. the layer-0 rows rides the SAME
                # stable collective transposes: g_X arrives already summed
                # over every device that read the row (all_gather ->
                # reduce-scatter etc.), i.e. the owner's total gradient — no
                # psum, which would double-count it.
                (num, (logits, new_hist, new_age, pushed)), (grads, g_X) = (
                    jax.value_and_grad(num_fn, argnums=(0, 1), has_aux=True)(
                        params, state["embed"]))
            else:
                (num, (logits, new_hist, new_age, pushed)), grads = (
                    jax.value_and_grad(num_fn, has_aux=True)(
                        params, cl["X"]))
            loss, den, grads = _sync_loss_and_grads(num, cl["w"], grads, ax)
            with jax.named_scope("sgd"):
                params2 = jax.tree_util.tree_map(
                    lambda p_, g_: p_ - c.lr * g_, params, grads)
                state2 = dict(params=params2, step=step_i + 1,
                              hist=new_hist, age=new_age)
                if c.trainable_features:
                    state2.update(self._embed_update_full(
                        state["embed"], g_X / den, state, cl))
            with jax.named_scope("history"):
                metrics = dict(loss=loss,
                               rows_pushed=jax.lax.psum(pushed, ax))
            return state2, metrics, logits

        smapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_specs, shard),
            out_specs=(state_specs, dict(loss=P(), rows_pushed=P()),
                       P(ax, None)),
            check_vma=False)

        @jax.jit
        def step(state, consts_):
            new_state, metrics, logits = smapped(state, consts_)
            return new_state, metrics, logits

        self._consts = self._place(consts, shard)
        self._jit_step = step
        self._step = lambda state: step(state, self._consts)
        return self._step

    def lower_step(self, state=None):
        """Lower (without running) the distributed step — for dry-runs that
        record memory/collective artifacts at scale."""
        self.make_step()
        state = state if state is not None else self.init_state()
        return self._jit_step.lower(state, self._consts)

    # ------------------------------------------------------------------
    # single-device oracle
    # ------------------------------------------------------------------

    def _make_reference_layer(self):
        """Single-device reference layer math, shared by the oracle train
        step and reference inference: global ELL gather (for vertex_cut:
        per-replica partials + a scatter-add combine over the global vertex
        space).  Returns ``layer_ref(p_l, H, last)`` over the padded [Vp]
        space."""
        c = self.cfg
        k, nb, Vp = self.k, self.nb, self.Vp
        ids_g = jnp.asarray(self.ids_global.astype(np.int32))
        mask, deg = self.mask, self.deg
        # replica families expose their [k, n] slot->global-vertex table; a
        # non-None table switches the combine to the scatter-based reference
        ref_vids = self.playout.ref_vert_ids
        if ref_vids is not None:
            vert_ids_ref = jnp.asarray(ref_vids.astype(np.int32))  # pad = V
            Vg = self.g.num_vertices

        def gat_layer_ref(p_l, H, last):
            """The GAT layer on one device: identical formulas to the
            distributed path, with the replica combines replaced by their
            scatter-based references for replica families."""
            Hw = H @ p_l["w"]
            table = jnp.concatenate([Hw, jnp.zeros((1, Hw.shape[1]),
                                                   Hw.dtype)], 0)
            e = self._sddmm(ids_g, mask, table, p_l["a_src"], p_l["a_dst"])
            if ref_vids is not None:
                m_loc = jnp.maximum(jnp.max(e, axis=1, keepdims=True), 0.0)
                M = jax.lax.stop_gradient(reference_combine_max(
                    m_loc.reshape(k, nb, 1), vert_ids_ref, Vg
                ).reshape(Vp, 1))
                pw = jnp.exp(e - M) * (e > -1e29)
                part = jnp.concatenate(
                    [(pw[..., None] * jnp.take(table, ids_g, axis=0)).sum(1),
                     pw.sum(1, keepdims=True)], 1)
                comb = reference_combine(part.reshape(k, nb, -1),
                                         vert_ids_ref, Vg).reshape(Vp, -1)
                num, den = comb[:, :-1], comb[:, -1:]
            else:
                pw, den = self._gat_softmax(e)
                num = (pw[..., None] * jnp.take(table, ids_g, axis=0)).sum(1)
            z = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), Hw)
            return z if last else jax.nn.relu(z)

        def layer_ref(p_l, H, last):
            if c.model == "gat":
                return gat_layer_ref(p_l, H, last)
            table = jnp.concatenate(
                [H, jnp.zeros((1, H.shape[1]), H.dtype)], 0)
            gathered = (mask[..., None]
                        * jnp.take(table, ids_g, axis=0)).sum(1)
            if ref_vids is not None:
                gathered = reference_combine(
                    gathered.reshape(k, nb, -1), vert_ids_ref, Vg
                ).reshape(Vp, -1)
            return self._combine(c.model, p_l, gathered / deg, H, last=last)

        return layer_ref

    def make_reference_step(self):
        """Identical math on one device: the shared reference layer
        (`_make_reference_layer`) + the same block_refresh vmapped over the
        k blocks."""
        if self._ref_step is not None:
            return self._ref_step
        c = self.cfg
        k, nb, Vp = self.k, self.nb, self.Vp
        L = len(self.dims) - 1
        layer_ref = self._make_reference_layer()
        X, y, w, bmask = self.X, self.y, self.train_w, self.bmask
        ref_vids = self.playout.ref_vert_ids
        if ref_vids is not None:
            vert_ids_ref = jnp.asarray(ref_vids.astype(np.int32))  # pad = V
            Vg = self.g.num_vertices

        def forward(params, hist, age, step_i, X_in=None):
            H = X if X_in is None else X_in
            new_hist, new_age = [], []
            pushed = jnp.zeros((), jnp.float32)
            for l, p_l in enumerate(params["layers"]):
                H = layer_ref(p_l, H, last=(l == L - 1))
                if c.protocol != "sync":
                    h_blocks = H.reshape(k, nb, -1)
                    hist_blocks = hist[l].reshape(k, nb, -1)
                    bm_blocks = bmask.reshape(k, nb)
                    h_used, h2, a2, rows = jax.vmap(
                        lambda hb, histb, ab, pidb, bmb: block_refresh(
                            c.protocol, histb, hb, ab, step_i, bmb, pidb,
                            **self._protocol_kwargs()))(
                        h_blocks, hist_blocks, age[l], jnp.arange(k), bm_blocks)
                    H = h_used.reshape(Vp, -1)
                    new_hist.append(h2.reshape(Vp, -1))
                    new_age.append(a2)
                    pushed = pushed + rows.sum().astype(jnp.float32)
                else:
                    new_hist.append(hist[l])
                    new_age.append(age[l])
            return H, tuple(new_hist), jnp.stack(new_age), pushed

        if c.trainable_features:
            touched_ref = jnp.asarray(self.emb_touched)

        def ref_combine_rows(rows):
            """Replica combine in the flattened replica space — the oracle's
            counterpart of the replica-sync passes in _embed_update_full."""
            return reference_combine(rows.reshape(k, nb, -1), vert_ids_ref,
                                     Vg).reshape(Vp, -1)

        @jax.jit
        def ref_step(state):
            params, step_i = state["params"], state["step"]

            def loss_fn(p, X_in):
                logits, new_hist, new_age, pushed = forward(
                    p, state["hist"], state["age"], step_i, X_in)
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
                loss = ((lse - ll) * w).sum() / jnp.maximum(w.sum(), 1.0)
                return loss, (logits, new_hist, new_age, pushed)

            if c.trainable_features:
                (loss, (logits, new_hist, new_age, pushed)), (grads, g_X) = (
                    jax.value_and_grad(loss_fn, argnums=(0, 1),
                                       has_aux=True)(params, state["embed"]))
            else:
                (loss, (logits, new_hist, new_age, pushed)), grads = (
                    jax.value_and_grad(loss_fn, has_aux=True)(params, X))
            params2 = jax.tree_util.tree_map(
                lambda p_, g_: p_ - c.lr * g_, params, grads)
            state2 = dict(params=params2, step=step_i + 1,
                          hist=new_hist, age=new_age)
            if c.trainable_features:
                emb = state["embed"]
                if self.playout.has_replicas:
                    g_X = ref_combine_rows(g_X)
                emb2, m2, v2, t2 = row_adamw_update(
                    emb, g_X, state["emb_m"], state["emb_v"],
                    state["emb_t"], touched_ref, **self._embed_hparams())
                if self.playout.has_replicas:
                    delta = (emb2 - emb) * touched_ref[:, None]
                    emb2 = emb + ref_combine_rows(delta)
                state2.update(embed=emb2, emb_m=m2, emb_v=v2, emb_t=t2)
            return state2, dict(loss=loss, rows_pushed=pushed), logits

        self._ref_step = ref_step
        return ref_step

    # ------------------------------------------------------------------
    # serving: layer-wise full-graph inference (the throughput tier)
    # ------------------------------------------------------------------

    def make_infer_step(self):
        """The jitted layer-wise full-graph inference sweep: compute layer l
        for ALL vertices before layer l+1 — the production answer to neighbor
        explosion (embeddings for every vertex in O(L) exchange sweeps, no
        fanout blow-up).  Reuses the training exchange per layer (the
        family's ExchangeBackend under `_model_layer_local`: chunked
        double-buffered broadcast/p2p, ring scan, replica sync);
        layer-0 rows arrive as an ARGUMENT so the sweep reads the live
        FeatureStore (or a trainable state's embed table) without retracing.

        Inference is protocol-free: it serves fresh activations, never the
        async history (stale serving reads are a ROADMAP item-4 follow-up).
        """
        if self._infer_step is not None:
            return self._infer_step
        ax = self.axis
        c = self.cfg
        L = len(self.dims) - 1

        consts = dict(deg=self.deg)
        consts.update(self.playout.exchange_consts())
        shard = {key: P(*((ax,) + (None,) * (jnp.ndim(a) - 1)))
                 for key, a in consts.items()}

        def local_infer(params, X_local, consts_local):
            # squeeze the device axis off per-device plans (as in local_step)
            cl = dict(consts_local)
            for key in self.playout.squeeze_keys:
                cl[key] = cl[key][0]
            H = X_local
            for l, p_l in enumerate(params["layers"]):
                with jax.named_scope(f"layer{l}"):
                    H = self._model_layer_local(p_l, H, cl,
                                                last=(l == L - 1))
            return H

        smapped = shard_map(local_infer, mesh=self.mesh,
                            in_specs=(P(), P(ax, None), shard),
                            out_specs=P(ax, None), check_vma=False)

        @jax.jit
        def istep(params, X, consts_):
            return smapped(params, X, consts_)

        self._infer_consts = consts = self._place(consts, shard)
        self._jit_infer = istep
        self._infer_step = lambda params, X: istep(params, X, consts)
        return self._infer_step

    def _layer0_table(self, state=None):
        """Layer-0 rows for inference: the trainable embed table when the
        features are learnable, else a LIVE read through the FeatureStore
        (rows published via `store.update_rows` / `publish_embeddings` flow
        into the next sweep — no dense re-materialization, no retrace)."""
        if self.cfg.trainable_features:
            if state is None or "embed" not in state:
                raise ValueError(
                    "trainable_features: inference reads layer-0 rows from "
                    "the train state's embed table — pass state=")
            return state["embed"]
        return self.store.device_table()

    def infer_full_graph(self, state=None, *, params=None, reference=False):
        """Owner-partitioned final-layer embeddings for EVERY vertex, [Vp, C]
        (edge_cut: the contiguous relabeled blocks; vertex_cut: replica slots,
        masters authoritative — `global_embeddings` maps either back to the
        original vertex ids).  One call = one O(L) layer-wise sweep; wire
        bytes are accounted into CommStats.inference_bytes and cross-checked
        against `cost_models.inference_bytes_per_sweep` by the serving tier.

        `reference=True` runs the bitwise-independent single-device oracle
        (shared `_make_reference_layer` math) instead of the jitted
        distributed sweep."""
        if params is None:
            if state is None or "params" not in state:
                raise ValueError("infer_full_graph needs params= or a train "
                                 "state with a 'params' entry")
            params = state["params"]
        X = self._layer0_table(state)
        if reference:
            if self._ref_infer is None:
                layer_ref = self._make_reference_layer()
                L = len(self.dims) - 1

                @jax.jit
                def ref_infer(p, X_in):
                    H = X_in
                    for l, p_l in enumerate(p["layers"]):
                        H = layer_ref(p_l, H, last=(l == L - 1))
                    return H

                self._ref_infer = ref_infer
            # one device, as in train(reference=True)
            out = self._ref_infer(*jax.device_put(
                (params, X), self.mesh.devices.flat[0]))
            return jax.device_put(out, NamedSharding(self.mesh, P()))
        with self.telemetry.span("infer_sweep"):
            out = self.make_infer_step()(params, X)
            with self._account_exchange("inference", None, None):
                self.comm_stats.inference_bytes += \
                    self.inference_bytes_per_sweep()
        return out

    def inference_bytes_per_sweep(self) -> int:
        """Wire bytes of one layer-wise sweep — the engine-side mirror of
        `cost_models.inference_bytes_per_sweep` (forward-only: one exchange
        per layer at that layer's model-dependent width, nothing back).
        Exactly the layout's per-step wire fields summed: a sweep runs the
        same L exchange passes a training forward runs."""
        return int(sum(self._wire_fields.values()))

    def global_embeddings(self, H) -> np.ndarray:
        """Map owner-partitioned padded embeddings [Vp, D] back to the
        ORIGINAL vertex ids, [V, D] (layout-specific: edge_cut inverts the
        contiguous relabel; replica families read each vertex's master
        replica row)."""
        return self.playout.global_embeddings(np.asarray(H))

    def publish_embeddings(self, state) -> None:
        """Serving handoff for trainable features: write the trained layer-0
        rows back into the FeatureStore (and refresh any attached overlay
        snapshot), so engines/serving tiers built on this store — including a
        non-trainable clone — read the TRAINED table.  Host-side, out of the
        jitted path."""
        emb = np.asarray(state["embed"], np.float32)
        if emb.shape != (self.store.num_rows, self.store.dim):
            raise ValueError(f"embed table {emb.shape} != store "
                             f"{(self.store.num_rows, self.store.dim)}")
        self.store.update_rows(np.arange(self.store.num_rows), emb)
        if self.store._overlay_ids is not None:
            self.store.refresh_overlay()
            if getattr(self, "_cache_table", None) is not None:
                self._cache_table = jnp.asarray(self.store.overlay_table())
        self.X = self.store.device_table()

    # ------------------------------------------------------------------
    # mini-batch path (§5 batch generation wired into the jitted step)
    # ------------------------------------------------------------------

    def _build_minibatch_plan(self):
        """Static mini-batch plan: frontier caps from the fanout config (ONE
        jit compile per config), plus the per-device resident feature cache
        (remote hot rows picked by a sampling/cache.py policy; exact, never
        stale — input features are constant during training)."""
        c, g, k = self.cfg, self.g, self.k
        L = c.num_layers
        self.caps = frontier_caps(
            c.batching, L, c.batch_size, fanouts=c.fanouts,
            layer_sizes=c.layer_sizes, walk_length=c.walk_length,
            num_vertices=g.num_vertices)
        # p2p halo slots per (dst, src) pair: bounded by the MEASURED halo —
        # the largest single-owner share of any destination's hops-hop
        # in-neighborhood — instead of the worst case caps[0] (every frontier
        # row remote from one owner), which blows the all_to_all buffer up by
        # orders of magnitude at scale (ROADMAP follow-up from PR 2)
        self.fcap = self.caps[0]
        if c.execution == "p2p":
            hops = c.walk_length if c.batching == "subgraph" else c.num_layers
            self.fcap = p2p_frontier_halo_cap(g, self.part, hops, self.caps[0])
            # power-of-two installments over the measured halo cap (the PR-4
            # bucketing, applied to the frontier fetch): row t of a pair's
            # per-batch need list always lands in installment t // w at
            # offset t % w, so bucket occupancy varies per batch but the
            # lowered all_to_all operands stay [k, w] — static shapes, ONE
            # compile, send buffers ~buckets x smaller than the single
            # monolithic fcap buffer
            self.fcap_widths = bucketed_cap_widths(self.fcap, c.p2p_buckets)
        D = g.features.shape[1]
        self.Ccap = Ccap = max(int(c.cache_capacity), 1)
        self.cache_old_ids = []
        self._cache_slot = []  # per device: old global id -> cache row
        self._cache_set = []
        for d in range(k):
            ids_d = device_cache_ids(g, self.part.assignment, d,
                                     c.cache_policy, c.cache_capacity)
            self.cache_old_ids.append(ids_d)
            self._cache_slot.append({int(v): j for j, v in enumerate(ids_d)})
            self._cache_set.append(frozenset(int(v) for v in ids_d))
        # the cache is a hot-row OVERLAY on the feature store: per-device
        # pinned remote store rows.  Frozen features: a build-time snapshot
        # (exact forever).  Trainable: the snapshot would go stale, so the
        # jitted step re-gathers the overlay rows from the LIVE owner shards
        # every step through a static bucketed all_to_all plan (whose
        # transpose routes cache-hit gradients back to the owners).
        overlay_sids = [self.new_of_old[ids_d].astype(np.int64)
                        for ids_d in self.cache_old_ids]
        self.store.attach_overlay(overlay_sids, Ccap)
        self._cache_table = jnp.asarray(self.store.overlay_table())
        self._has_overlay = any(len(a) for a in overlay_sids)
        if c.trainable_features:
            if self._has_overlay:
                ov_send, ov_tab, self._ov_widths = overlay_refresh_plan(
                    overlay_sids, k, self.nb, Ccap, buckets=c.p2p_buckets)
                self._ov_send = jnp.asarray(ov_send)
                self._ov_tab = jnp.asarray(ov_tab)
            # touched-row cap: per owner, at most every one of its rows, and
            # at most one per frontier slot across all k devices
            self.tcap = min(self.nb, k * self.caps[0])
        # The host-side sample+extract stages live in a PICKLABLE numpy-only
        # builder: the engine delegates to it in-process, and the process
        # prefetcher (prefetch_mode="process") ships a copy (graph swapped
        # for a shared-memory handle) to each sampling worker — one code
        # path, so pooled epochs are bitwise-identical by construction.
        self.host_builder = HostBatchBuilder(
            batching=c.batching, execution=c.execution, seed=c.seed,
            batch_size=c.batch_size, fanouts=tuple(c.fanouts),
            layer_sizes=tuple(c.layer_sizes), walk_length=c.walk_length,
            num_layers=L, trainable_features=c.trainable_features,
            k=k, nb=self.nb, caps=tuple(int(x) for x in self.caps),
            fcap=int(self.fcap),
            fcap_widths=(tuple(int(x) for x in self.fcap_widths)
                         if c.execution == "p2p" else None),
            Ccap=Ccap, tcap=int(getattr(self, "tcap", 0)), feature_dim=D,
            assignment=self.part.assignment, new_of_old=self.new_of_old,
            labels=np.asarray(g.labels),
            train_mask=(None if g.train_mask is None
                        else np.asarray(g.train_mask)),
            cache_slots=self._cache_slot, cache_sets=self._cache_set,
            overlay_rows=tuple(len(a) for a in self.cache_old_ids),
            graph=g)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def enable_telemetry(self, telemetry: Optional[Telemetry] = None
                         ) -> Telemetry:
        """Attach an ENABLED `core.telemetry.Telemetry` (or the one passed
        in) and return it.  Spans wrap the host-side stage boundaries only —
        nothing inside the jitted step changes — and every CommStats
        mutation from here on is mirrored into labeled ``comm.*`` counters
        plus instant ``exchange`` spans carrying the wire-byte delta (their
        sum equals ``CommStats.total()`` exactly for a fresh run).  Also
        seeds the imbalance report with the static per-device layout gauges
        (owned edges/vertices, replica rows) and threads the instance into
        the FeatureStore's overlay counters."""
        tel = telemetry if telemetry is not None else Telemetry()
        self.telemetry = tel
        self.store.telemetry = tel
        if not tel.enabled:
            return tel
        self.playout.telemetry_gauges(tel)
        return tel

    @contextlib.contextmanager
    def _account_exchange(self, stage: str, step, device):
        """Mirror the CommStats deltas accrued inside this block into
        labeled ``comm.<field>`` counters and one instant ``exchange`` span
        whose ``bytes`` label is the WIRE delta (cache hits excluded) — the
        invariant the trace contract asserts: summed exchange-span bytes ==
        ``CommStats.total()``."""
        tel = self.telemetry
        if not tel.enabled:
            yield
            return
        s = self.comm_stats
        before = {f.name: getattr(s, f.name)
                  for f in dataclasses.fields(CommStats)}
        wire0 = s.total()
        yield
        labels = {} if device is None else {"device": device}
        for name, v0 in before.items():
            dv = getattr(s, name) - v0
            if dv:
                tel.counter("comm." + name, **labels).add(dv)
        mark = dict(stage=stage, bytes=s.total() - wire0, **labels)
        if step is not None:
            mark["step"] = step
        tel.instant("exchange", **mark)

    def _sample_host(self, step_idx: int):
        """Host sampling stage, delegated to the picklable
        `sampling.host_batch.HostBatchBuilder` (the same object the
        process-pool prefetcher ships to its workers, so in-process and
        pooled epochs run literally the same code).  Deterministic in
        (seed, step, device) so the oracle — and any rerun, in any process —
        regenerates bitwise-identical batches."""
        return self.host_builder.sample(
            step_idx, span_factory=self.telemetry.span)

    def _make_batch(self, mbs, step=None) -> Dict:
        """Extract stage: the builder pads/relabels/builds the fetch plan in
        numpy; `_finish_batch` ingests the result (CommStats + telemetry
        accounting, jnp conversion) — the same ingest the process-pooled
        epoch runs on arrays arriving from shared memory."""
        arrays, meta = self.host_builder.extract(mbs, step=step)
        return self._finish_batch(arrays, meta, step=step)

    def _finish_batch(self, arrays, meta, step=None) -> Dict:
        """Ingest one extracted batch: apply the per-device CommStats byte
        deltas inside `_account_exchange` (identical counters/spans whether
        the batch was built inline or by a worker process), mirror frontier
        occupancy + overlay hit/miss telemetry, and convert the flat numpy
        arrays to the jnp batch the jitted step consumes."""
        c, L = self.cfg, self.cfg.num_layers
        tel = self.telemetry
        for d, dd in enumerate(meta["per_device"]):
            with self._account_exchange("extract", step, d):
                for name, dv in dd["stats"].items():
                    setattr(self.comm_stats, name,
                            getattr(self.comm_stats, name) + dv)
            if tel.enabled:
                tel.gauge("frontier_occupancy", device=d).set(dd["occupancy"])
                self.store.count_overlay(
                    d, hits=dd["cache_hits"],
                    misses=dd["remote"] - dd["cache_hits"])
        batch = dict(
            frontier=jnp.asarray(arrays["frontier"]),
            y=jnp.asarray(arrays["y"]), w=jnp.asarray(arrays["w"]),
            adj=tuple(jnp.asarray(arrays[f"adj{l}"]) for l in range(L)),
            self_idx=tuple(jnp.asarray(arrays[f"self_idx{l}"])
                           for l in range(L)),
            cache_ids=jnp.asarray(arrays["cache_ids"]))
        for key in ("bc_ids", "ring_ids", "send_rows", "tab_ids", "emb_ids"):
            if key in arrays:
                batch[key] = jnp.asarray(arrays[key])
        return batch

    def sample_minibatch(self, step_idx: int) -> Dict:
        """sample + extract: one static-shape device batch for `step_idx`."""
        tel = self.telemetry
        with tel.span("sample", step=step_idx):
            mbs = self._sample_host(step_idx)
        with tel.span("extract", step=step_idx):
            return self._make_batch(mbs, step=step_idx)

    def _check_minibatch_runnable(self):
        """Validate the config ONCE at epoch entry: the constructor already
        rejects mini-batch + async-history configs, but a config mutated
        after construction (or an engine driven past a stale reference)
        would otherwise die deep inside jit with an opaque shape error."""
        c = self.cfg
        if c.batching == "full_graph":
            raise ValueError(
                "batching='full_graph' has no mini-batch epoch; use train() "
                "/ make_step(), or rebuild the engine with a sampled "
                "batching mode (node_wise | layer_wise | subgraph)")
        if c.protocol != "sync":
            raise ValueError(
                f"mini-batch training supports protocol='sync' only, but "
                f"this engine's config now has protocol={c.protocol!r} "
                f"(changed after construction?).  The historical-embedding "
                f"protocols keep full-graph state that sampled batches "
                f"cannot refresh — rebuild the engine with protocol='sync', "
                f"or use batching='full_graph' to train with "
                f"{c.protocol!r}.")

    def init_minibatch_state(self, key=None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(self.cfg.seed)
        state = dict(params=init_gnn_params(self.cfg.model, self.dims, key),
                     step=jnp.zeros((), jnp.int32))
        # Pre-place replicated, matching the step's output sharding — so
        # feeding the state back in reuses the ONE compiled executable
        # (the recompile-count contract in tests/test_engine_minibatch.py).
        state = jax.device_put(state, NamedSharding(self.mesh, P()))
        if self.cfg.trainable_features:
            # layer-0 rows are parameters: the store table plus owner-sharded
            # sparse-AdamW moments and per-row step counts
            mat = NamedSharding(self.mesh, P(self.axis, None))
            row = NamedSharding(self.mesh, P(self.axis))
            state["embed"] = jax.device_put(self.X, mat)
            state["emb_m"] = jax.device_put(jnp.zeros_like(self.X), mat)
            state["emb_v"] = jax.device_put(jnp.zeros_like(self.X), mat)
            state["emb_t"] = jax.device_put(
                jnp.zeros((self.Vp,), jnp.int32), row)
        return state

    def _overlay_rows_live(self, X_local, cl):
        """Re-gather this device's overlay rows from the LIVE owner shards
        (trainable_features): the static bucketed all_to_all refresh plan —
        one extra exchange per step whose transpose routes cache-hit
        gradients back to the owners' embedding shards."""
        recv = bucketed_all_to_all(X_local, cl["ov_send"], self.axis, self.k)
        tab = jnp.concatenate([X_local, recv, zero_pad_row(X_local)], 0)
        return jnp.take(tab, cl["ov_tab"], axis=0)  # [Ccap, D]

    def _fetch_frontier(self, X_local, cache_rows, bl):
        """Device-local frontier feature fetch under shard_map: resident-cache
        reads plus the execution-model exchange for the misses.  Every valid
        frontier slot is covered by exactly one of the two (the other reads a
        zero row), so the sum is exact.  ``cache_rows`` is the [Ccap, D]
        overlay table (the static snapshot, or the live-refreshed rows under
        trainable_features), or None when no cache is configured.  The
        broadcast/p2p exchanges are feature-chunked like the full-graph
        backend aggregate when ``exchange_chunks`` > 1 (the frontier
        gather consumes chunk c while chunk c+1's collective flies)."""
        ax, k, nb = self.axis, self.k, self.nb
        C = self.cfg.exchange_chunks
        D = X_local.shape[1]
        if cache_rows is None:
            F = jnp.zeros((bl["cache_ids"].shape[0], D), X_local.dtype)
        else:
            ctab = jnp.concatenate(
                [cache_rows, zero_pad_row(cache_rows)], 0)
            F = jnp.take(ctab, bl["cache_ids"], axis=0)
        if self.cfg.execution == "broadcast":
            def exchange(hc):
                with jax.named_scope("exchange"):
                    h_full = jax.lax.all_gather(hc, ax, axis=0, tiled=True)
                return jnp.concatenate([h_full, zero_pad_row(hc)], 0)

            return F + chunked_overlap(
                X_local, C, exchange,
                lambda tab: jnp.take(tab, bl["bc_ids"], axis=0))
        if self.cfg.execution == "ring":
            me = jax.lax.axis_index(ax)
            # the zero pad row is concatenated ONCE and rotates with the
            # block (every device appends zeros, so slot nb stays zero)
            tab0 = jnp.concatenate([X_local, zero_pad_row(X_local)], 0)

            def ring_step(carry, r):
                acc, tab_cur = carry
                owner = (me + r) % k
                ids_r = jnp.take(bl["ring_ids"], owner, axis=0)
                acc = acc + jnp.take(tab_cur, ids_r, axis=0)
                with jax.named_scope("exchange"):
                    tab_nxt = jax.lax.ppermute(
                        tab_cur, ax, [(i, (i - 1) % k) for i in range(k)])
                return (acc, tab_nxt), None

            acc0 = jnp.zeros((bl["cache_ids"].shape[0], D), X_local.dtype)
            (acc, _), _ = jax.lax.scan(ring_step, (acc0, tab0),
                                       jnp.arange(k))
            return F + acc

        # p2p: ship only the rows each destination's misses actually need,
        # in the power-of-two bucketed installments (send operand [k, w]
        # per round instead of one monolithic [k, fcap] buffer)
        def exchange(hc):
            recv = bucketed_all_to_all(hc, bl["send_rows"], ax, k)
            return jnp.concatenate([hc, recv, zero_pad_row(hc)], 0)

        return F + chunked_overlap(
            X_local, C, exchange,
            lambda tab: jnp.take(tab, bl["tab_ids"], axis=0))

    def make_minibatch_step(self):
        """The jitted distributed mini-batch step: (state, batch) ->
        (state, metrics, target logits [k, cap_L, C]).  Batch arrays have
        static shapes from the fanout caps, so this compiles exactly once."""
        if self._mb_step is not None:
            return self._mb_step
        if self.cfg.batching == "full_graph":
            raise ValueError("batching='full_graph' has no mini-batch step; "
                             "use make_step()")
        ax, c, k, L = self.axis, self.cfg, self.k, self.cfg.num_layers

        if c.trainable_features:
            # the feature plane lives in STATE (store rows are parameters);
            # the cache snapshot is replaced by the live overlay refresh plan
            consts, cshard = {}, {}
            if self._has_overlay:
                consts["ov_send"] = self._ov_send
                consts["ov_tab"] = self._ov_tab
                cshard["ov_send"] = P(ax, None, None, None)
                cshard["ov_tab"] = P(ax, None)
        else:
            consts = dict(X=self.X, cache=self._cache_table)
            cshard = dict(X=P(ax, None), cache=P(ax, None, None))
        bspec = dict(frontier=P(ax, None), y=P(ax, None), w=P(ax, None),
                     adj=tuple(P(ax, None, None) for _ in range(L)),
                     self_idx=tuple(P(ax, None) for _ in range(L)),
                     cache_ids=P(ax, None))
        if c.execution == "broadcast":
            bspec["bc_ids"] = P(ax, None)
        elif c.execution == "ring":
            bspec["ring_ids"] = P(ax, None, None)
        else:
            bspec["send_rows"] = P(ax, None, None, None)
            bspec["tab_ids"] = P(ax, None)
        state_spec = dict(params=P(), step=P())
        if c.trainable_features:
            bspec["emb_ids"] = P(ax, None)
            state_spec.update(embed=P(ax, None), emb_m=P(ax, None),
                              emb_v=P(ax, None), emb_t=P(ax))
        nb = self.nb

        def local_step(state, consts_local, batch_local):
            params, step_i = state["params"], state["step"]
            bl = {key: (tuple(a[0] for a in v) if isinstance(v, tuple)
                        else v[0]) for key, v in batch_local.items()}
            if c.trainable_features:
                cl = {key: consts_local[key][0] for key in consts_local}

                # the fetch moves INSIDE the differentiated function: the
                # collectives' transposes route each frontier row's cotangent
                # back to its owner's embedding shard (all_gather ->
                # psum_scatter, ppermute -> inverse ppermute, all_to_all ->
                # reversed all_to_all), so g_X arrives pre-summed across
                # devices — the owner's TOTAL gradient, no extra psum
                def num_fn(p, X_l):
                    cache_rows = (self._overlay_rows_live(X_l, cl)
                                  if self._has_overlay else None)
                    F = self._fetch_frontier(X_l, cache_rows, bl)
                    logits = padded_minibatch_forward(
                        p, list(bl["adj"]), F, model=c.model,
                        self_idx=list(bl["self_idx"]))
                    with jax.named_scope("loss"):
                        num = _xent_numerator(logits, bl["y"], bl["w"])
                    return num, logits

                (num, logits), (grads, g_X) = jax.value_and_grad(
                    num_fn, argnums=(0, 1), has_aux=True)(
                        params, state["embed"])
            else:
                X_l = consts_local["X"]
                cache_l = consts_local["cache"][0]
                F = self._fetch_frontier(X_l, cache_l, bl)
                # Differentiate the LOCAL loss numerator only (same rationale
                # as the full-graph step); the fetch above is outside the
                # grad, so the grad path is collective-free and portable.
                def num_fn(p):
                    logits = padded_minibatch_forward(
                        p, list(bl["adj"]), F, model=c.model,
                        self_idx=list(bl["self_idx"]))
                    with jax.named_scope("loss"):
                        num = _xent_numerator(logits, bl["y"], bl["w"])
                    return num, logits

                (num, logits), grads = jax.value_and_grad(
                    num_fn, has_aux=True)(params)
            loss, den, grads = _sync_loss_and_grads(num, bl["w"], grads, ax)
            with jax.named_scope("sgd"):
                params2 = jax.tree_util.tree_map(
                    lambda p_, g_: p_ - c.lr * g_, params, grads)
                state2 = dict(params=params2, step=step_i + 1)
                if c.trainable_features:
                    # scatter-update ONLY this owner's touched rows: emb_ids
                    # row d (sorted distinct local rows any device's
                    # frontier read, sentinel nb) against the pre-summed
                    # owner gradient
                    ids = bl["emb_ids"]
                    g_rows = jnp.take(
                        g_X, jnp.where(ids < nb, ids, 0), axis=0) / den
                    emb2, m2, v2, t2 = sparse_adamw_ids(
                        state["embed"], state["emb_m"], state["emb_v"],
                        state["emb_t"], ids, g_rows, valid=ids < nb,
                        **self._embed_hparams())
                    state2.update(embed=emb2, emb_m=m2, emb_v=v2, emb_t=t2)
            return state2, dict(loss=loss), logits[None]

        smapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_spec, cshard, bspec),
            out_specs=(state_spec, dict(loss=P()), P(ax, None, None)),
            check_vma=False)

        @jax.jit
        def step(state, consts_, batch):
            return smapped(state, consts_, batch)

        self._mb_consts = self._place(consts, cshard)
        self._jit_mb_step = step
        self._mb_step = lambda state, batch: step(state, self._mb_consts, batch)
        return self._mb_step

    def lower_minibatch_step(self, state=None, batch=None):
        """Lower (without running) the mini-batch step — dry-runs at scale."""
        self.make_minibatch_step()
        state = state if state is not None else self.init_minibatch_state()
        batch = batch if batch is not None else self.sample_minibatch(0)
        return self._jit_mb_step.lower(state, self._mb_consts, batch)

    def make_reference_minibatch_step(self):
        """Single-device oracle: the identical padded batches, features read
        straight from the global table, forward vmapped over the k device
        blocks — multi-device runs must match to float tolerance."""
        if self._mb_ref_step is not None:
            return self._mb_ref_step
        c = self.cfg
        k, nb = self.k, self.nb
        D = self.g.features.shape[1]
        zrow = jnp.zeros((1, D), self.X.dtype)
        table0 = jnp.concatenate([self.X, zrow], 0)

        def batch_loss(p, F, batch):
            logits = jax.vmap(
                lambda f, adjs, sidx: padded_minibatch_forward(
                    p, list(adjs), f, model=c.model, self_idx=list(sidx))
            )(F, batch["adj"], batch["self_idx"])
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(
                logits, batch["y"][..., None], axis=-1)[..., 0]
            w = batch["w"]
            loss = ((lse - ll) * w).sum() / jnp.maximum(w.sum(), 1.0)
            return loss, logits

        if c.trainable_features:
            # dense [Vp, D] oracle embedding: fetch through the live table
            # inside the grad, then sparse-AdamW over the batch's global
            # touched ids — row (s, j) of emb_ids maps to flat id s*nb + j
            offsets = jnp.asarray(
                (np.arange(k) * nb)[:, None], jnp.int32)

            @jax.jit
            def ref_step(state, batch):
                params, step_i = state["params"], state["step"]

                def loss_fn(p, emb):
                    table = jnp.concatenate([emb, zrow], 0)
                    F = jnp.take(table, batch["frontier"], axis=0)
                    return batch_loss(p, F, batch)

                (loss, logits), (grads, g_E) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(
                        params, state["embed"])
                params2 = jax.tree_util.tree_map(
                    lambda p_, g_: p_ - c.lr * g_, params, grads)
                valid = (batch["emb_ids"] < nb).reshape(-1)
                ids = (offsets + batch["emb_ids"]).reshape(-1)
                g_rows = jnp.take(
                    g_E, jnp.where(valid, ids, 0), axis=0)
                emb2, m2, v2, t2 = sparse_adamw_ids(
                    state["embed"], state["emb_m"], state["emb_v"],
                    state["emb_t"], ids, g_rows, valid=valid,
                    **self._embed_hparams())
                return (dict(params=params2, step=step_i + 1, embed=emb2,
                             emb_m=m2, emb_v=v2, emb_t=t2),
                        dict(loss=loss), logits)
        else:
            @jax.jit
            def ref_step(state, batch):
                params, step_i = state["params"], state["step"]
                F = jnp.take(table0, batch["frontier"], axis=0)  # [k,cap0,D]

                (loss, logits), grads = jax.value_and_grad(
                    batch_loss, has_aux=True)(params, F, batch)
                params2 = jax.tree_util.tree_map(
                    lambda p_, g_: p_ - c.lr * g_, params, grads)
                return (dict(params=params2, step=step_i + 1),
                        dict(loss=loss), logits)

        self._mb_ref_step = ref_step
        return ref_step

    def _ensure_proc_pool(self, depth: int):
        """The engine's persistent sampling-process pool (prefetch_mode=
        'process'), built lazily and reused across epochs: graph CSR arrays
        go to shared memory once, workers run a pickled-then-forked copy of
        `self.host_builder` whose ``graph`` is the shm handle (attached
        read-only at worker init), finished batches come back through the
        shared-memory ring.  Rebuilt if depth/num_workers change."""
        from repro.core.sampling.proc_prefetch import (
            ProcPrefetchPool,
            share_graph,
        )
        key = (int(depth), int(self.cfg.num_sample_workers))
        pool = getattr(self, "_proc_pool", None)
        if pool is not None and pool.alive and self._proc_pool_key == key:
            return pool
        self.close_prefetch_pool()
        shared, arena = share_graph(self.host_builder._g())
        builder = dataclasses.replace(self.host_builder, graph=shared)
        self._proc_pool = ProcPrefetchPool(
            builder.produce, self.host_builder.array_layout(),
            depth=key[0], num_workers=key[1], telemetry=self.telemetry,
            shared_inputs=(arena,))
        self._proc_pool_key = key
        return self._proc_pool

    def close_prefetch_pool(self) -> None:
        """Stop the sampling processes and unlink their shared memory.
        Idempotent; safe to call with no pool built."""
        pool = getattr(self, "_proc_pool", None)
        if pool is not None:
            pool.close()
            self._proc_pool = None

    def run_epoch_minibatch(self, num_batches: int, schedule: str = "conventional",
                            state=None, reference: bool = False,
                            prefetch_depth: Optional[int] = None,
                            prefetch_mode: Optional[str] = None):
        """Drive the §6.1 mini-batch execution schedules (conventional /
        factored / operator_parallel / pipelined) with the engine's REAL
        stages: host sampling, padded-batch extraction (+fetch-plan build),
        and the jitted train step.  Returns (state, losses, StageTimes).

        ``schedule="pipelined"`` runs the double-buffered sampler for real: a
        background `PrefetchWorker` thread samples/extracts batch i+1
        (bounded ``prefetch_depth`` ahead, default cfg.prefetch_depth) while
        the trainer lane dispatches step i WITHOUT blocking on the device —
        losses are synced once at epoch end, so the jitted step, the
        host->device transfer, and host sampling genuinely overlap.  Batches
        stay deterministic in (seed, step, device): the pipelined epoch is
        bitwise-identical to the blocking schedules (state, losses, and
        CommStats), just faster on the wall.

        ``prefetch_mode`` (default cfg.prefetch_mode) picks the pipelined
        producer: "thread" shares this process's GIL; "process" runs
        sample+extract in a persistent `ProcPrefetchPool` of
        ``cfg.num_sample_workers`` worker processes over a shared-memory
        batch ring (sampling/proc_prefetch.py) — the GIL-free data plane,
        same bitwise guarantee.  The pool is reused across epochs; call
        `close_prefetch_pool()` when done (GC also reclaims it).

        A fresh run (state=None) resets self.comm_stats like train();
        passing a state in continues accumulating."""
        from repro.core.execution.minibatch_pipeline import (
            SCHEDULES,
            run_pipelined,
            run_pipelined_process,
        )
        self._check_minibatch_runnable()
        step = (self.make_reference_minibatch_step() if reference
                else self.make_minibatch_step())
        if state is None:
            self.comm_stats.reset()
        holder = dict(state=state if state is not None
                      else self.init_minibatch_state())
        pipelined = schedule == "pipelined"
        tel = self.telemetry
        losses: List = []

        def train_fn(mbs, batch):
            holder["state"], metrics, _ = step(holder["state"], batch)
            # pipelined lane: keep the dispatch async — float() here would
            # block the trainer on the device step and kill the overlap
            losses.append(metrics["loss"] if pipelined
                          else float(metrics["loss"]))
            tel.log_step(step=len(losses) - 1, schedule=schedule,
                         comm_total_bytes=self.comm_stats.total())

        batch_ids = list(range(num_batches))
        # items carry their step index so the extract stage can label its
        # exchange spans (train_fn never looks inside mbs)
        sample_fn = lambda i: (int(i), self._sample_host(int(i)))  # noqa: E731
        extract_fn = lambda si: self._make_batch(si[1], step=si[0])  # noqa: E731
        if pipelined:
            depth = (self.cfg.prefetch_depth if prefetch_depth is None
                     else prefetch_depth)
            mode = (self.cfg.prefetch_mode if prefetch_mode is None
                    else prefetch_mode)
            if mode not in ("thread", "process"):
                raise ValueError(
                    "prefetch_mode must be 'thread' or 'process'")
            if mode == "process":
                # GIL-free lane: workers already ran sample+extract; here we
                # fold their byte deltas into comm_stats, assemble the jnp
                # batch, and dispatch — still async, synced at epoch end
                def train_fn_proc(item, arrays, meta):
                    batch = self._finish_batch(arrays, meta, step=item)
                    train_fn(None, batch)

                times = run_pipelined_process(
                    batch_ids, self._ensure_proc_pool(depth), train_fn_proc,
                    finalize_fn=lambda: jax.block_until_ready(
                        holder["state"]),
                    telemetry=tel)
            else:
                times = run_pipelined(
                    batch_ids, sample_fn, extract_fn, train_fn,
                    prefetch_depth=depth,
                    finalize_fn=lambda: jax.block_until_ready(
                        holder["state"]),
                    telemetry=tel)
            losses = [float(l) for l in losses]
        else:
            times = SCHEDULES[schedule](
                batch_ids, sample_fn, extract_fn, train_fn, telemetry=tel)
        return holder["state"], losses, times

    def minibatch_accuracy(self, logits, batch) -> float:
        """Accuracy over the batch's weighted (owned train) targets."""
        correct = (jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32)
        w = batch["w"]
        return float((correct * w).sum() / jnp.maximum(w.sum(), 1.0))

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------

    def train(self, epochs: int, reference: bool = False
              ) -> Tuple[List[float], jnp.ndarray]:
        """Run `epochs` steps; returns (losses, final logits) — logits are
        [Vp, C] for full-graph batching, [k, cap_L, C] target logits for the
        mini-batch modes.  Mini-batch runs reset and accumulate
        self.comm_stats (feature fetch bytes, cache hits)."""
        tel = self.telemetry
        if self.cfg.batching != "full_graph":
            self._check_minibatch_runnable()
            step = (self.make_reference_minibatch_step() if reference
                    else self.make_minibatch_step())
            state = self.init_minibatch_state()
            self.comm_stats.reset()
            losses: List[float] = []
            logits = None
            for i in range(epochs):
                batch = self.sample_minibatch(i)
                with tel.span("train", step=i):
                    state, metrics, logits = step(state, batch)
                    losses.append(float(metrics["loss"]))
                tel.log_step(step=i, loss=losses[-1],
                             comm_total_bytes=self.comm_stats.total())
            return losses, logits
        step = self.make_reference_step() if reference else self.make_step()
        state = self.init_state()
        if reference:
            # the oracle runs on one device: a state left on a multi-device
            # mesh would partition its Pallas kernels, which Mosaic refuses
            state = jax.device_put(state, self.mesh.devices.flat[0])
        if not reference and (self._wire_fields
                              or self.cfg.trainable_features):
            self.comm_stats.reset()
        losses = []
        logits = None
        for i in range(epochs):
            with tel.span("train", step=i):
                state, metrics, logits = step(state)
                losses.append(float(metrics["loss"]))
            if not reference:
                with self._account_exchange("full_graph", i, None):
                    for name, b in self._wire_fields.items():
                        setattr(self.comm_stats, name,
                                getattr(self.comm_stats, name) + b)
                    if self.cfg.trainable_features:
                        self.comm_stats.embed_grad_bytes += \
                            self._emb_bytes_per_step
                tel.log_step(step=i, loss=losses[-1],
                             comm_total_bytes=self.comm_stats.total())
        if reference:
            # hand the oracle's logits back on the mesh, comparable with the
            # distributed step's
            logits = jax.device_put(logits, NamedSharding(self.mesh, P()))
        return losses, logits

    def accuracy(self, logits, split: str = "test") -> float:
        w = self.test_w if split == "test" else self.train_w
        correct = (jnp.argmax(logits, -1) == self.y).astype(jnp.float32)
        return float((correct * w).sum() / jnp.maximum(w.sum(), 1.0))
