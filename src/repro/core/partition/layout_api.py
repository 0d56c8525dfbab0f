"""PartitionLayout: the partition-family interface (survey §4.2 made a
first-class axis).

A *layout* owns everything a partition family decides about how a graph
lands on k devices — the engine only dispatches:

  * the slot tables (who owns which padded row, how vertices relabel or
    replicate) and the local-multiply ELL constants (`ids`/`mask`/`deg`);
  * the exchange-plan constants the execution model needs (`send_rows`,
    replica-sync tables, halo tables) via `exchange_consts()` — the engine
    derives every shard spec generically (`P(ax, None, ...)` from ndim) and
    squeezes the leading device axis off the keys named in `squeeze_keys`;
  * master masking for loss/grads (`train_w`/`test_w`/`emb_touched` are
    built HERE, already masked);
  * the reference-oracle combine: `ref_vert_ids` is None for families whose
    padded rows are globally unique, else the [k, n] global-vertex table the
    oracle scatter-adds partials over (replica families);
  * per-step byte accounting (`wire_fields_per_step`, `embed_grad_bytes`,
    `device_bytes_per_step`), telemetry gauges, and the host-side mapping
    back to original vertex ids (`global_embeddings`).

Extension policy — what a FOURTH family must implement
------------------------------------------------------
1. Subclass `PartitionLayout` (or `ReplicaLayoutBase` if the family keeps
   replica slot tables), set `family`, and implement `_build` to populate
   the engine-facing attributes listed in `ENGINE_MIRROR_ATTRS` that apply
   (at minimum: nb, Vp, K, ids_exec, ids_global, mask, deg, store, X,
   emb_touched, y, train_w, test_w, bmask).
2. Implement `exchange_consts()` (must include "ids" and "mask") and set
   `squeeze_keys` to the const keys whose LEADING axis is the device axis
   of stacked per-device tables (they arrive [1, ...] under shard_map and
   are squeezed); leading-[Vp] consts shard naturally and are not listed.
3. Implement the accounting quartet (`wire_fields_per_step` names which
   CommStats fields the family accrues per full-graph step — the engine
   adds exactly these, so the cost-model cross-check tests stay exact),
   `telemetry_gauges`, and `global_embeddings`.
4. Pick an execution backend in `execution/exchange_api.py` (edge-cut halo
   vs replica-sync GAS — or compose both, as the hybrid family does, via
   the `sync_active`/`halo_active` flags `ReplicaSyncBackend` reads).
5. Register the class in `LAYOUT_BUILDERS` and add the family string to
   `engine.PARTITION_FAMILIES`; the oracle tiers then apply unchanged
   (`ref_vert_ids` drives the reference combine automatically).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.execution.pipeline_exchange import (
    bucketed_cap_widths,
    bucketed_send_table,
    halo_slot,
)
from repro.core.execution.replica_sync import build_replica_sync_plan
from repro.core.feature_store import FeatureStore
from repro.core.partition.cost_models import (
    FEAT_BYTES,
    edge_cut_halo_device_bytes,
    model_exchange_widths,
    replica_sync_device_bytes,
)
from repro.core.partition.edge_cut import PARTITIONERS, Partition
from repro.core.partition.vertex_cut import VERTEX_CUTS
from repro.core.partition.vertex_layout import build_vertex_layout
from repro.core.telemetry import NULL_TELEMETRY

# Engine attributes a layout may provide; DistGNNEngine mirrors every one
# that exists (hasattr) so downstream code (mini-batch planner, dryrun
# drivers, the streaming-partition equality tier) keeps reading eng.<attr>.
ENGINE_MIRROR_ATTRS = (
    "part", "new_of_old", "vcut", "layout", "nb", "nv", "Vp", "K",
    "ids_global", "mask", "mask_exec", "deg", "store", "X", "emb_touched",
    "y", "train_w", "test_w", "bmask", "ids_exec", "cap", "p2p_widths",
    "send_rows", "_halo_rows", "_vc_rows_per_layer", "_vc_p2p_caps",
    "_vc_plan",
)


class PartitionLayout:
    """Base class — see the module docstring for the extension policy."""

    family = "abstract"
    has_replicas = False          # replica slot tables + master masking?
    supports_minibatch = False    # §5 sampled batching available?
    ref_vert_ids = None           # [k, n] np global-vertex table (pad = V)
    #   for the oracle's scatter-add replica combine; None = rows unique
    squeeze_keys: tuple = ()      # exchange consts to squeeze [0] under map

    def __init__(self, g, k: int, cfg, partition=None, telemetry=None):
        self.g = g
        self.k = k
        self.cfg = cfg
        # the build's stages are spans: layout.partition, .vertex_blocks,
        # .store (the FeatureStore and the device copies), .exchange_plan
        self.tel = NULL_TELEMETRY if telemetry is None else telemetry
        self._build(partition)

    @classmethod
    def validate(cls, cfg, partition=None) -> None:
        """Raise ValueError for configs this family cannot run."""

    def _build(self, partition) -> None:
        raise NotImplementedError

    def exchange_consts(self) -> dict:
        """Static jnp constants the device-local exchange reads (always
        includes "ids" and "mask"; plan extras ride alongside)."""
        raise NotImplementedError

    def wire_fields_per_step(self, model: str, dims) -> dict:
        """CommStats field name -> wire bytes ONE full-graph train step
        accrues on that field.  The engine adds exactly these per step (and
        their sum per inference sweep), so each entry must mirror the
        standalone cost model for this family bit for bit."""
        raise NotImplementedError

    def embed_grad_bytes(self, dims) -> int:
        """Wire bytes/step for routing layer-0 embedding gradients home
        (trainable_features) — the transpose of one width-dims[0] pass."""
        raise NotImplementedError

    def device_bytes_per_step(self, model: str, dims) -> np.ndarray:
        """[k] per-device bytes/step, both directions — max() is the
        critical-path volume the autotuner minimizes."""
        raise NotImplementedError

    def telemetry_gauges(self, tel) -> None:
        """Seed per-device static layout gauges for the imbalance report."""
        raise NotImplementedError

    def global_embeddings(self, H: np.ndarray) -> np.ndarray:
        """Map padded per-slot rows [Vp, D] back to original ids [V, D]."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# edge_cut: a partitioner assigns VERTICES; contiguous relabeled blocks +
# halo exchange (the neighbor rows cross the wire)
# ---------------------------------------------------------------------------


class EdgeCutLayout(PartitionLayout):
    family = "edge_cut"
    supports_minibatch = True

    def _build(self, partition):
        span = self.tel.span
        with span("layout.partition"):
            if partition is None and self.k == 1:  # one part: nothing to cut
                partition = Partition(np.zeros(self.g.num_vertices, np.int32),
                                      1)
            self.part = (partition or
                         PARTITIONERS[self.cfg.partitioner](self.g, self.k))
        with span("layout.vertex_blocks"):
            host = self._build_vertex_blocks()
        with span("layout.store"):
            self._place_vertex_blocks(*host)
        with span("layout.exchange_plan"):
            self._build_exchange_plan()
        if self.cfg.execution == "ring":
            self.squeeze_keys = ("ids", "mask")
        elif self.cfg.execution == "p2p":
            self.squeeze_keys = ("send_rows",)

    def _build_vertex_blocks(self):
        """Relabel vertices so partition p owns global rows [p*nb, (p+1)*nb).
        Pad slots are dead: no edges, zero features/weights.  Returns the
        host arrays `_place_vertex_blocks` puts on the device."""
        g, k = self.g, self.k
        assign = self.part.assignment
        sizes = np.bincount(assign, minlength=k)
        self.nb = nb = max(int(sizes.max()), 1)
        self.Vp = Vp = k * nb
        old_by_part = [np.where(assign == p)[0] for p in range(k)]
        new_of_old = np.full(g.num_vertices, -1, np.int64)
        for p, olds in enumerate(old_by_part):
            new_of_old[olds] = p * nb + np.arange(len(olds))
        self.new_of_old = new_of_old
        D = g.features.shape[1]
        X = np.zeros((Vp, D), np.float32)
        y = np.zeros((Vp,), np.int32)
        train_w = np.zeros((Vp,), np.float32)
        test_w = np.zeros((Vp,), np.float32)
        olds = np.arange(g.num_vertices)
        X[new_of_old[olds]] = g.features[olds]
        y[new_of_old[olds]] = g.labels[olds]
        if g.train_mask is not None:
            train_w[new_of_old[olds]] = g.train_mask[olds].astype(np.float32)
        if g.test_mask is not None:
            test_w[new_of_old[olds]] = g.test_mask[olds].astype(np.float32)
        # ELL adjacency in new ids; pad id = Vp (zero row in gather tables)
        deg = g.degree()
        self.K = K = max(int(deg.max()), 1)
        ids = np.full((Vp, K), Vp, np.int64)
        mask = np.zeros((Vp, K), np.float32)
        for old_v in range(g.num_vertices):
            v = new_of_old[old_v]
            nbs = new_of_old[g.neighbors(old_v)]
            ids[v, : len(nbs)] = nbs
            mask[v, : len(nbs)] = 1.0
        self.ids_global = ids
        # full-graph touched set for trainable embeddings: every REAL owned
        # row is in the batch (pads stay untouched forever)
        real = np.zeros((Vp,), np.float32)
        real[new_of_old[olds]] = 1.0
        self.emb_touched = real
        # boundary: rows read by at least one remote partition
        owner = ids // nb  # partition of each neighbor (pad -> k)
        bmask = np.zeros((Vp,), bool)
        row_part = np.repeat(np.arange(self.k), nb)
        remote = (mask > 0) & (owner != row_part[:, None])
        src = ids[remote]
        bmask[src[src < Vp]] = True
        return X, y, train_w, test_w, mask, bmask

    def _place_vertex_blocks(self, X, y, train_w, test_w, mask, bmask):
        k, nb, D = self.k, self.nb, X.shape[1]
        self.mask = jnp.asarray(mask)
        degp = np.maximum(mask.sum(1, keepdims=True), 1.0).astype(np.float32)
        self.deg = jnp.asarray(degp)
        # the feature plane lives in an owner-partitioned store: flat store
        # id == the relabeled vertex id (owner * nb + slot), so the exchange
        # plans move store rows without any translation
        self.store = FeatureStore(X.reshape(k, nb, D))
        self.X = self.store.device_table()
        self.y = jnp.asarray(y)
        self.train_w = jnp.asarray(train_w)
        self.test_w = jnp.asarray(test_w)
        self.bmask = jnp.asarray(bmask)

    def _build_exchange_plan(self):
        """Execution-model-specific static arrays (the §7 protocol plan)."""
        k, nb, Vp, K = self.k, self.nb, self.Vp, self.K
        ids = self.ids_global
        if self.cfg.execution == "broadcast":
            # gather table per device = all_gather(H) [Vp] + zero row at Vp
            self.ids_exec = jnp.asarray(ids.astype(np.int32))
            return
        if self.cfg.execution == "ring":
            # per (dst row, src block): neighbor ids local to the src block.
            # Pad slots carry id 0 with mask 0 — the masked ELL reduction
            # zeroes them, so the scan needs NO per-round zero-row
            # concatenate onto the rotating block.
            ids_by_src = np.zeros((Vp, k, K), np.int32)
            src_part = np.where(ids < Vp, ids // nb, -1)
            local_id = np.where(ids < Vp, ids % nb, 0)
            for s in range(k):
                sel = src_part == s  # [Vp, K]
                ids_by_src[:, s][sel] = local_id[sel]
            # reshape to [k(dev), nb, k(src), K] so P(ax) shards devices
            self.ids_exec = jnp.asarray(
                ids_by_src.reshape(k, nb, k, K).transpose(0, 2, 1, 3))
            mask_np = np.asarray(self.mask)
            mask_by_src = np.zeros((Vp, k, K), np.float32)
            for s in range(k):
                mask_by_src[:, s] = mask_np * (src_part == s)
            self.mask_exec = jnp.asarray(
                mask_by_src.reshape(k, nb, k, K).transpose(0, 2, 1, 3))
            return
        # p2p halo exchange plan: need[dst, src] = sorted local indices (within
        # src block) of src rows that dst's aggregation reads
        need_sets = [[np.zeros(0, np.int64) for _ in range(k)]
                     for _ in range(k)]
        src_part = np.where(ids < Vp, ids // nb, -1)
        local_id = np.where(ids < Vp, ids % nb, 0)
        for d in range(k):
            rows = slice(d * nb, (d + 1) * nb)
            for s in range(k):
                if s == d:
                    continue
                sel = src_part[rows] == s
                need_sets[d][s] = np.unique(local_id[rows][sel])
        cap = max(1, max((len(x) for row in need_sets for x in row),
                         default=1))
        self.cap = cap
        # true halo rows per layer-0-width pass (== part.communication_volume:
        # each need set is one partition's remote in-neighbor set) — the
        # trainable-embedding gradient transpose ships exactly these rows back
        self._halo_rows = sum(len(x) for row in need_sets for x in row)
        # power-of-two bucketed installment caps (1 bucket = the classic
        # max-pairwise-need buffer): each lowered all_to_all operand holds
        # k*w rows instead of k*cap, shipping the same rows over B rounds
        widths = bucketed_cap_widths(cap, self.cfg.p2p_buckets)
        self.p2p_widths = widths
        B, w = len(widths), widths[0]
        # send_rows[src, B, dst, w]: what each SOURCE ships per installment
        # and destination (need_sets is dst-major; the builder wants
        # src-major need[s][d])
        self.send_rows = jnp.asarray(bucketed_send_table(
            [[need_sets[d][s] for d in range(k)] for s in range(k)],
            k, widths))
        # remap ids into the local gather table:
        #   [0, nb)            own block
        #   [nb, nb + B*k*w)   halo slot (installment-major; see halo_slot)
        #   nb + B*k*w         zero row (pads + absent)
        ids_remap = np.full((Vp, K), nb + B * k * w, np.int32)
        for d in range(k):
            rows = slice(d * nb, (d + 1) * nb)
            sp_blk, li_blk = src_part[rows], local_id[rows]
            out = ids_remap[rows]
            for s in range(k):
                sel = sp_blk == s  # pads (id >= Vp) have src_part -1
                if s == d:
                    out[sel] = li_blk[sel]
                else:  # halo row t = li's rank in the sorted need list
                    t = np.searchsorted(need_sets[d][s], li_blk[sel])
                    out[sel] = halo_slot(t, s, w, k, nb)
        self.ids_exec = jnp.asarray(ids_remap)

    # -- engine-facing interface -------------------------------------------

    def exchange_consts(self) -> dict:
        consts = dict(ids=self.ids_exec, mask=self.mask)
        if self.cfg.execution == "ring":
            consts["mask"] = self.mask_exec
        elif self.cfg.execution == "p2p":
            consts["send_rows"] = self.send_rows
        return consts

    def _halo_rows_per_pass(self) -> int:
        if self.cfg.execution in ("broadcast", "ring"):
            return self.k * (self.k - 1) * self.nb
        return self._halo_rows

    def wire_fields_per_step(self, model, dims) -> dict:
        widths = model_exchange_widths(model, dims, "edge_cut")
        return {"halo_bytes":
                self._halo_rows_per_pass() * int(sum(widths)) * FEAT_BYTES}

    def embed_grad_bytes(self, dims) -> int:
        return self._halo_rows_per_pass() * int(dims[0]) * FEAT_BYTES

    def device_bytes_per_step(self, model, dims) -> np.ndarray:
        if self.cfg.execution == "p2p":
            return edge_cut_halo_device_bytes(self.g, self.part, dims,
                                              model=model)
        widths = model_exchange_widths(model, dims, "edge_cut")
        per = 2 * (self.k - 1) * self.nb * int(sum(widths)) * FEAT_BYTES
        return np.full(self.k, per, np.int64)

    def telemetry_gauges(self, tel) -> None:
        k = self.k
        owned_v = np.bincount(self.part.assignment, minlength=k)
        owned_edges = np.asarray(self.mask).reshape(
            k, self.nb, -1).sum((1, 2))
        for d in range(k):
            tel.gauge("layout.owned_vertices", device=d).set(
                int(owned_v[d]))
            tel.gauge("layout.owned_edges", device=d).set(
                float(owned_edges[d]))

    def global_embeddings(self, H: np.ndarray) -> np.ndarray:
        return H[self.new_of_old]


# ---------------------------------------------------------------------------
# replica families: vertex_cut (and the hybrid cut, which subclasses the
# shared base in partition/hybrid_cut.py) — replica slot tables + master
# masking + the replica-sync combine
# ---------------------------------------------------------------------------


class ReplicaLayoutBase(PartitionLayout):
    """Shared engine-facing plumbing for families built on replica slot
    tables (an inner `VertexCutLayout`-shaped `self.layout` + a
    `build_replica_sync_plan` exchange plan)."""

    has_replicas = True

    def _flatten_layout(self):
        """Mirror the inner [k, nv] slot tables into the flattened replica
        space [Vp = k*nv] the engine shards, and flatten the sync plan's
        slot tables the same way."""
        lay, k = self.layout, self.k
        self.nb = self.nv = nv = lay.nv
        self.Vp = Vp = k * nv
        self.K = lay.Kc
        self.store = FeatureStore(np.asarray(lay.X, np.float32))
        self.X = self.store.device_table()
        # trainable embeddings update at MASTER slots only (replicas receive
        # the master's delta through the replica sync, so they never drift
        # and never double-update)
        self.emb_touched = np.asarray(
            lay.master_mask.reshape(Vp), np.float32)
        self.y = jnp.asarray(lay.y.reshape(Vp))
        self.train_w = jnp.asarray(lay.train_w.reshape(Vp))
        self.test_w = jnp.asarray(lay.test_w.reshape(Vp))
        self.deg = jnp.asarray(lay.deg.reshape(Vp, 1))
        self.bmask = jnp.asarray(lay.bmask.reshape(Vp))
        self.mask = jnp.asarray(lay.mask_owned.reshape(Vp, lay.Kc))
        self.ids_exec = jnp.asarray(lay.ids_owned.reshape(Vp, lay.Kc))
        self.ref_vert_ids = lay.vert_ids  # [k, nv] np, pad = V

    def _build_sync_plan(self, masters):
        c, Vp = self.cfg, self.Vp
        with self.tel.span("layout.exchange_plan"):
            plan = build_replica_sync_plan(self.layout, masters, c.execution,
                                           buckets=c.p2p_buckets)
        plan.pop("execution")
        self._vc_rows_per_layer = plan.pop("rows_per_layer")
        self._vc_p2p_caps = plan.pop("caps", None)  # p2p: pre-bucket c1/c2
        self._vc_plan = {}
        slot_tables = ("rep_ids", "rep_mask", "gather_ids", "gather_mask",
                       "scatter_ids")  # [k, nv, ...] -> flatten like X/y/...
        for key, a in plan.items():
            if key in slot_tables:
                a = a.reshape((Vp,) + a.shape[2:])
            self._vc_plan[key] = jnp.asarray(a)
        self.squeeze_keys = tuple(
            key for key in ("send1", "send2", "ring_ids")
            if key in self._vc_plan)

    def exchange_consts(self) -> dict:
        return dict(ids=self.ids_exec, mask=self.mask, **self._vc_plan)

    def telemetry_gauges(self, tel) -> None:
        lay, k = self.layout, self.k
        V = self.g.num_vertices
        owned_edges = np.asarray(lay.mask_owned).reshape(k, -1).sum(1)
        replica_rows = (np.asarray(lay.vert_ids) < V).sum(1)
        masters = np.asarray(lay.master_mask).reshape(k, -1).sum(1)
        for d in range(k):
            tel.gauge("layout.owned_edges", device=d).set(
                float(owned_edges[d]))
            tel.gauge("layout.replica_rows", device=d).set(
                int(replica_rows[d]))
            tel.gauge("layout.master_rows", device=d).set(
                float(masters[d]))

    def global_embeddings(self, H: np.ndarray) -> np.ndarray:
        """Read each vertex's MASTER replica row.  With sorted_masters
        layouts the masters are a contiguous per-device prefix, so this is
        k prefix SLICES instead of a [Vp] boolean mask scan."""
        lay = self.layout
        V = self.g.num_vertices
        out = np.zeros((V, H.shape[1]), H.dtype)
        counts = getattr(lay, "master_counts", None)
        if getattr(lay, "sorted_masters", False) and counts is not None:
            for d in range(self.k):
                n = int(counts[d])
                out[lay.vert_ids[d, :n]] = H[d * self.nv: d * self.nv + n]
            return out
        flat_vid = np.asarray(lay.vert_ids).reshape(-1)  # pad slots -> V
        mm = np.asarray(lay.master_mask).reshape(-1) > 0.5
        out[flat_vid[mm]] = H[mm]
        return out


class VertexCutFamilyLayout(ReplicaLayoutBase):
    family = "vertex_cut"

    @classmethod
    def validate(cls, cfg, partition=None) -> None:
        if cfg.vertex_cut not in VERTEX_CUTS:
            raise ValueError(
                f"vertex_cut must be one of {tuple(VERTEX_CUTS)}")
        if cfg.batching != "full_graph":
            raise ValueError(
                "vertex_cut supports batching='full_graph' only "
                "(vertex-cut mini-batch sampling is a ROADMAP follow-up)")
        if partition is not None:
            raise ValueError(
                "partition= is an edge-cut Partition; vertex_cut builds "
                "its own cut from cfg.vertex_cut")

    def _build(self, partition):
        c, g, k = self.cfg, self.g, self.k
        with self.tel.span("layout.partition"):
            self.vcut = VERTEX_CUTS[c.vertex_cut](g, k, seed=c.seed)
        with self.tel.span("layout.vertex_blocks"):
            self.layout = build_vertex_layout(
                g, self.vcut, k,
                sorted_masters=getattr(c, "sorted_masters", False))
        with self.tel.span("layout.store"):
            self._flatten_layout()
        # reference-step ELL in the flattened replica space: local slot ->
        # global flat slot d*nv + slot; pads -> Vp (the appended zero row),
        # the same pad convention as the edge-cut ids_global table
        lay, nv, Vp = self.layout, self.nv, self.Vp
        flat_off = (np.arange(k) * nv)[:, None, None]
        self.ids_global = np.where(lay.mask_owned > 0,
                                   lay.ids_owned + flat_off, Vp
                                   ).reshape(Vp, lay.Kc).astype(np.int64)
        self._build_sync_plan(self.vcut.masters)

    def wire_fields_per_step(self, model, dims) -> dict:
        # wire bytes of one distributed step: every layer's replica sync
        # ships `rows_per_layer` rows at that layer's model-dependent
        # exchange width (input width for gcn/sage/gin; transformed width
        # + attention coefficient + the max pass for gat) — the same
        # accounting as cost_models.replica_sync_bytes_per_step
        widths = model_exchange_widths(model, dims, "vertex_cut")
        return {"replica_sync_bytes":
                self._vc_rows_per_layer * int(sum(widths)) * FEAT_BYTES}

    def embed_grad_bytes(self, dims) -> int:
        # grad combine + master-delta re-broadcast: two sync passes at D0
        return 2 * self._vc_rows_per_layer * int(dims[0]) * FEAT_BYTES

    def device_bytes_per_step(self, model, dims) -> np.ndarray:
        if self.cfg.execution == "p2p":
            return replica_sync_device_bytes(self.layout, self.vcut.masters,
                                             dims, model=model)
        widths = model_exchange_widths(model, dims, "vertex_cut")
        per = 2 * (self.k - 1) * self.nv * int(sum(widths)) * FEAT_BYTES
        return np.full(self.k, per, np.int64)


LAYOUT_BUILDERS = {
    "edge_cut": EdgeCutLayout,
    "vertex_cut": VertexCutFamilyLayout,
}


def get_layout_builder(family: str):
    """Resolve a family string to its layout class.  The hybrid family
    self-registers on import (lazy, to keep partition/hybrid_cut.py free to
    import this module's base classes)."""
    if family == "hybrid" and family not in LAYOUT_BUILDERS:
        from repro.core.partition import hybrid_cut  # noqa: F401 — registers
    try:
        return LAYOUT_BUILDERS[family]
    except KeyError:
        raise ValueError(f"unknown partition family {family!r}; known: "
                         f"{tuple(LAYOUT_BUILDERS)}") from None
