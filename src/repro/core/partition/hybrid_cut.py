"""PowerLyra-style hybrid degree-threshold cut (survey §4.2, ROADMAP item
3): low-degree vertices live edge-cut-local behind a halo exchange; hub
vertices (in-degree >= threshold) replicate vertex-cut-style with the
replica-sync GAS combine.  One layout composes the two existing dataflows
per vertex class.

Construction
------------
Start from an edge-cut master assignment (any `PARTITIONERS` entry, or a
user-supplied `Partition`).  Classify vertices: ``hub = in_degree >=
threshold``.  Each edge (src -> dst, CSR order) is then owned by

  * ``masters[dst]``  when dst is LOW-degree  — the edge computes at dst's
    home, exactly the edge-cut rule; if src is low and lives elsewhere its
    row crosses the HALO wire (no replica is materialized);
  * ``masters[src]``  when dst is a HUB       — dst's aggregation partials
    accumulate where its in-edges already live, and the replica-sync
    combine sums them across src masters (the PowerLyra insight: only hubs
    pay replication, and their fan-in never concentrates on one device).

Hub SOURCES of owned edges are also materialized as replica slots (they are
local by construction when dst is low: owner == masters[dst] only consumes
src rows through the halo when src is low).  The degenerate thresholds
recover the pure families exactly: ``inf`` -> nobody is a hub -> every
vertex has exactly its master replica and the halo carries precisely the
edge-cut `communication_volume`; ``0`` -> everybody is a hub -> edges
compute at ``masters[src]`` with zero halo — a src-replicating vertex-cut.

The engine-facing class `HybridLayout` builds an inner `VertexCutLayout`
over the presence sets (so `build_replica_sync_plan` and the flattening in
`ReplicaLayoutBase` apply unchanged) plus per-execution halo tables the
`ReplicaSyncBackend` consumes when ``halo_active``:

  halo_send [k, B, k, w]  p2p bucketed installments (same builder as the
                          edge-cut plan);
  halo_src  [k, Hbuf]     broadcast: flat index into the all_gathered
                          [k*nv | zero] table per canonical halo slot;
  halo_ring [k, k, Hbuf]  ring: per source-owner rotation, local slot to
                          read (pad nv -> the appended zero row; each
                          canonical slot has exactly ONE real source, so
                          the k-round sum is exact).

Canonical halo slots use the same installment-major `halo_slot` numbering
as the edge-cut p2p plan, so the owned-edge ELL ids are shared by all three
execution models.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.execution.pipeline_exchange import (
    bucketed_cap_widths,
    bucketed_send_table,
    halo_slot,
)
from repro.core.graph import Graph
from repro.core.partition.cost_models import (
    FEAT_BYTES,
    hybrid_device_bytes,
    hybrid_exchange_widths,
)
from repro.core.partition.edge_cut import PARTITIONERS
from repro.core.partition.layout_api import (
    LAYOUT_BUILDERS,
    ReplicaLayoutBase,
)
from repro.core.partition.vertex_cut import VertexCut, edge_endpoints
from repro.core.partition.vertex_layout import VertexCutLayout


def auto_hub_threshold(g: Graph, q: float = 95.0) -> float:
    """Default hub threshold: the q-th percentile of the in-degree
    distribution — on power-law graphs this tags the heavy tail whose
    fan-in makes edge-cut's hub-owner straggler, while keeping the >=95%
    low-degree mass halo-cheap."""
    deg = g.degree()
    if len(deg) == 0:
        return np.inf
    return float(np.percentile(deg, q))


@dataclasses.dataclass
class HybridCut:
    """The cut decision alone (layout-free) — what the property tier locks."""
    threshold: float
    hub: np.ndarray         # [V] bool — in_degree >= threshold
    masters: np.ndarray     # [V] int64 master partition (the edge-cut side)
    edge_owner: np.ndarray  # [E] int64 owner per CSR edge
    num_parts: int

    def as_vertex_cut(self) -> VertexCut:
        return VertexCut(self.edge_owner.astype(np.int32), self.num_parts,
                         self.masters.astype(np.int32))


def build_hybrid_cut(g: Graph, k: int, threshold: Optional[float] = None,
                     partition=None,
                     partitioner: str = "metis_like") -> HybridCut:
    """Classify vertices by the degree threshold and assign edge owners
    (see module docstring).  ``threshold=None`` -> `auto_hub_threshold`."""
    if threshold is None:
        threshold = auto_hub_threshold(g)
    part = partition or PARTITIONERS[partitioner](g, k)
    masters = np.asarray(part.assignment, np.int64)
    deg = g.degree()
    # np.inf/-inf thresholds compare correctly; hub set is EXACTLY >= thr
    hub = deg.astype(np.float64) >= threshold
    src, dst = edge_endpoints(g)
    owner = np.where(hub[dst], masters[src], masters[dst]).astype(np.int64) \
        if len(src) else np.zeros(0, np.int64)
    return HybridCut(threshold=float(threshold), hub=hub, masters=masters,
                     edge_owner=owner, num_parts=k)


class HybridLayout(ReplicaLayoutBase):
    family = "hybrid"

    @classmethod
    def validate(cls, cfg, partition=None) -> None:
        if cfg.batching != "full_graph":
            raise ValueError(
                "hybrid supports batching='full_graph' only "
                "(vertex-cut mini-batch sampling is a ROADMAP follow-up)")
        thr = getattr(cfg, "hub_threshold", None)
        if thr is not None and not thr >= 0:  # rejects negatives and NaN
            raise ValueError(
                "hub_threshold must be >= 0 (np.inf -> pure edge-cut, "
                "0 -> pure vertex-cut) or None for the auto percentile")

    def _build(self, partition):
        c, g, k = self.cfg, self.g, self.k
        with self.tel.span("layout.partition"):
            self.part = (partition
                         or PARTITIONERS[c.partitioner](g, k))
            cut = self.cut = build_hybrid_cut(
                g, k, threshold=getattr(c, "hub_threshold", None),
                partition=self.part)
        self.vcut = cut.as_vertex_cut()
        V = g.num_vertices
        src, dst = edge_endpoints(g)
        owner, masters = cut.edge_owner, cut.masters
        # presence: every master replica; dst of each owned edge; hub srcs
        # (low srcs are NOT materialized remotely — they ride the halo)
        key_list = [masters * V + np.arange(V, dtype=np.int64)]
        if len(owner):
            key_list.append(owner * V + dst)
            hs = cut.hub[src]
            if hs.any():
                key_list.append((owner * V + src)[hs])
        keys = np.unique(np.concatenate(key_list))
        part_of, vid = keys // V, keys % V
        rep_count = np.bincount(vid, minlength=V)
        sizes = np.bincount(part_of, minlength=k)
        nv = max(int(sizes.max()), 1)
        vert_ids = np.full((k, nv), V, np.int64)
        slot_of = np.full((k, V), -1, np.int64)
        master_counts = np.zeros(k, np.int64)
        for d in range(k):
            vs = vid[part_of == d]  # sorted ascending (keys are sorted)
            master_counts[d] = int((masters[vs] == d).sum())
            vert_ids[d, : len(vs)] = vs
            slot_of[d, vs] = np.arange(len(vs))
        # owned-edge ELL rows: dst slot on the owner (dst always present)
        dslot = slot_of[owner, dst] if len(owner) else owner
        sslot = slot_of[owner, src] if len(owner) else owner
        absent = sslot < 0  # low-degree remote src -> halo
        cnt = np.zeros((k, nv), np.int64)
        if len(owner):
            np.add.at(cnt, (owner, dslot), 1)
        Kc = max(int(cnt.max()), 1)
        # halo need sets: need[d][s] = sorted home slots (on master s) that
        # owner d's ELL reads through the wire — same shape as the edge-cut
        # p2p plan, reused for all three execution models' tables
        need = [[np.zeros(0, np.int64) for _ in range(k)] for _ in range(k)]
        sm = masters[src] if len(owner) else owner
        if absent.any():
            for d in range(k):
                for s in range(k):
                    if s == d:
                        continue
                    sel = absent & (owner == d) & (sm == s)
                    if sel.any():
                        need[d][s] = np.unique(slot_of[s, src[sel]])
        self.halo_need = need
        self.halo_rows = sum(len(x) for row in need for x in row)
        self.halo_active = self.halo_rows > 0
        execution = c.execution
        buckets = c.p2p_buckets if execution == "p2p" else 1
        Hcap = max(1, max((len(x) for row in need for x in row), default=1))
        widths = bucketed_cap_widths(Hcap, buckets)
        B, w = len(widths), widths[0]
        Hbuf = B * k * w if self.halo_active else 0
        self.halo_widths = widths
        # ELL columns: local slot, or nv + canonical halo slot; pad/zero row
        # sits AFTER the halo block (ReplicaSyncBackend._halo_table order)
        pad_id = nv + Hbuf
        ids_owned = np.full((k, nv, Kc), pad_id, np.int32)
        mask_owned = np.zeros((k, nv, Kc), np.float32)
        ref_cols = np.full((k, nv, Kc), k * nv, np.int64)
        if len(owner):
            pos_lut = [dict() for _ in range(k)]
            for d in range(k):
                for s in range(k):
                    for t, li in enumerate(need[d][s]):
                        pos_lut[d][(s, int(li))] = t
            col = np.where(absent, 0, np.maximum(sslot, 0)).astype(np.int64)
            refc = np.where(absent, 0, owner * nv + np.maximum(sslot, 0))
            if absent.any():
                home = slot_of[sm, src]  # src present at its own master
                hp = np.zeros(len(owner), np.int64)
                for e in np.flatnonzero(absent):
                    t = pos_lut[int(owner[e])][(int(sm[e]), int(home[e]))]
                    hp[e] = nv + halo_slot(t, int(sm[e]), w, k, 0)
                col = np.where(absent, hp, col)
                refc = np.where(absent, sm * nv + home, refc)
            grp = owner * nv + dslot
            order = np.argsort(grp, kind="stable")
            gs = grp[order]
            run_id = np.cumsum(np.r_[0, (np.diff(gs) != 0).astype(np.int64)])
            first = np.r_[0, np.flatnonzero(np.diff(gs)) + 1]
            pos = np.arange(len(gs)) - first[run_id]
            ids_owned[owner[order], dslot[order], pos] = col[order]
            mask_owned[owner[order], dslot[order], pos] = 1.0
            ref_cols[owner[order], dslot[order], pos] = refc[order]
        # per-slot tables — identical construction to build_vertex_layout
        deg_g = np.maximum(g.degree(), 1).astype(np.float32)
        present = vert_ids < V
        safe = np.minimum(vert_ids, V - 1)
        deg = np.where(present, deg_g[safe], 1.0)[..., None].astype(np.float32)
        master_mask = (present & (masters[safe] == np.arange(k)[:, None])
                       ).astype(np.float32)
        # boundary = rows other devices read: replicated slots + halo sources
        bmask = present & (rep_count[safe] > 1)
        for s in range(k):
            lis = [need[d][s] for d in range(k) if len(need[d][s])]
            if lis:
                bmask[s, np.unique(np.concatenate(lis))] = True
        D = g.features.shape[1]
        X = np.where(present[..., None], g.features[safe],
                     0.0).astype(np.float32)
        y = np.where(present, g.labels[safe], 0).astype(np.int32)
        train = (g.train_mask[safe] if g.train_mask is not None
                 else np.zeros((k, nv), bool))
        test = (g.test_mask[safe] if g.test_mask is not None
                else np.zeros((k, nv), bool))
        train_w = (master_mask
                   * np.where(present, train, False)).astype(np.float32)
        test_w = (master_mask
                  * np.where(present, test, False)).astype(np.float32)
        self.layout = VertexCutLayout(
            k=k, nv=nv, Kc=Kc, Rm=max(int(rep_count.max()), 1),
            vert_ids=vert_ids, slot_of=slot_of, master_mask=master_mask,
            rep_count=rep_count, ids_owned=ids_owned, mask_owned=mask_owned,
            deg=deg, bmask=bmask, X=X, y=y, train_w=train_w, test_w=test_w,
            master_counts=master_counts)
        with self.tel.span("layout.store"):
            self._flatten_layout()
        # reference ELL: halo columns point at the source's HOME flat slot
        # (s*nv + home), present columns at their replica slot; pad -> Vp
        self.ids_global = np.where(mask_owned > 0, ref_cols,
                                   k * nv).reshape(self.Vp, Kc
                                                   ).astype(np.int64)
        self.sync_active = int(rep_count.max()) > 1 if V else False
        self.has_replicas = self.sync_active
        if self.sync_active:
            self._build_sync_plan(masters)
        else:
            self._vc_plan = {}
            self._vc_rows_per_layer = 0
            self._vc_p2p_caps = None
            self.squeeze_keys = ()
        # per-execution halo tables (see module docstring)
        self._halo_consts = {}
        if self.halo_active:
            if execution == "p2p":
                self._halo_consts["halo_send"] = jnp.asarray(
                    bucketed_send_table(
                        [[need[d][s] for d in range(k)] for s in range(k)],
                        k, widths))
            elif execution == "broadcast":
                halo_src = np.full((k, Hbuf), k * nv, np.int64)
                for d in range(k):
                    for s in range(k):
                        for t, li in enumerate(need[d][s]):
                            halo_src[d, halo_slot(t, s, w, k, 0)] = \
                                s * nv + li
                self._halo_consts["halo_src"] = jnp.asarray(halo_src)
            else:  # ring
                halo_ring = np.full((k, k, Hbuf), nv, np.int64)
                for d in range(k):
                    for s in range(k):
                        for t, li in enumerate(need[d][s]):
                            halo_ring[d, s, halo_slot(t, s, w, k, 0)] = li
                self._halo_consts["halo_ring"] = jnp.asarray(halo_ring)
            self.squeeze_keys = (self.squeeze_keys
                                 + tuple(self._halo_consts))
        # halo rows crossing the wire per exchange pass
        if not self.halo_active:
            self.halo_rows_exec = 0
        elif execution == "p2p":
            self.halo_rows_exec = self.halo_rows
        else:
            self.halo_rows_exec = k * (k - 1) * nv

    def exchange_consts(self) -> dict:
        consts = super().exchange_consts()
        consts.update(self._halo_consts)
        return consts

    def wire_fields_per_step(self, model, dims) -> dict:
        # == cost_models.hybrid_bytes_per_step(halo_rows_exec,
        #    _vc_rows_per_layer, dims, model), split per CommStats field
        halo_w, sync_w = hybrid_exchange_widths(model, dims)
        out = {}
        if self.halo_active:
            out["halo_bytes"] = (self.halo_rows_exec
                                 * int(sum(halo_w)) * FEAT_BYTES)
        if self.sync_active:
            out["replica_sync_bytes"] = (self._vc_rows_per_layer
                                         * int(sum(sync_w)) * FEAT_BYTES)
        return out

    def embed_grad_bytes(self, dims) -> int:
        # halo grad transpose (one width-D0 return pass) + the vertex-cut
        # grad-combine / master-delta pair over the replica rows
        rows = self.halo_rows_exec
        if self.sync_active:
            rows += 2 * self._vc_rows_per_layer
        return rows * int(dims[0]) * FEAT_BYTES

    def device_bytes_per_step(self, model, dims) -> np.ndarray:
        return hybrid_device_bytes(
            self.layout, self.cut.masters, self.halo_need,
            self.cfg.execution, dims, model=model,
            halo_active=self.halo_active, sync_active=self.sync_active)

    def telemetry_gauges(self, tel) -> None:
        super().telemetry_gauges(tel)
        recv = [sum(len(self.halo_need[d][s]) for s in range(self.k))
                for d in range(self.k)]
        for d in range(self.k):
            tel.gauge("layout.halo_rows", device=d).set(int(recv[d]))


LAYOUT_BUILDERS["hybrid"] = HybridLayout
