"""Replica-sync exchange for vertex-cut execution (survey §4.2 + §7): the
Gather-ApplyEdge-Scatter dataflow over replicated vertices.

Each device computes PARTIAL aggregations over its owned edges (a local ELL
multiply in replica-slot space); this module combines those partials across
every replica of a vertex so all replicas see the full neighbor sum.  Three
collective families mirror the engine's edge-cut exchange axis:

  broadcast  all_gather every device's partial block; each device sums its
             slots' replicas out of the gathered table (CAGNET-style).
  ring       ppermute the partial blocks around the ring; each device
             accumulates the visiting block's contribution to its own slots.
  p2p        master-based two-phase GAS: replicas ship partials to each
             vertex's MASTER (all_to_all #1), the master combines, then
             scatters the finished aggregate back to the replicas
             (all_to_all #2) — only 2·Σ(r(v)−1) rows cross the wire per
             layer, the replication-factor-bounded volume that makes
             vertex-cut win on skewed graphs.

All plans are static numpy tables built once from a VertexCutLayout; the
device-side `replica_combine` is pure traced code (collectives + gathers)
with well-defined transposes, so gradients flow through the exchange and the
master-masked loss gives exact weight gradients after the engine's psum.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution.pipeline_exchange import (
    bucketed_all_to_all,
    bucketed_cap_widths,
    bucketed_send_table,
    chunked_overlap,
    halo_slot,
    zero_pad_row,
)
from repro.core.partition.vertex_layout import VertexCutLayout

REPLICA_EXECUTIONS = ("broadcast", "ring", "p2p")


def _vertex_replica_tables(lay: VertexCutLayout):
    """Per-vertex replica tables: rep_flat[v, r] = flat slot (d*nv + slot) of
    v's r-th replica (pad k*nv), rep_part[v, r] = its device (pad -1).
    Replicas are ordered by device id — deterministic."""
    k, nv = lay.k, lay.nv
    V = lay.slot_of.shape[1]
    parts, verts = np.nonzero(lay.slot_of >= 0)
    order = np.argsort(verts, kind="stable")
    v_s, p_s = verts[order], parts[order]
    flat = p_s * nv + lay.slot_of[p_s, v_s]
    newv = np.r_[0, (np.diff(v_s) != 0).astype(np.int64)]
    first = np.r_[0, np.flatnonzero(np.diff(v_s)) + 1]
    pos = np.arange(len(v_s)) - first[np.cumsum(newv)]
    rep_flat = np.full((V, lay.Rm), k * nv, np.int64)
    rep_part = np.full((V, lay.Rm), -1, np.int64)
    rep_flat[v_s, pos] = flat
    rep_part[v_s, pos] = p_s
    return rep_flat, rep_part


def build_replica_sync_plan(lay: VertexCutLayout, masters: np.ndarray,
                            execution: str, buckets: int = 1) -> Dict:
    """Static exchange plan for one collective family.  Every returned dict
    carries ``rows_per_layer``: the TRUE number of replica rows that cross
    the wire per GNN layer (padding excluded) — the engine's CommStats
    accounting and the standalone cost model must both reproduce it.

    ``buckets`` > 1 splits the p2p send caps (c1/c2, the max pairwise need)
    into power-of-two installments so each lowered all_to_all operand is
    ~``buckets``x smaller (PR 3 follow-up); the wire rows are unchanged."""
    if execution not in REPLICA_EXECUTIONS:
        raise ValueError(f"execution must be one of {REPLICA_EXECUTIONS}")
    k, nv, Rm = lay.k, lay.nv, lay.Rm
    V = lay.slot_of.shape[1]
    vert_ids = lay.vert_ids
    rep_flat, rep_part = _vertex_replica_tables(lay)
    if execution == "broadcast":
        pad_row = np.full((1, Rm), k * nv, np.int64)
        rep_ids = np.concatenate([rep_flat, pad_row], 0)[vert_ids]
        return dict(execution=execution,
                    rep_ids=rep_ids.astype(np.int32),
                    rep_mask=(rep_ids < k * nv).astype(np.float32),
                    rows_per_layer=k * (k - 1) * nv)
    if execution == "ring":
        slot_ext = np.concatenate(
            [lay.slot_of, np.full((k, 1), -1, np.int64)], 1)  # col V = pad
        tmp = slot_ext[:, vert_ids.reshape(-1)].reshape(k, k, nv)
        ring_ids = np.where(tmp < 0, nv, tmp).transpose(1, 0, 2)
        return dict(execution=execution,
                    ring_ids=ring_ids.astype(np.int32),
                    rows_per_layer=k * (k - 1) * nv)
    # p2p: master-based two-phase GAS
    m_of = masters.astype(np.int64)
    # phase 1 (gather): src s ships partial rows of its non-master replicas
    # to each vertex's master.  pos1[s, v] = position of v in need1[s][m(v)].
    need1 = [[np.zeros(0, np.int64) for _ in range(k)] for _ in range(k)]
    pos1 = np.full((k, V), -1, np.int64)
    rows1 = 0
    for s in range(k):
        pres = vert_ids[s] < V
        vs = vert_ids[s][pres]
        sl = np.flatnonzero(pres)
        m = m_of[vs]
        rem = m != s
        for mm in np.unique(m[rem]):
            sel = rem & (m == mm)
            need1[s][mm] = sl[sel]
            pos1[s, vs[sel]] = np.arange(int(sel.sum()))
            rows1 += int(sel.sum())
    c1 = max(1, max((len(x) for row in need1 for x in row), default=1))
    w1 = bucketed_cap_widths(c1, buckets)
    send1 = bucketed_send_table(need1, k, w1)
    pad1 = nv + len(w1) * k * w1[0]
    gather_ids = np.full((k, nv, Rm), pad1, np.int32)
    gather_mask = np.zeros((k, nv, Rm), np.float32)
    for d in range(k):
        pres = vert_ids[d] < V
        vs = vert_ids[d][pres]
        slots = np.flatnonzero(pres)
        own = m_of[vs] == d
        mv, msl = vs[own], slots[own]
        for r in range(Rm):
            s = rep_part[mv, r]
            valid = s >= 0
            ssafe = np.clip(s, 0, k - 1)
            idx = np.where(s == d, msl,
                           halo_slot(pos1[ssafe, mv], ssafe, w1[0], k, nv))
            gather_ids[d, msl[valid], r] = idx[valid]
            gather_mask[d, msl[valid], r] = 1.0
    # phase 2 (scatter): each master ships the finished aggregate back to the
    # other replicas.  pos2[dst, v] = position of v in need2[m(v)][dst].
    need2 = [[np.zeros(0, np.int64) for _ in range(k)] for _ in range(k)]
    pos2 = np.full((k, V), -1, np.int64)
    rows2 = 0
    for m in range(k):
        pres = vert_ids[m] < V
        vs = vert_ids[m][pres]
        slots = np.flatnonzero(pres)
        own = m_of[vs] == m
        mv, msl = vs[own], slots[own]
        dsts, slts, vss = [], [], []
        for r in range(Rm):
            s = rep_part[mv, r]
            valid = (s >= 0) & (s != m)
            dsts.append(s[valid])
            slts.append(msl[valid])
            vss.append(mv[valid])
        dsts = np.concatenate(dsts) if dsts else np.zeros(0, np.int64)
        slts = np.concatenate(slts) if slts else np.zeros(0, np.int64)
        vss = np.concatenate(vss) if vss else np.zeros(0, np.int64)
        order = np.lexsort((slts, dsts))
        dsts, slts, vss = dsts[order], slts[order], vss[order]
        for dd in np.unique(dsts):
            sel = dsts == dd
            need2[m][dd] = slts[sel]
            pos2[dd, vss[sel]] = np.arange(int(sel.sum()))
            rows2 += int(sel.sum())
    c2 = max(1, max((len(x) for row in need2 for x in row), default=1))
    w2 = bucketed_cap_widths(c2, buckets)
    send2 = bucketed_send_table(need2, k, w2)
    pad2 = nv + len(w2) * k * w2[0]
    scatter_ids = np.full((k, nv), pad2, np.int32)
    for d in range(k):
        pres = vert_ids[d] < V
        vs = vert_ids[d][pres]
        slots = np.flatnonzero(pres)
        m = m_of[vs]
        own = m == d
        scatter_ids[d, slots[own]] = slots[own]
        rem = ~own
        scatter_ids[d, slots[rem]] = halo_slot(
            pos2[d, vs[rem]], m[rem], w2[0], k, nv).astype(np.int32)
    return dict(execution=execution, send1=send1, gather_ids=gather_ids,
                gather_mask=gather_mask, send2=send2,
                scatter_ids=scatter_ids, rows_per_layer=rows1 + rows2,
                caps=(c1, c2))  # pre-bucketing max pairwise needs


def _ring_combine(partial: jnp.ndarray, ring_ids: jnp.ndarray, axis: str,
                  k: int, combine_op: Callable) -> jnp.ndarray:
    """Double-buffered ring combine (shared by the sum and max passes): the
    ppermute for rotation r+1 is ISSUED in the same step that rotation r's
    block feeds the local gather — the two are data-independent, the pattern
    XLA's async collectives overlap (the same double-buffering as
    `pipeline_exchange.chunked_overlap`).  Exactly k-1 ppermute rounds, the
    plan's rows_per_layer = k*(k-1)*nv wire accounting: the prologue issues
    rotation 1, the scan body issues rotations 2..k-1 while consuming
    1..k-2, and the epilogue consumes rotation k-1 without rotating further.
    Accumulation order (own block, then rotations 1..k-1) is unchanged, so
    results are bitwise-identical to the serial permute-then-gather ring.

    The zero pad row is hoisted out of the loop: every device appends a zero
    row, so rotation keeps slot nv a zero row and pad ring_ids read zeros
    (the identity for the sum combine; the max combine requires all real
    values >= 0 — see `replica_combine_max`)."""
    me = jax.lax.axis_index(axis)
    table0 = jnp.concatenate([partial, zero_pad_row(partial)], 0)
    acc = jnp.take(table0, jnp.take(ring_ids, me, axis=0), axis=0)
    if k == 1:
        return acc
    perm = [(i, (i - 1) % k) for i in range(k)]
    with jax.named_scope("exchange"):
        tab1 = jax.lax.ppermute(table0, axis, perm)

    def ring_step(carry, r):
        acc, tab_cur = carry
        with jax.named_scope("exchange"):  # rotation r+1 ...
            tab_nxt = jax.lax.ppermute(tab_cur, axis, perm)
        owner = (me + r) % k  # ... flies while rotation r feeds the gather
        acc = combine_op(acc, jnp.take(
            tab_cur, jnp.take(ring_ids, owner, axis=0), axis=0))
        return (acc, tab_nxt), None

    (acc, tab_last), _ = jax.lax.scan(ring_step, (acc, tab1),
                                      jnp.arange(1, k - 1))
    owner = (me + k - 1) % k
    return combine_op(acc, jnp.take(
        tab_last, jnp.take(ring_ids, owner, axis=0), axis=0))


def replica_combine(execution: str, partial: jnp.ndarray, plan: Dict, *,
                    axis: str, k: int, ell_fn: Callable,
                    num_chunks: int = 1) -> jnp.ndarray:
    """Device-local (under shard_map) replica combine: partial [nv, D] ->
    full per-slot neighbor sums [nv, D].  ``plan`` holds this device's slice
    of the static tables; ``ell_fn(ids, mask, table)`` is the masked-gather
    reduction (the engine passes its ELL SpMM).

    ``num_chunks`` > 1 feature-chunks the broadcast/p2p exchange (see
    `pipeline_exchange.chunked_overlap`): the collective for chunk c+1 is
    issued while chunk c's combine computes, and only two chunk-sized
    gathered tables are ever live."""

    if execution == "broadcast":
        def exchange(pc):
            with jax.named_scope("exchange"):
                full = jax.lax.all_gather(pc, axis, axis=0, tiled=True)
            return jnp.concatenate([full, zero_pad_row(pc)], 0)

        return chunked_overlap(
            partial, num_chunks, exchange,
            lambda table: ell_fn(plan["rep_ids"], plan["rep_mask"], table))
    if execution == "ring":
        return _ring_combine(partial, plan["ring_ids"], axis, k,
                             lambda a, b: a + b)

    # p2p: gather partials at masters, combine, scatter aggregates back.
    # Phase-1 installment all_to_alls are issued one chunk ahead of the
    # master combine; phase 2 rides inside the consumer (it depends on the
    # combined aggregate, so it cannot be hoisted ahead of it).
    def exchange(pc):
        return pc, bucketed_all_to_all(pc, plan["send1"], axis, k)

    def consume(carry):
        pc, recv = carry
        table = jnp.concatenate([pc, recv, zero_pad_row(pc)], 0)
        agg_m = ell_fn(plan["gather_ids"], plan["gather_mask"], table)
        recv_b = bucketed_all_to_all(agg_m, plan["send2"], axis, k)
        table2 = jnp.concatenate([agg_m, recv_b, zero_pad_row(pc)], 0)
        return jnp.take(table2, plan["scatter_ids"], axis=0)

    return chunked_overlap(partial, num_chunks, exchange, consume)


def replica_combine_max(execution: str, partial: jnp.ndarray, plan: Dict, *,
                        axis: str, k: int) -> jnp.ndarray:
    """Max-combine across replicas — the first pass of the distributed GAT
    segment-softmax: every replica's local max of the per-edge logits is
    combined so all replicas share ONE exact softmax stabilizer, then the
    exp-sum pass rides the ordinary `replica_combine`.

    Reuses the SAME static plan tables as the sum combine, with one invariant
    pushed onto the caller: all real values must be >= 0 (the engine floors
    its local maxima at 0 — any upper bound of the logits is a valid softmax
    shift).  Pad/absent slots then read the zero rows the plans already
    route to, and fold into the max as harmless identities."""
    if execution == "broadcast":
        with jax.named_scope("exchange"):
            full = jax.lax.all_gather(partial, axis, axis=0, tiled=True)
        table = jnp.concatenate([full, zero_pad_row(partial)], 0)
        vals = jnp.take(table, plan["rep_ids"], axis=0)  # [nv, Rm, D]
        return jnp.where(plan["rep_mask"][..., None] > 0, vals, 0.0).max(1)
    if execution == "ring":
        return _ring_combine(partial, plan["ring_ids"], axis, k, jnp.maximum)
    # p2p: max partials at masters, scatter the combined max back
    recv = bucketed_all_to_all(partial, plan["send1"], axis, k)
    table = jnp.concatenate([partial, recv, zero_pad_row(partial)], 0)
    vals = jnp.take(table, plan["gather_ids"], axis=0)  # [nv, Rm, D]
    agg_m = jnp.where(plan["gather_mask"][..., None] > 0, vals, 0.0).max(1)
    recv2 = bucketed_all_to_all(agg_m, plan["send2"], axis, k)
    table2 = jnp.concatenate([agg_m, recv2, zero_pad_row(partial)], 0)
    return jnp.take(table2, plan["scatter_ids"], axis=0)


def reference_combine(partial: jnp.ndarray, vert_ids: jnp.ndarray,
                      num_vertices: int) -> jnp.ndarray:
    """Single-device oracle combine: scatter-add every replica's partial into
    the global vertex space and gather back per slot — the same sum any of
    the three collectives computes, without a wire.  partial [k, nv, D]."""
    D = partial.shape[-1]
    G = jnp.zeros((num_vertices + 1, D), partial.dtype).at[
        vert_ids.reshape(-1)].add(partial.reshape(-1, D))
    return jnp.take(G, vert_ids, axis=0)  # pad slots read G[V] = 0


def reference_combine_max(partial: jnp.ndarray, vert_ids: jnp.ndarray,
                          num_vertices: int) -> jnp.ndarray:
    """Single-device oracle for `replica_combine_max`: scatter-MAX into the
    global vertex space and gather back.  Same >= 0 invariant — the zero
    init of the global table plays the role of the plans' zero pad rows."""
    D = partial.shape[-1]
    G = jnp.zeros((num_vertices + 1, D), partial.dtype).at[
        vert_ids.reshape(-1)].max(partial.reshape(-1, D))
    return jnp.take(G, vert_ids, axis=0)
