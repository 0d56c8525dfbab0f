"""ExchangeBackend: the execution side of the partition-family interface
(`partition/layout_api.py` owns the static tables; this module owns the
device-local traced programs that move rows over the wire under shard_map).

Two backends cover the survey's §4.2 families:

  EdgeCutBackend      halo exchange — neighbor rows cross the wire
                      (broadcast all_gather / ring ppermute scan / bucketed
                      p2p all_to_all installments), then ONE masked ELL
                      multiply over the gathered table.  GAT ships the
                      transformed rows FUSED with their attention-coefficient
                      column in a single chunked exchange (see `gat_attend`).
  ReplicaSyncBackend  partial aggregation over OWNED edges in replica-slot
                      space, then the replica-sync GAS combine
                      (execution/replica_sync.py).  Parametrized by two
                      layout flags so ONE backend serves both replica
                      families:
                        sync_active  replicas exist -> combine partials
                                     (vertex_cut: always; hybrid: only when
                                     some vertex actually replicates);
                        halo_active  the owned-edge ELL reads remote
                                     low-degree source rows through a halo
                                     table appended after the local block
                                     (hybrid only; vertex_cut keeps every
                                     source row local by construction).

A backend duck-types the engine: it reads eng.{_ell, _ell_attend, _sddmm,
_combine, _gat_softmax, axis, k, nb, cfg, playout} and nothing else.  A
fourth family either reuses one of these (the hybrid route: flags on the
layout) or adds a class here and maps it in `make_backend`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.execution.pipeline_exchange import (
    bucketed_all_to_all,
    chunked_overlap,
    feature_chunks,
    chunk_width,
    zero_pad_row,
)
from repro.core.execution.replica_sync import (
    replica_combine,
    replica_combine_max,
)


class ExchangeBackend:
    has_replicas = False

    def __init__(self, eng):
        self.eng = eng

    def aggregate(self, h_local, cl):
        """One layer's neighbor exchange + masked aggregation, normalized by
        the (global) degree: h_local [nb, D] -> agg [nb, D]."""
        raise NotImplementedError

    def gat_attend(self, p_l, Hw, cl):
        """One distributed GAT aggregation (edge-wise attention through this
        backend's exchange) of the transformed rows Hw [nb, D]: returns the
        softmax numerator [nb, D] and denominator [nb, 1]."""
        raise NotImplementedError

    def combine_rows(self, rows, cl):
        """Sum per-slot rows across replicas (identity when the family has
        none) — the trainable-embedding grad/delta path."""
        return rows


class EdgeCutBackend(ExchangeBackend):
    """Halo exchange: broadcast / ring / bucketed-p2p assembly of the
    gathered neighbor table, feature-chunked for §6-§7 overlap."""

    def exchange_fn(self, cl):
        """The broadcast/p2p table assembly as a reusable closure:
        hc [nb, Dc] -> gather table (+ the one zero pad row).
        Width-agnostic, so the GAT layer reuses it for the fused
        [s-column | Hw] payload."""
        eng = self.eng
        ax, k = eng.axis, eng.k
        if eng.cfg.execution == "broadcast":
            def exchange(hc):
                with jax.named_scope("exchange"):
                    h_full = jax.lax.all_gather(hc, ax, axis=0, tiled=True)
                return jnp.concatenate([h_full, zero_pad_row(hc)], 0)
        else:
            send_rows = cl["send_rows"]  # [B, k, w]

            def exchange(hc):
                recv = bucketed_all_to_all(hc, send_rows, ax, k)
                return jnp.concatenate([hc, recv, zero_pad_row(hc)], 0)
        return exchange

    def aggregate(self, h_local, cl):
        eng = self.eng
        ax, k, nb = eng.axis, eng.k, eng.nb
        C = eng.cfg.exchange_chunks
        ids, mask, deg = cl["ids"], cl["mask"], cl["deg"]
        if eng.cfg.execution == "ring":
            me = jax.lax.axis_index(ax)

            def ring_step(carry, r):
                acc, h_cur = carry
                owner = (me + r) % k
                ids_r = jnp.take(ids, owner, axis=0)  # [nb, K]
                mask_r = jnp.take(mask, owner, axis=0)
                # pad slots carry id 0 / mask 0: no zero-row concatenate in
                # the scan, the masked reduction drops them
                part = eng._ell(ids_r, mask_r, h_cur)
                with jax.named_scope("exchange"):
                    h_nxt = jax.lax.ppermute(
                        h_cur, ax, [(i, (i - 1) % k) for i in range(k)])
                return (acc + part, h_nxt), None

            acc0 = jnp.zeros((nb, h_local.shape[1]), h_local.dtype)
            (acc, _), _ = jax.lax.scan(ring_step, (acc0, h_local),
                                       jnp.arange(k))
            # normalize ONCE after the scan: deg is constant across rounds
            return acc / deg
        # broadcast / p2p: chunked double-buffered exchange + ELL multiply
        agg = chunked_overlap(h_local, C, self.exchange_fn(cl),
                              lambda table: eng._ell(ids, mask, table))
        return agg / deg

    def gat_attend(self, p_l, Hw, cl):
        """Distributed edge-cut GAT: per-edge logits over the ELL structure,
        masked segment-softmax, attention-weighted gather-sum — pad slots
        stay inert (degree-0 rows get den == 0).

        broadcast/p2p ship ONE fused exchange of [a_src.Hw | Hw] (width
        d_out + 1): the attention-coefficient column rides as column 0 of
        chunk 0 of the chunked exchange instead of a separate width-1
        pre-pass.  Same bytes (rows x (d_out+1)), one less collective
        launch per layer, and bitwise-identical output: the exchange is a
        row-wise gather and the attend reduction is column-independent, so
        fusing/chunking never mixes columns."""
        eng = self.eng
        c = eng.cfg
        ids, mask = cl["ids"], cl["mask"]
        if c.execution == "ring":
            num, den = self._gat_ring(p_l, Hw, ids, mask)
        else:
            exchange = self.exchange_fn(cl)
            s_dst = (Hw @ p_l["a_dst"])[:, None]
            F = jnp.concatenate([(Hw @ p_l["a_src"])[:, None], Hw], 1)
            rows, Dtot = F.shape  # Dtot = d_out + 1
            C = feature_chunks(Dtot, c.exchange_chunks)

            def softmax_from(tab0):
                s_nbr = jnp.take(tab0[:, :1], ids, axis=0)[..., 0]
                e = jnp.where(mask > 0,
                              jax.nn.leaky_relu(s_dst + s_nbr, 0.2), -1e30)
                return eng._gat_softmax(e)

            if C <= 1:
                tab = exchange(F)
                pw, den = softmax_from(tab)
                num = eng._ell_attend(ids, pw, tab[:, 1:])
            else:
                Dc = chunk_width(Dtot, C)
                if C * Dc != Dtot:
                    F = jnp.pad(F, ((0, 0), (0, C * Dc - Dtot)))
                hs = F.reshape(rows, C, Dc).transpose(1, 0, 2)
                g0 = exchange(hs[0])
                # the fused pre-pass: softmax weights come from chunk 0's
                # first column, BEFORE chunk 0's attend is consumed — the
                # remaining chunks double-buffer exactly as chunked_overlap
                pw, den = softmax_from(g0)

                def body(g_cur, h_next):
                    g_next = exchange(h_next)
                    return g_next, eng._ell_attend(ids, pw, g_cur)

                g_last, outs = jax.lax.scan(body, g0, hs[1:])
                out = jnp.concatenate(
                    [outs, eng._ell_attend(ids, pw, g_last)[None]], 0)
                out = out.transpose(1, 0, 2).reshape(out.shape[1], C * Dc)
                # column 0 is the shipped s-column's attend (unused); pad
                # columns attend to zero — slice the Hw columns back out
                num = out[:, 1:Dtot]
        return num, den

    def _gat_ring(self, p_l, Hw, ids_all, mask_all):
        """Edge-cut ring GAT: one pass of online softmax (flash-attention
        style running max + rescale) over the k rotating source blocks — the
        exact masked softmax without a second max round.  The rotating block
        carries [Hw | a_src . Hw]; rotation r+1 is issued while rotation r
        feeds the gather (same double-buffering as the replica-sync ring)."""
        eng = self.eng
        ax, k, nb = eng.axis, eng.k, eng.nb
        me = jax.lax.axis_index(ax)
        s_dst = (Hw @ p_l["a_dst"])[:, None]
        blk0 = jnp.concatenate([Hw, (Hw @ p_l["a_src"])[:, None]], 1)
        perm = [(i, (i - 1) % k) for i in range(k)]

        def consume(carry, blk, owner):
            m, num, den = carry
            ids_r = jnp.take(ids_all, owner, axis=0)
            mask_r = jnp.take(mask_all, owner, axis=0)
            s_nbr = jnp.take(blk[:, -1], ids_r, axis=0)
            e = jnp.where(mask_r > 0,
                          jax.nn.leaky_relu(s_dst + s_nbr, 0.2), -1e30)
            m_new = jax.lax.stop_gradient(
                jnp.maximum(m, jnp.max(e, axis=1, keepdims=True)))
            sc = jnp.exp(m - m_new)
            pw = jnp.exp(e - m_new) * (e > -1e29)
            num = num * sc + eng._ell_attend(ids_r, pw, blk[:, :-1])
            den = den * sc + pw.sum(1, keepdims=True)
            return m_new, num, den

        carry = (jnp.full((nb, 1), -1e30, Hw.dtype),
                 jnp.zeros_like(Hw), jnp.zeros((nb, 1), Hw.dtype))
        carry = consume(carry, blk0, me)  # round 0: own block, no rotation
        if k == 1:
            return carry[1], carry[2]
        # exactly k-1 ppermute rounds, same prologue/scan/epilogue structure
        # as replica_sync._ring_combine (the scan-every-round form issued a
        # k-th rotation whose output was never consumed)
        with jax.named_scope("exchange"):
            blk1 = jax.lax.ppermute(blk0, ax, perm)

        def ring_step(carry_blk, r):
            carry, blk = carry_blk
            with jax.named_scope("exchange"):  # rotation r+1 flies ...
                blk_nxt = jax.lax.ppermute(blk, ax, perm)
            carry = consume(carry, blk, (me + r) % k)  # ... while r is used
            return (carry, blk_nxt), None

        (carry, blk_last), _ = jax.lax.scan(ring_step, (carry, blk1),
                                            jnp.arange(1, k - 1))
        _, num, den = consume(carry, blk_last, (me + k - 1) % k)
        return num, den


class ReplicaSyncBackend(ExchangeBackend):
    """Owned-edge partial aggregation + replica-sync combine, with an
    optional halo table for hybrid layouts whose owned edges read remote
    (low-degree, never-replicated) source rows."""

    def __init__(self, eng):
        super().__init__(eng)
        lay = eng.playout
        self.sync_active = getattr(lay, "sync_active", True)
        self.halo_active = getattr(lay, "halo_active", False)
        self.has_replicas = self.sync_active

    def _halo_table(self, hc, cl):
        """Gather table for one feature chunk: [local block (nv rows) |
        halo rows (canonical installment-major slots) | one zero row].
        Without a halo the table is the vertex-cut [h | zero] form, bit for
        bit.  Each canonical halo slot has exactly ONE real source; under
        broadcast/ring the other reads land on zero rows (sum-identity)."""
        eng = self.eng
        ax, k = eng.axis, eng.k
        if not self.halo_active:
            return jnp.concatenate([hc, zero_pad_row(hc)], 0)
        execution = eng.cfg.execution
        if execution == "broadcast":
            with jax.named_scope("exchange"):
                h_all = jax.lax.all_gather(hc, ax, axis=0, tiled=True)
            tab = jnp.concatenate([h_all, zero_pad_row(hc)], 0)
            halo = jnp.take(tab, cl["halo_src"], axis=0)  # [Hbuf, Dc]
        elif execution == "ring":
            me = jax.lax.axis_index(ax)
            perm = [(i, (i - 1) % k) for i in range(k)]
            Hbuf = cl["halo_ring"].shape[1]

            def ring_step(carry, r):
                acc, h_cur = carry
                owner = (me + r) % k
                idx = jnp.take(cl["halo_ring"], owner, axis=0)  # [Hbuf]
                tab = jnp.concatenate([h_cur, zero_pad_row(h_cur)], 0)
                acc = acc + jnp.take(tab, idx, axis=0)
                with jax.named_scope("exchange"):
                    h_nxt = jax.lax.ppermute(h_cur, ax, perm)
                return (acc, h_nxt), None

            acc0 = jnp.zeros((Hbuf, hc.shape[1]), hc.dtype)
            (halo, _), _ = jax.lax.scan(ring_step, (acc0, hc),
                                        jnp.arange(k))
        else:  # p2p: canonical order is built into the send table
            halo = bucketed_all_to_all(hc, cl["halo_send"], ax, k)
        return jnp.concatenate([hc, halo, zero_pad_row(hc)], 0)

    def aggregate(self, h_local, cl):
        eng = self.eng
        c = eng.cfg
        ax, k = eng.axis, eng.k
        ids, mask, deg = cl["ids"], cl["mask"], cl["deg"]
        if self.halo_active:
            partial = chunked_overlap(
                h_local, c.exchange_chunks,
                lambda hc: self._halo_table(hc, cl),
                lambda table: eng._ell(ids, mask, table))
        else:
            # partial aggregation over OWNED edges (replica-slot space)
            partial = eng._ell(ids, mask,
                               self._halo_table(h_local, cl))
        if self.sync_active:
            partial = replica_combine(c.execution, partial, cl, axis=ax,
                                      k=k, ell_fn=eng._ell,
                                      num_chunks=c.exchange_chunks)
        return partial / deg

    def gat_attend(self, p_l, Hw, cl):
        """GAT over owned edges: a two-pass (max, then sum) replica sync
        exactifies the segment-softmax normalizer across replicas.  When
        sync is inactive (hybrid at threshold=inf: no vertex replicates)
        the local floored max IS the exact stabilizer and the partial IS
        the total — both passes degenerate to identity, matching the
        reference's single-replica scatter combine bit for bit."""
        eng = self.eng
        c = eng.cfg
        ax, k = eng.axis, eng.k
        ids, mask = cl["ids"], cl["mask"]
        table = self._halo_table(Hw, cl)
        e = eng._sddmm(ids, mask, table, p_l["a_src"], p_l["a_dst"])
        m_loc = jnp.maximum(jnp.max(e, axis=1, keepdims=True), 0.0)
        if self.sync_active:
            M = jax.lax.stop_gradient(replica_combine_max(
                c.execution, m_loc, cl, axis=ax, k=k))
        else:
            M = jax.lax.stop_gradient(m_loc)
        pw = jnp.exp(e - M) * (e > -1e29)
        part = jnp.concatenate(
            [eng._ell_attend(ids, pw, table),
             pw.sum(1, keepdims=True)], 1)
        if self.sync_active:
            comb = replica_combine(c.execution, part, cl, axis=ax, k=k,
                                   ell_fn=eng._ell,
                                   num_chunks=c.exchange_chunks)
        else:
            comb = part
        return comb[:, :-1], comb[:, -1:]

    def combine_rows(self, rows, cl):
        if not self.sync_active:
            return rows
        eng, c = self.eng, self.eng.cfg
        return replica_combine(c.execution, rows, cl, axis=eng.axis,
                               k=eng.k, ell_fn=eng._ell,
                               num_chunks=c.exchange_chunks)


BACKENDS = {
    "edge_cut": EdgeCutBackend,
    "vertex_cut": ReplicaSyncBackend,
    "hybrid": ReplicaSyncBackend,
}


def make_backend(eng) -> ExchangeBackend:
    return BACKENDS[eng.playout.family](eng)
