"""Chunked communication/compute overlap for the jitted exchange (survey
§6-§7 pipelining, CAGNET-style).

The engine's broadcast/p2p exchanges used to materialize the FULL gathered
neighbor table (all rows x all feature columns) before a single ELL multiply
ran: peak per-device memory O(V*D) and zero overlap between the wire and the
MXU.  This module splits the feature dimension into C static chunks and
software-pipelines them with a double-buffered `jax.lax.scan`: the collective
for chunk c+1 is ISSUED in the same scan step that the consumer (the Pallas
ELL multiply) processes chunk c, so XLA's async collectives can hide wire
time behind compute, and at most TWO chunk-sized gathered tables are ever
live — peak O(V*D/C).

Feature columns are independent in every consumer the engine has (masked
gather-sum over K neighbors, plain row gather), so the chunked exchange is
numerically identical to the monolithic one column by column.

Also here: the power-of-two BUCKETED p2p installment schedule.  A single
all_to_all must pad every (src, dst) pair to the max pairwise need, so one
heavy pair inflates the lowered send buffer k-fold; splitting the cap into B
power-of-two installments keeps each all_to_all operand at k*w rows
(w ~ cap/B) while shipping exactly the same rows overall.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.execution.bucketing import (  # noqa: F401 — re-exported API
    bucketed_cap_widths,
    bucketed_send_table,
    halo_slot,
)
from repro.core.partition.cost_models import FEAT_BYTES


# ---------------------------------------------------------------------------
# Feature-dim chunking (double-buffered exchange/consume overlap)
# ---------------------------------------------------------------------------


def feature_chunks(D: int, num_chunks: int) -> int:
    """Effective static chunk count: clipped to [1, D]."""
    return max(1, min(int(num_chunks), int(D)))


def chunk_width(D: int, num_chunks: int) -> int:
    """Per-chunk feature width (ceil division)."""
    C = feature_chunks(D, num_chunks)
    return -(-int(D) // C)


def zero_pad_row(h: jnp.ndarray) -> jnp.ndarray:
    """The one-row zero pad every gather table appends so pad/absent ids
    read zeros — shared here so the pad-row convention lives in one place."""
    return jnp.zeros((1, h.shape[1]), h.dtype)


def chunked_overlap(h: jnp.ndarray, num_chunks: int,
                    exchange_fn: Callable, consume_fn: Callable) -> jnp.ndarray:
    """Software-pipelined per-feature-chunk exchange.

    ``h`` [rows, D] is split into C static chunks along the feature axis;
    ``exchange_fn(h_chunk [rows, Dc]) -> pytree`` issues the collective for
    one chunk (all_gather / all_to_all + table assembly) and
    ``consume_fn(pytree) -> [out_rows, Dc]`` is the chunk consumer (the ELL
    multiply / row gather).  The scan carries the prefetched chunk: per step
    the collective for chunk c+1 is issued while chunk c is consumed — the
    two are data-independent inside the step, which is exactly the pattern
    XLA's async collectives overlap.  With C == 1 this is the monolithic
    exchange, bit for bit.
    """
    rows, D = h.shape
    C = feature_chunks(D, num_chunks)
    if C <= 1:
        return consume_fn(exchange_fn(h))
    Dc = chunk_width(D, C)
    if C * Dc != D:
        h = jnp.pad(h, ((0, 0), (0, C * Dc - D)))
    hs = h.reshape(rows, C, Dc).transpose(1, 0, 2)  # [C, rows, Dc]
    g0 = exchange_fn(hs[0])

    def body(g_cur, h_next):
        g_next = exchange_fn(h_next)  # issue chunk c+1's collective ...
        out = consume_fn(g_cur)       # ... while chunk c feeds the multiply
        return g_next, out

    g_last, outs = jax.lax.scan(body, g0, hs[1:])
    out = jnp.concatenate([outs, consume_fn(g_last)[None]], axis=0)
    out = out.transpose(1, 0, 2).reshape(out.shape[1], C * Dc)
    return out[:, :D] if C * Dc != D else out


def gathered_table_peak_bytes(rows: int, D: int, num_chunks: int,
                              feat_bytes: int = FEAT_BYTES) -> int:
    """Peak bytes of the gathered neighbor table live at once on one device
    for the broadcast exchange: the monolithic path keeps the full
    rows x D table; the double-buffered chunked path keeps at most TWO
    rows x ceil(D/C) chunk tables (current + prefetched)."""
    C = feature_chunks(D, num_chunks)
    if C <= 1:
        return int(rows) * int(D) * feat_bytes
    return 2 * int(rows) * chunk_width(D, C) * feat_bytes


# ---------------------------------------------------------------------------
# Power-of-two bucketed p2p installments
# ---------------------------------------------------------------------------
# The static slot layout (bucketed_cap_widths / halo_slot /
# bucketed_send_table) lives in `bucketing.py` — numpy-only so the
# process-pool sampling workers can build fetch plans without importing jax —
# and is re-exported above.  Only the jax collective lives here.


def bucketed_all_to_all(h: jnp.ndarray, send_rows: jnp.ndarray, axis: str,
                        k: int) -> jnp.ndarray:
    """The installment all_to_alls: ``send_rows`` [B, k, w] holds, per
    installment b and destination d, the local row ids this device ships.
    Returns the received halo rows [B*k*w, D] in installment-major order
    (matching `halo_slot`).  Each round's send operand is k*w rows — the
    lowered all_to_all buffer is ``B``x smaller than the monolithic
    k*cap-row send, and the rounds are independent so they pipeline.  The
    rounds run under the ``exchange`` scope, as every collective that moves
    rows does."""
    B, k2, w = send_rows.shape
    assert k2 == k, (send_rows.shape, k)
    D = h.shape[1]
    recvs = []
    with jax.named_scope("exchange"):
        for b in range(B):  # static unroll; a round's buffers die after use
            send = h[send_rows[b].reshape(-1)].reshape(k, w, D)
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0)
            recvs.append(recv.reshape(k * w, D))
        return recvs[0] if B == 1 else jnp.concatenate(recvs, axis=0)
