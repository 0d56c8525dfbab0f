"""Distributed SpMM execution models (survey §6.2.2, Table 2), as shard_map
programs over jax.lax collectives.

The survey's taxonomy {replicated, 1D, 1.5D, 2D} x {A-, H-, P-stationary}
collapses to three execution shapes:
  C   (computation-only)              : spmm_replicated
  CC  (communication-computation)     : spmm_1d_broadcast (CAGNET 1D),
                                        spmm_1d_ring (chunk-based/pipelined,
                                        SAR/ParallelGCN), spmm_1d_p2p
                                        (selective boundary exchange)
  CCR (communication-computation-     : spmm_2d_summa (CAGNET 2D),
       reduction)                       spmm_15d

All functions compute Y = A @ H for a dense (normalized) adjacency A and
feature matrix H, partitioned per the model. Dense blocks keep the collective
structure identical to the sparse case while staying oracle-checkable; the
sparse local multiply is the ELL SpMM (repro.kernels).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map


def _axis1(mesh: Mesh) -> str:
    return mesh.axis_names[0]


def spmm_replicated(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """Computation-only (C): A replicated, H column-partitioned."""
    ax = _axis1(mesh)

    def local(A_full, H_cols):
        return A_full @ H_cols  # no communication at all

    return shard_map(local, mesh=mesh, in_specs=(P(), P(None, ax)),
                     out_specs=P(None, ax), check_vma=False)(A, H)


def spmm_1d_broadcast(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """1D P-stationary (CC), broadcast protocol (CAGNET 1D): every device owns
    a row block of A and H; H is all-gathered, Y row block stays local."""
    ax = _axis1(mesh)

    def local(A_rows, H_rows):
        H_full = jax.lax.all_gather(H_rows, ax, axis=0, tiled=True)
        return A_rows @ H_full

    return shard_map(local, mesh=mesh, in_specs=(P(ax, None), P(ax, None)),
                     out_specs=P(ax, None), check_vma=False)(A, H)


def spmm_1d_ring(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """1D CC with *sequential chunk-based execution* (survey §6.2.1) and the
    pipeline protocol (§7.1.3): H row-blocks rotate around a ppermute ring;
    each step accumulates the partial aggregation of one chunk (SAR-style;
    communication of the next chunk overlaps the current partial aggregation
    on real hardware)."""
    ax = _axis1(mesh)
    k = mesh.devices.size

    def local(A_rows, H_rows):
        n_block = H_rows.shape[0]
        me = jax.lax.axis_index(ax)

        def step(carry, r):
            acc, H_cur = carry
            # owner of the block currently held: (me + r) mod k
            owner = (me + r) % k
            A_blk = jax.lax.dynamic_slice_in_dim(A_rows, owner * n_block, n_block, axis=1)
            acc = acc + A_blk @ H_cur
            H_nxt = jax.lax.ppermute(H_cur, ax, [(i, (i - 1) % k) for i in range(k)])
            return (acc, H_nxt), None

        acc0 = jnp.zeros((A_rows.shape[0], H_rows.shape[1]), H_rows.dtype)
        (acc, _), _ = jax.lax.scan(step, (acc0, H_rows), jnp.arange(k))
        return acc

    return shard_map(local, mesh=mesh, in_specs=(P(ax, None), P(ax, None)),
                     out_specs=P(ax, None), check_vma=False)(A, H)


def p2p_plan(A_np: np.ndarray, k: int) -> Tuple[np.ndarray, int]:
    """Selective-P2P plan from block sparsity: which rows of H block j does
    device i actually need (nonzero columns of A[i,:] within block j)?
    Returns (need [k, k, cap] padded row indices within block, cap)."""
    V = A_np.shape[0]
    nb = V // k
    need_sets = [[(np.zeros(0, np.int64) if i == j else  # own block is local
                   np.unique(np.nonzero(A_np[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])[1]))
                  for j in range(k)] for i in range(k)]
    cap = max(1, max(len(s) for row in need_sets for s in row))
    need = np.zeros((k, k, cap), np.int32)
    cnt = np.zeros((k, k), np.int32)
    for i in range(k):
        for j in range(k):
            s = need_sets[i][j]
            need[i, j, : len(s)] = s
            cnt[i, j] = len(s)
    return need, cnt, cap


def spmm_1d_p2p(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray,
                plan: Tuple[np.ndarray, np.ndarray, int]) -> jnp.ndarray:
    """1D CC with selective P2P (ParallelGCN/DistGNN): only the boundary rows
    each pair actually needs are exchanged, via all_to_all of padded
    per-destination buffers. Communication ∝ cut size, not V."""
    ax = _axis1(mesh)
    k = mesh.devices.size
    need, cnt, cap = plan
    need_j = jnp.asarray(need)  # [dst, src, cap] rows of src block needed by dst
    cnt_j = jnp.asarray(cnt)

    def local(A_rows, H_rows):
        me = jax.lax.axis_index(ax)
        nb = H_rows.shape[0]
        # build send buffer: for each destination d, the rows of MY block that
        # d needs = need[d, me]
        rows_for = need_j[:, me, :]  # [k, cap]
        send = H_rows[rows_for.reshape(-1)].reshape(k, cap, H_rows.shape[1])
        recv = jax.lax.all_to_all(send, ax, split_axis=0, concat_axis=0)  # [k, cap, D]
        # scatter received rows into a sparse H view per source block
        acc = jnp.zeros((A_rows.shape[0], H_rows.shape[1]), H_rows.dtype)
        my_need = need_j[me]  # [k, cap] row ids within each source block
        my_cnt = cnt_j[me]
        for j in range(k):  # static loop over source blocks
            H_blk = jnp.zeros((nb, H_rows.shape[1]), H_rows.dtype)
            valid = (jnp.arange(cap) < my_cnt[j])[:, None]
            H_blk = H_blk.at[my_need[j]].add(jnp.where(valid, recv[j], 0.0))
            # the own block never crosses the wire: read it locally
            H_blk = jnp.where(me == j, H_rows, H_blk)
            A_blk = jax.lax.dynamic_slice_in_dim(A_rows, j * nb, nb, axis=1)
            acc = acc + A_blk @ H_blk
        return acc

    return shard_map(local, mesh=mesh, in_specs=(P(ax, None), P(ax, None)),
                     out_specs=P(ax, None), check_vma=False)(A, H)


def spmm_2d_summa(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """2D A-stationary (CCR, CAGNET 2D / SUMMA): grid (r x c) over both mesh
    axes. A block (i,j) is stationary; H row-blocks are gathered along grid
    columns, partials are reduce-scattered along grid rows."""
    ax_r, ax_c = mesh.axis_names

    def local(A_blk, H_blk):
        # H_blk: rows sharded over (r, c) jointly -> gather the column group's
        # rows: device (i,j) needs H rows of block-column j = all row chunks
        # held by column j across rows i' -> all_gather over ax_r.
        Hj = jax.lax.all_gather(H_blk, ax_r, axis=0, tiled=True)  # rows of block j
        part = A_blk @ Hj  # partial P[i, :] contribution from column j
        # reduce across the row (sum over j) and scatter rows so each (i,j)
        # ends with its chunk of P block-row i
        out = jax.lax.psum_scatter(part, ax_c, scatter_dimension=0, tiled=True)
        return out

    # H rows are laid out column-group-major: the devices of grid column j
    # jointly hold block-column j's rows, so the ax_r all-gather reassembles
    # exactly the rows A block (i,j) needs.
    return shard_map(local, mesh=mesh,
                     in_specs=(P(ax_r, ax_c), P((ax_c, ax_r), None)),
                     out_specs=P((ax_r, ax_c), None), check_vma=False)(A, H)


def spmm_15d(mesh: Mesh, A: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """1.5D A-stationary (CCR): A is 2D-partitioned (r x c); H is 1D
    row-partitioned over c (replicated over r). Partials reduce over c."""
    ax_r, ax_c = mesh.axis_names

    def local(A_blk, H_blk):
        part = A_blk @ H_blk  # A block (i,j) x H rows of block j
        out = jax.lax.psum_scatter(part, ax_c, scatter_dimension=0, tiled=True)
        return out

    return shard_map(local, mesh=mesh,
                     in_specs=(P(ax_r, ax_c), P(ax_c, None)),
                     out_specs=P((ax_r, ax_c), None), check_vma=False)(A, H)


SPMM_MODELS = {
    "replicated": spmm_replicated,
    "spmm_1d": spmm_1d_broadcast,
    "spmm_1d_ring": spmm_1d_ring,
    "spmm_2d": spmm_2d_summa,
    "spmm_15d": spmm_15d,
}
