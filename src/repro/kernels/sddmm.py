"""Pallas TPU kernel: SDDMM-style GAT edge scores on ELL structure.

e[v, k] = LeakyReLU(a_dst . Hw[v]  +  a_src . Hw[ids[v, k]]), masked -> -1e30.

The score is separable: each row's two coefficients s_src = Hw @ a_src and
s_dst = Hw @ a_dst are one matrix-vector product over the table, so an edge
needs one gathered scalar, never a gathered feature row.  The products and
the [V, K] scalar gather run in XLA; the kernel fuses the per-edge combine
(add, LeakyReLU, mask) over row blocks of the ELL slot grid.  Nothing in it
scales with the table: per program it holds [rb, K] scores and [rb, 1]
destination coefficients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import round_up


def _sddmm_kernel(snbr_ref, sdst_ref, mask_ref, out_ref, *, slope: float):
    e = sdst_ref[...] + snbr_ref[...]  # [rb, 1] + [rb, K]
    e = jnp.where(e > 0, e, slope * e)
    out_ref[...] = jnp.where(mask_ref[...] > 0, e, -1e30).astype(out_ref.dtype)


def sddmm_pallas(ids: jnp.ndarray, mask: jnp.ndarray, Hw: jnp.ndarray,
                 a_src: jnp.ndarray, a_dst: jnp.ndarray, *, slope: float = 0.2,
                 row_block: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Masked edge logits; destination row v is table row v (the table's
    first V rows are the dst rows).  Rows are padded to the grid."""
    V, K = ids.shape
    s_nbr = jnp.take(Hw @ a_src, ids, axis=0)  # [V, K]
    s_dst = (Hw[:V] @ a_dst)[:, None]  # [V, 1]
    rb = max(8, min(row_block, round_up(V, 8)))
    Vp = round_up(V, rb)
    mask = mask.astype(jnp.float32)
    if Vp != V:  # pad rows: mask 0 -> -1e30 logits, sliced away
        pad = ((0, Vp - V), (0, 0))
        s_nbr, s_dst, mask = (jnp.pad(x, pad) for x in (s_nbr, s_dst, mask))
    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, slope=slope),
        grid=(Vp // rb,),
        in_specs=[
            pl.BlockSpec((rb, K), lambda i: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i: (i, 0)),
            pl.BlockSpec((rb, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp, K), jnp.float32),
        interpret=interpret,
        name="sddmm",
    )(s_nbr.astype(jnp.float32), s_dst.astype(jnp.float32), mask)
    return out[:V] if Vp != V else out


# ---------------------------------------------------------------------------
# Differentiable wrapper (the distributed GAT path)
# ---------------------------------------------------------------------------
#
# pallas_call carries no autodiff rule, but the edge-score VJP is analytic:
# with z = s_dst[v] + s_src[ids[v,k]] the masked logits e = LeakyReLU(z) give
#   de/dHw = scatter(dz) * a_dst + scatter_over_ids(dz) * a_src
# — two dense rank-1 products plus a scatter-add, all XLA-native.  ids/mask
# are graph structure (non-differentiable); masked slots emit the constant
# -1e30, so their cotangent is dropped.
#
# Contract (same as the kernel): destination row v's features live at table
# row v — the table's first V rows ARE the dst rows.  Rows are padded to the
# grid here, so any V works.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _sddmm_vjp(slope, row_block, interpret, ids, mask, Hw, a_src, a_dst):
    return sddmm_pallas(ids, mask, Hw, a_src, a_dst, slope=slope,
                        row_block=row_block, interpret=interpret)


def _sddmm_fwd(slope, row_block, interpret, ids, mask, Hw, a_src, a_dst):
    out = sddmm_pallas(ids, mask, Hw, a_src, a_dst, slope=slope,
                       row_block=row_block, interpret=interpret)
    return out, (ids, mask, Hw, a_src, a_dst)


def _sddmm_bwd(slope, row_block, interpret, res, ct):
    ids, mask, Hw, a_src, a_dst = res
    V, K = ids.shape
    N = Hw.shape[0]
    s_dst = Hw @ a_dst  # [N]
    s_src = Hw @ a_src
    z = s_dst[:V, None] + jnp.take(s_src, ids, axis=0)
    dz = ct.astype(jnp.float32) * jnp.where(z > 0, 1.0, slope) * (mask > 0)
    g_dst = jnp.zeros((N,), jnp.float32).at[:V].set(dz.sum(1))
    g_src = jnp.zeros((N,), jnp.float32).at[ids.reshape(-1)].add(
        dz.reshape(-1))
    dHw = (g_dst[:, None] * a_dst[None, :]
           + g_src[:, None] * a_src[None, :]).astype(Hw.dtype)
    da_dst = (Hw * g_dst[:, None]).sum(0).astype(a_dst.dtype)
    da_src = (Hw * g_src[:, None]).sum(0).astype(a_src.dtype)
    return (jnp.zeros(ids.shape, jax.dtypes.float0), jnp.zeros_like(mask),
            dHw, da_src, da_dst)


_sddmm_vjp.defvjp(_sddmm_fwd, _sddmm_bwd)


def sddmm_ell(ids: jnp.ndarray, mask: jnp.ndarray, Hw: jnp.ndarray,
              a_src: jnp.ndarray, a_dst: jnp.ndarray, *, slope: float = 0.2,
              row_block: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Differentiable masked GAT edge logits over ELL structure: Pallas
    forward (rows padded to the grid), analytic VJP for Hw / a_src / a_dst.

    e[v, k] = LeakyReLU(a_dst . Hw[v] + a_src . Hw[ids[v, k]]), masked slots
    -> -1e30.  Destination row v must be table row v (the table's first V
    rows are the dst rows — the engine's local/p2p/reference layouts)."""
    return _sddmm_vjp(slope, row_block, interpret, ids,
                      mask.astype(jnp.float32), Hw, a_src, a_dst)
