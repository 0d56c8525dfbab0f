"""Pallas TPU kernels: ELLPACK neighbour aggregation over a table in HBM.

TPU adaptation of CSR gather-SpMM (DESIGN.md §2): neighbour lists are padded
to width K (ELLPACK), so a block of ``rb`` destination rows has a static
``[rb, K]`` slot grid.  The feature table never enters VMEM whole: it stays
in HBM, the row block's ids arrive in SMEM, and each slot's ``rb`` neighbour
rows are DMA'd into a double-buffered VMEM tile — slot k+1's rows are in
flight while slot k is reduced.  Slots whose weight is zero issue no DMA.

Grid (row blocks, feature blocks) for the gather-sum; per program:
  ids   [rb*K]        int32  SMEM  — the block's neighbour ids, row-major
  w     [rb, K]       f32    VMEM  — mask or attention weights
  H     [N, 1, Dp]           HBM   — the table; one row per DMA
  out   [rb, fb]      f32
  buf   [2, rb, 1, fb]       VMEM  — the double-buffered gathered slot

Backward passes never build a ``[V, K, D]`` (or ``[V*K, D]``) temporary:
the table gradient is the transposed aggregation, summed one slot at a time
with an XLA scatter-add (peak O(N*D + V*D)), and the weight gradient of
``ell_attend`` is the gather-dot kernel below, which reuses the DMA loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import round_up

_SMEM_IDS = 16384  # ids per row block held in SMEM (x2 buffers, 128 KiB)
_SMEM_TILE = 1024  # Mosaic tiles a 1-D int32 SMEM operand by 1024 words
_LANES = 128  # a DMA'd row slice must cover whole 128-lane tiles


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def _blocks(V: int, K: int, row_block: int):
    """(rb, Kp): rows per grid program — a power of two >= 8 within
    ``row_block``, the SMEM id budget and the row count — and the slot width
    padded so a block's flattened ids fill whole SMEM tiles (pad slots carry
    id -1 and weight 0: no DMA, no contribution)."""
    rb = min(_pow2_floor(row_block), _pow2_floor(_SMEM_IDS // max(K, 1)),
             max(8, 1 << (max(V, 1) - 1).bit_length()))
    rb = max(8, rb)
    return rb, round_up(K, max(1, _SMEM_TILE // rb))


def _slot_loop(ids_ref, h_hbm, buf, sem, *, K: int, rb: int, col, fb: int,
               consume, init):
    """For k = 0..K-1: gather the rows ``h[ids[r, k]]`` (r < rb; ids < 0 are
    skipped) into ``buf[k % 2]`` and fold them into the carry with
    ``consume(k, rows [rb, fb] f32, carry)``; the DMAs of slot k+1 are
    started before slot k is waited on."""

    def dmas(k, slot, start: bool):
        def one(r, c):
            row = ids_ref[r * K + k]

            @pl.when(row >= 0)
            def _():
                cp = pltpu.make_async_copy(
                    h_hbm.at[row, :, pl.ds(col, fb)], buf.at[slot, r],
                    sem.at[slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return c

        jax.lax.fori_loop(0, rb, one, 0)

    dmas(0, 0, True)

    def body(k, carry):
        slot = k % 2

        @pl.when(k + 1 < K)
        def _():
            dmas(k + 1, 1 - slot, True)

        dmas(k, slot, False)
        return consume(k, buf[slot][:, 0, :].astype(jnp.float32), carry)

    return jax.lax.fori_loop(0, K, body, init)


def _gather_sum_kernel(ids_ref, w_ref, h_hbm, out_ref, buf, sem, *, K, rb,
                       fb):
    w = w_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)

    def consume(k, rows, acc):
        wk = jnp.sum(jnp.where(lane == k, w, 0.0), axis=1, keepdims=True)
        # a skipped slot leaves stale (or uninitialised) rows in the buffer:
        # select, never multiply, so they cannot leak NaNs into the sum
        return acc + jnp.where(wk != 0, wk * rows, 0.0)

    acc = _slot_loop(ids_ref, h_hbm, buf, sem, K=K, rb=rb,
                     col=pl.program_id(1) * fb, fb=fb, consume=consume,
                     init=jnp.zeros((rb, fb), jnp.float32))
    out_ref[...] = acc.astype(out_ref.dtype)


def _gather_dot_kernel(ids_ref, ct_ref, h_hbm, out_ref, buf, sem, *, K, rb,
                       fb):
    ct = ct_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rb, K), 1)

    def consume(k, rows, acc):
        return acc + jnp.where(lane == k,
                               jnp.sum(ct * rows, axis=1, keepdims=True), 0.0)

    out_ref[...] = _slot_loop(ids_ref, h_hbm, buf, sem, K=K, rb=rb, col=0,
                              fb=fb, consume=consume,
                              init=jnp.zeros((rb, K), jnp.float32))


def _pad(x, Vp, Kp):
    """Zero-pad a [V, K] slot table to [Vp, Kp]."""
    V, K = x.shape
    return x if (V, K) == (Vp, Kp) else jnp.pad(x, ((0, Vp - V), (0, Kp - K)))


def _safe_ids(ids, keep, N):
    """Flattened SMEM ids: in-range where ``keep``, -1 (no DMA) elsewhere."""
    return jnp.where(keep, jnp.clip(ids, 0, N - 1), -1).astype(
        jnp.int32).reshape(-1)


def gather_sum_pallas(ids, w, H, *, row_block: int = 128,
                      feat_block=None, interpret: bool = False):
    """out[v] = sum_k w[v,k] * H[ids[v,k]] (f32 accumulation, H's dtype out).
    Rows and features that do not tile are zero-padded to the block (a
    feature block is a whole number of 128-lane tiles); slots with w == 0
    are skipped (they contribute nothing)."""
    V, K = ids.shape
    N, D = H.shape
    rb, K = _blocks(V, K, row_block)
    fb = round_up(min(feat_block or D, D), _LANES)
    Vp, Dp = round_up(V, rb), round_up(D, fb)
    w = _pad(w.astype(jnp.float32), Vp, K)
    ids = _safe_ids(_pad(ids, Vp, K), w != 0, N)
    if Dp != D:
        H = jnp.pad(H, ((0, 0), (0, Dp - D)))
    out = pl.pallas_call(
        functools.partial(_gather_sum_kernel, K=K, rb=rb, fb=fb),
        grid=(Vp // rb, Dp // fb),
        in_specs=[
            pl.BlockSpec((rb * K,), lambda i, j: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((rb, K), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rb, fb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Vp, Dp), H.dtype),
        scratch_shapes=[pltpu.VMEM((2, rb, 1, fb), H.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        name="gather_sum",
    )(ids, w, H.reshape(N, 1, Dp))
    return out[:V, :D] if (Vp, Dp) != (V, D) else out


def gather_dot_pallas(ids, ct, H, *, row_block: int = 128,
                      interpret: bool = False):
    """out[v, k] = ct[v] . H[ids[v, k]] — the SDDMM-shaped transpose of the
    weights of `gather_sum_pallas` (every slot is gathered)."""
    V, K0 = ids.shape
    N, D0 = H.shape
    rb, K = _blocks(V, K0, row_block)
    Vp, D = round_up(V, rb), round_up(D0, _LANES)
    slot = jax.lax.broadcasted_iota(jnp.int32, (Vp, K), 1)
    ids = _safe_ids(_pad(ids, Vp, K), slot < K0, N)
    ct = jnp.pad(ct, ((0, Vp - V), (0, D - D0)))  # zero columns add nothing
    if D != D0:
        H = jnp.pad(H, ((0, 0), (0, D - D0)))
    out = pl.pallas_call(
        functools.partial(_gather_dot_kernel, K=K, rb=rb, fb=D),
        grid=(Vp // rb,),
        in_specs=[
            pl.BlockSpec((rb * K,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((rb, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rb, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, rb, 1, D), H.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        name="gather_dot",
    )(ids, ct, H.reshape(N, 1, D))
    return out[:V, :K0]


def _mean(out, mask):
    """The sum over a row's slots divided by its degree (at least 1)."""
    deg = jnp.maximum(mask.sum(1, keepdims=True), 1.0)
    return (out.astype(jnp.float32) / deg).astype(out.dtype)


def ell_spmm_pallas(ids: jnp.ndarray, mask: jnp.ndarray, H: jnp.ndarray, *,
                    row_block: int = 128, feat_block=None,
                    normalize: bool = True,
                    interpret: bool = False) -> jnp.ndarray:
    """out[v] = sum_k mask[v,k] * H[ids[v,k]]  (/ max(deg[v], 1) if
    normalize)."""
    mask = mask.astype(jnp.float32)
    out = gather_sum_pallas(ids, mask, H, row_block=row_block,
                            feat_block=feat_block, interpret=interpret)
    return _mean(out, mask) if normalize else out


# ---------------------------------------------------------------------------
# XLA forms of the same sums (EngineConfig(use_pallas=False)), one slot at a
# time so they too stay O(V*D)
# ---------------------------------------------------------------------------


def _slot(x, k):
    return jax.lax.dynamic_index_in_dim(x, k, axis=1, keepdims=False)


def gather_sum_xla(ids, w, H):
    def body(k, acc):
        return acc + _slot(w, k)[:, None] * jnp.take(H, _slot(ids, k), axis=0)

    return jax.lax.fori_loop(
        0, ids.shape[1], body,
        jnp.zeros((ids.shape[0], H.shape[1]), jnp.float32)).astype(H.dtype)


def gather_dot_xla(ids, ct, H):
    def body(k, out):
        col = (ct * jnp.take(H, _slot(ids, k), axis=0)).sum(1)
        return jax.lax.dynamic_update_index_in_dim(out, col, k, axis=1)

    return jax.lax.fori_loop(0, ids.shape[1], body,
                             jnp.zeros(ids.shape, jnp.float32))


def transpose_sum(ids, w, ct, N):
    """dH[u] = sum_{(v,k): ids[v,k] = u} w[v,k] * ct[v] — the transposed
    aggregation, scatter-added one slot at a time (peak O(N*D + V*D))."""
    ct = ct.astype(jnp.float32)

    def body(k, dH):
        return dH.at[_slot(ids, k)].add(_slot(w, k)[:, None] * ct)

    return jax.lax.fori_loop(0, ids.shape[1], body,
                             jnp.zeros((N, ct.shape[1]), jnp.float32))


# ---------------------------------------------------------------------------
# Differentiable wrappers
# ---------------------------------------------------------------------------
#
# pallas_call carries no autodiff rule; the aggregation's VJP w.r.t. H is the
# transpose SpMM (`transpose_sum`).  ids and mask are graph structure
# (non-differentiable); `ell_attend`'s weights do get a gradient.  The static
# ``kern`` tuple is (use_pallas, interpret, row_block, feat_block).


def _fwd_sum(kern, ids, w, H):
    use_pallas, interpret, row_block, feat_block = kern
    if not use_pallas:
        return gather_sum_xla(ids, w, H)
    return gather_sum_pallas(ids, w, H, row_block=row_block,
                             feat_block=feat_block, interpret=interpret)


def _fwd_dot(kern, ids, ct, H):
    use_pallas, interpret, row_block, _ = kern
    if not use_pallas:
        return gather_dot_xla(ids, ct, H)
    return gather_dot_pallas(ids, ct, H, row_block=row_block,
                             interpret=interpret)


def _no_grad(x):
    return (jnp.zeros(x.shape, jax.dtypes.float0)
            if jnp.issubdtype(x.dtype, jnp.integer) else jnp.zeros_like(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ell_spmm_vjp(normalize, kern, ids, mask, H):
    out = _fwd_sum(kern, ids, mask, H)
    return _mean(out, mask) if normalize else out


def _ell_spmm_fwd(normalize, kern, ids, mask, H):
    return _ell_spmm_vjp(normalize, kern, ids, mask, H), (ids, mask,
                                                          H.shape[0])


def _ell_spmm_bwd(normalize, kern, res, ct):
    ids, mask, N = res
    ctn = ct.astype(jnp.float32)
    if normalize:
        ctn = _mean(ctn, mask)
    dH = transpose_sum(ids, mask, ctn, N).astype(ct.dtype)
    return _no_grad(ids), _no_grad(mask), dH


_ell_spmm_vjp.defvjp(_ell_spmm_fwd, _ell_spmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ell_attend_vjp(kern, ids, w, H):
    return _fwd_sum(kern, ids, w, H)


def _ell_attend_fwd(kern, ids, w, H):
    return _fwd_sum(kern, ids, w, H), (ids, w, H)


def _ell_attend_bwd(kern, res, ct):
    ids, w, H = res
    dH = transpose_sum(ids, w, ct, H.shape[0]).astype(ct.dtype)
    # dL/dw[v,k] = ct[v] . H[ids[v,k]] — the gather-dot kernel
    dw = _fwd_dot(kern, ids, ct, H).astype(w.dtype)
    return _no_grad(ids), dw, dH


_ell_attend_vjp.defvjp(_ell_attend_fwd, _ell_attend_bwd)


def ell_attend(ids: jnp.ndarray, weights: jnp.ndarray, H: jnp.ndarray, *,
               interpret: bool = False, row_block: int = 128,
               feat_block=None, use_pallas: bool = True) -> jnp.ndarray:
    """Attention-weighted ELL sum: out[v] = sum_k weights[v,k] * H[ids[v,k]],
    with gradients flowing to BOTH ``weights`` and ``H``.

    Same forward as `ell_spmm` (the weights ride the mask lane), but where
    `ell_spmm` treats the mask as graph structure (zero cotangent), GAT's
    attention coefficients are a function of the params — their VJP is the
    SDDMM-shaped gather product ct[v] . H[ids[v,k]]."""
    return _ell_attend_vjp((use_pallas, interpret, row_block, feat_block),
                           ids, weights.astype(jnp.float32), H)


def ell_spmm(ids: jnp.ndarray, mask: jnp.ndarray, H: jnp.ndarray, *,
             normalize: bool = True, interpret: bool = False,
             row_block: int = 128, feat_block=None,
             use_pallas: bool = True) -> jnp.ndarray:
    """Differentiable ELL SpMM: DMA-gather forward, transposed-sum backward.

    out[v] = sum_k mask[v,k] * H[ids[v,k]]  (/ max(deg[v], 1) if normalize)

    ids/mask may be traced values (e.g. selected per ring step inside a scan);
    only H carries gradient.  ``row_block``/``feat_block`` tune the Pallas
    grid (both clipped to the operand); ``use_pallas=False`` runs the same
    sums as plain XLA gathers.
    """
    return _ell_spmm_vjp(normalize,
                         (use_pallas, interpret, row_block, feat_block),
                         ids, mask.astype(jnp.float32), H)
