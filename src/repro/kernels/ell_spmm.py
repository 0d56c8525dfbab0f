"""ELLPACK neighbour aggregation: an XLA gather-sum forward, a Pallas TPU
gather-dot for the attention weights' gradient.

Neighbour lists are padded to width K (ELLPACK, DESIGN.md §2), so the
aggregation is a static ``[V, K]`` slot grid.  Pad slots carry weight 0
and an id in range (the engine points them at the zero row every gather
table carries).

The forward ``out[v] = sum_k w[v,k] * H[ids[v,k]]`` is XLA's own gather,
one slot at a time (`gather_sum_xla`, under the scope ``gather_sum``):
for a chunk of rows, slot k's rows are gathered and folded into an f32
accumulator, so no ``[V, K, D]`` temporary is built.

Backward passes never build a ``[V, K, D]`` (or ``[V*K, D]``) temporary
either: the table gradient is the transposed aggregation, summed one slot
at a time with an XLA scatter-add (peak O(N*D + V*D)), and the weight
gradient of ``ell_attend`` is the gather-dot kernel below.  It keeps the
table in HBM; the row block's ids arrive in SMEM, and each slot's ``rb``
neighbour rows are DMA'd into a double-buffered VMEM tile — slot k+1's
rows are in flight while slot k is reduced.  Per grid program:
  ids   [rb*K]        int32  SMEM  — the block's neighbour ids, row-major
  ct    [rb, D]       f32    VMEM  — the output's cotangent
  H     [N, 1, D]            HBM   — the table; one row per DMA
  out   [rb, K]       f32
  buf   [2, rb, 1, D]        VMEM  — the double-buffered gathered slot
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
# rows per chunk of the XLA gather-sum: of 4096-32768, the fastest on a TPU
# v5e at 256 wide (2^20 rows, K = 38), and its loop stays in VMEM
_SUM_ROWS = 8192


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def _blocks(V: int, K: int, row_block: int):
    """(rb, Kp): rows per grid program — a power of two >= 8 within
    ``row_block``, the SMEM id budget and the row count — and the slot width
    padded so a block's flattened ids fill whole SMEM tiles (pad slots carry
    id -1: no DMA, and their outputs are sliced off)."""
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


def gather_dot_pallas(ids, ct, H, *, row_block: int = 128,
                      interpret: bool = False):
    """out[v, k] = ct[v] . H[ids[v, k]] — the SDDMM-shaped transpose of the
    weights of `gather_sum_xla` (every slot is gathered)."""
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


# ---------------------------------------------------------------------------
# XLA forms, one slot at a time so they stay O(V*D); gather_dot_xla is the
# EngineConfig(use_pallas=False) form of the gather-dot kernel
# ---------------------------------------------------------------------------


def _slot(x, k):
    return jax.lax.dynamic_index_in_dim(x, k, axis=1, keepdims=False)


def gather_sum_xla(ids, w, H):
    """out[v] = sum_k w[v,k] * H[ids[v,k]], slots in order, f32 accumulation,
    H's dtype out.  Ids are clipped into range, as the gather-dot kernel
    clips them.

    Rows go in chunks of ``_SUM_ROWS``: a chunk's accumulator and its
    gathered slot are small enough for XLA to keep on chip, where a
    whole-table loop writes every slot's ``[V, D]`` gathered rows to HBM and
    reads them back.  The last chunk is aligned to the table's end and
    recomputes, bit for bit, the rows it shares with the one before."""
    V, K = ids.shape
    r = max(1, min(_SUM_ROWS, V))

    def chunk(c, out):
        lo = jnp.minimum(c * r, V - r)
        ids_c = jax.lax.dynamic_slice_in_dim(ids, lo, r)
        w_c = jax.lax.dynamic_slice_in_dim(w, lo, r)

        def body(k, acc):
            rows = jnp.take(H, _slot(ids_c, k), axis=0, mode="clip")
            return acc + _slot(w_c, k)[:, None] * rows

        acc = jax.lax.fori_loop(0, K, body,
                                jnp.zeros((r, H.shape[1]), jnp.float32))
        return jax.lax.dynamic_update_slice_in_dim(
            out, acc.astype(H.dtype), lo, axis=0)

    with jax.named_scope("gather_sum"):
        return jax.lax.fori_loop(0, pl.cdiv(V, r), chunk,
                                 jnp.zeros((V, H.shape[1]), H.dtype))


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
# The aggregation's VJP w.r.t. H is the transpose SpMM (`transpose_sum`).
# ids and mask are graph structure (non-differentiable); `ell_attend`'s
# weights do get a gradient, from the gather-dot kernel (pallas_call carries
# no autodiff rule).  Its static ``kern`` tuple is (use_pallas, interpret,
# row_block).


def _fwd_dot(kern, ids, ct, H):
    use_pallas, interpret, row_block = kern
    if not use_pallas:
        return gather_dot_xla(ids, ct, H)
    return gather_dot_pallas(ids, ct, H, row_block=row_block,
                             interpret=interpret)


def _no_grad(x):
    return (jnp.zeros(x.shape, jax.dtypes.float0)
            if jnp.issubdtype(x.dtype, jnp.integer) else jnp.zeros_like(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ell_spmm_vjp(normalize, ids, mask, H):
    out = gather_sum_xla(ids, mask, H)
    return _mean(out, mask) if normalize else out


def _ell_spmm_fwd(normalize, ids, mask, H):
    return _ell_spmm_vjp(normalize, ids, mask, H), (ids, mask, H.shape[0])


def _ell_spmm_bwd(normalize, res, ct):
    ids, mask, N = res
    ctn = ct.astype(jnp.float32)
    if normalize:
        ctn = _mean(ctn, mask)
    dH = transpose_sum(ids, mask, ctn, N).astype(ct.dtype)
    return _no_grad(ids), _no_grad(mask), dH


_ell_spmm_vjp.defvjp(_ell_spmm_fwd, _ell_spmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ell_attend_vjp(kern, ids, w, H):
    return gather_sum_xla(ids, w, H)


def _ell_attend_fwd(kern, ids, w, H):
    return gather_sum_xla(ids, w, H), (ids, w, H)


def _ell_attend_bwd(kern, res, ct):
    ids, w, H = res
    dH = transpose_sum(ids, w, ct, H.shape[0]).astype(ct.dtype)
    # dL/dw[v,k] = ct[v] . H[ids[v,k]] — the gather-dot kernel
    dw = _fwd_dot(kern, ids, ct, H).astype(w.dtype)
    return _no_grad(ids), dw, dH


_ell_attend_vjp.defvjp(_ell_attend_fwd, _ell_attend_bwd)


def ell_attend(ids: jnp.ndarray, weights: jnp.ndarray, H: jnp.ndarray, *,
               interpret: bool = False, row_block: int = 128,
               use_pallas: bool = True) -> jnp.ndarray:
    """Attention-weighted ELL sum: out[v] = sum_k weights[v,k] * H[ids[v,k]],
    with gradients flowing to BOTH ``weights`` and ``H``.

    Same forward as `ell_spmm` (the weights ride the mask lane), but where
    `ell_spmm` treats the mask as graph structure (zero cotangent), GAT's
    attention coefficients are a function of the params — their VJP is the
    SDDMM-shaped gather product ct[v] . H[ids[v,k]]: the gather-dot kernel,
    whose grid ``row_block`` tunes, or with ``use_pallas=False`` its XLA
    form."""
    return _ell_attend_vjp((use_pallas, interpret, row_block),
                           ids, weights.astype(jnp.float32), H)


def ell_spmm(ids: jnp.ndarray, mask: jnp.ndarray, H: jnp.ndarray, *,
             normalize: bool = True) -> jnp.ndarray:
    """Differentiable ELL SpMM: slot-wise gather forward, transposed-sum
    backward.

    out[v] = sum_k mask[v,k] * H[ids[v,k]]  (/ max(deg[v], 1) if normalize)

    ids/mask may be traced values (e.g. selected per ring step inside a scan);
    only H carries gradient.
    """
    return _ell_spmm_vjp(normalize, ids, mask.astype(jnp.float32), H)
