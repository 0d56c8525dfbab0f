"""Pallas TPU kernel: chunked RWKV6 WKV scan.

TPU adaptation (DESIGN.md §2/§5): the per-step recurrence becomes per-chunk
masked matmuls; the [K,K] state is carried ACROSS grid steps in a VMEM
scratch buffer — the TPU grid executes sequentially over the chunk axis, so
the scratch acts as the recurrent carry (the standard Pallas-TPU scan idiom).

Grid: (B*H, S // C). Inputs per step: r,k,v,g [1, C, K]; u [1, K].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU-only import guard; interpret mode works anywhere
    from jax.experimental.pallas import tpu as pltpu

    _SCRATCH = lambda shape: pltpu.VMEM(shape, jnp.float32)
except Exception:  # pragma: no cover
    _SCRATCH = None


def _wkv_kernel(r_ref, k_ref, v_ref, g_ref, u_ref, o_ref, state_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # [C, K]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)  # log decay <= 0 (pre-clamped)
    u = u_ref[0].astype(jnp.float32)  # [K]
    C = r.shape[0]
    state = state_ref[...]  # [K, K]

    L = jnp.cumsum(g, axis=0)  # inclusive
    L_prev = L - g  # exclusive
    L_end = L[-1]
    q_eff = r * jnp.exp(L_prev)
    k_eff = k * jnp.exp(-L)
    A = jax.lax.dot_general(q_eff, k_eff, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [C, C]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.where(s_i < t_i, A, 0.0)  # strictly past
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus (current token through u)
    coef = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True)
    y = y + coef * v
    # inter-chunk: carried state
    y = y + jax.lax.dot_general(q_eff, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # state update
    k_dec = k * jnp.exp(L_end[None, :] - L)
    state_new = jnp.exp(L_end)[:, None] * state + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = state_new
    o_ref[0] = y.astype(o_ref.dtype)


def wkv_chunk_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, u: jnp.ndarray, *, chunk: int = 64,
                     interpret: bool = False) -> jnp.ndarray:
    """r,k,v,g [B,H,S,K]; u [H,K] -> y [B,H,S,K]."""
    B, H, S, K = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    g = jnp.clip(g, -1.2, 0.0)  # numerics contract shared with ssm.py
    rf = r.reshape(B * H, S, K)
    kf = k.reshape(B * H, S, K)
    vf = v.reshape(B * H, S, K)
    gf = g.reshape(B * H, S, K)
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    grid = (B * H, S // chunk)
    scratch = [_SCRATCH((K, K))] if _SCRATCH is not None else [
        pl.BlockSpec(memory_space=None)]  # pragma: no cover
    out = pl.pallas_call(
        _wkv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, K), lambda b, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, K), r.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="wkv_chunk",
    )(rf, kf, vf, gf, uf)
    return out.reshape(B, H, S, K)
