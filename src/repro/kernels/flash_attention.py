"""Pallas TPU kernel: flash attention (prefill/training attention hot spot).

Grid (batch*heads, num_q_blocks); the q block and streaming softmax stats live
in VMEM; k/v are consumed in kv-sized blocks via an inner fori_loop over VMEM
slices of the per-(bh) k/v panels. fp32 accumulation, causal masking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, causal: bool,
                  scale: float):
    q = q_ref[0]  # [Qb, D]
    Qb, D = q.shape
    T = k_ref.shape[1]
    nkv = T // kv_block
    qi = pl.program_id(1)
    q_idx = qi * Qb + jax.lax.broadcasted_iota(jnp.int32, (Qb, 1), 0)

    def body(kv_i, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kv_i * kv_block, kv_block), :]
        v_blk = v_ref[0, pl.ds(kv_i * kv_block, kv_block), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            k_idx = kv_i * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv_block), 1)
            s = jnp.where(k_idx <= q_idx, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_blk.dtype), v_blk,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((Qb, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Qb, 1), jnp.float32)
    a0 = jnp.zeros((Qb, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           q_block: int = 128, kv_block: int = 128,
                           causal: bool = True, interpret: bool = False
                           ) -> jnp.ndarray:
    """q,k,v [B,H,S,D] -> [B,H,S,D]."""
    B, H, S, D = q.shape
    T = k.shape[2]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    assert S % q_block == 0 and T % kv_block == 0
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    grid = (B * H, S // q_block)
    kernel = functools.partial(_flash_kernel, kv_block=kv_block, causal=causal,
                               scale=D ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
