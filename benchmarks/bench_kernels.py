"""Per-kernel µs/call. On CPU these run the interpret-mode kernel (structural
check) AND the jnp oracle; the oracle timing is the meaningful CPU number,
interpret timing only proves the kernel executes."""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.ell_spmm import ell_spmm
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.wkv_chunk import wkv_chunk_pallas


def _time(fn, *args, repeats=3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeats * 1e6


def bench_kernels() -> Tuple[List[Dict], str]:
    rng = np.random.default_rng(0)
    rows = []
    # ell_spmm
    V, K, D = 1024, 16, 128
    ids = jnp.asarray(rng.integers(0, V, (V, K)), jnp.int32)
    mask = jnp.asarray(rng.random((V, K)) < 0.7, jnp.float32)
    H = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    oracle = jax.jit(lambda i, m, h: ref.ell_spmm_ref(i, m, h))
    rows.append(dict(kernel="ell_spmm", shape=f"V{V}xK{K}xD{D}",
                     oracle_us=round(_time(oracle, ids, mask, H), 1),
                     interpret_us=round(_time(
                         ell_spmm, ids, mask, H, repeats=1), 1)))
    # sddmm
    a_src = jnp.asarray(rng.standard_normal(D), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal(D), jnp.float32)
    oracle = jax.jit(lambda *a: ref.sddmm_ref(*a))
    rows.append(dict(kernel="sddmm", shape=f"V{V}xK{K}xD{D}",
                     oracle_us=round(_time(oracle, ids, mask, H, a_src, a_dst), 1),
                     interpret_us=round(_time(
                         lambda *a: sddmm_pallas(*a, interpret=True),
                         ids, mask, H, a_src, a_dst, repeats=1), 1)))
    # flash attention
    B, Hh, S, Dh = 1, 4, 512, 64
    q = jnp.asarray(rng.standard_normal((B, Hh, S, Dh)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, Hh, S, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, Hh, S, Dh)), jnp.bfloat16)
    oracle = jax.jit(lambda *a: ref.flash_attention_ref(*a))
    rows.append(dict(kernel="flash_attention", shape=f"B{B}H{Hh}S{S}D{Dh}",
                     oracle_us=round(_time(oracle, q, k, v), 1),
                     interpret_us=round(_time(
                         lambda *a: flash_attention_pallas(*a, interpret=True),
                         q, k, v, repeats=1), 1)))
    # wkv
    B2, H2, S2, K2 = 1, 4, 256, 64
    r = jnp.asarray(rng.standard_normal((B2, H2, S2, K2)) * 0.5, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((B2, H2, S2, K2)) * 0.5, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((B2, H2, S2, K2)) * 0.5, jnp.float32)
    g = jnp.asarray(-np.abs(rng.standard_normal((B2, H2, S2, K2))) * 0.3, jnp.float32)
    u = jnp.asarray(rng.standard_normal((H2, K2)) * 0.1, jnp.float32)
    oracle = jax.jit(lambda *a: ref.wkv_chunk_ref(*a))
    rows.append(dict(kernel="wkv_chunk", shape=f"B{B2}H{H2}S{S2}K{K2}",
                     oracle_us=round(_time(oracle, r, kk, vv, g, u), 1),
                     interpret_us=round(_time(
                         lambda *a: wkv_chunk_pallas(*a, interpret=True),
                         r, kk, vv, g, u, repeats=1), 1)))
    return rows, f"{len(rows)} kernels validated"
