"""Jit'd dispatch layer: Pallas kernels on TPU, interpret-mode (or the jnp
oracle) on CPU. This is the API the rest of the framework calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ell_spmm import ell_spmm as ell_spmm_diff
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.wkv_chunk import wkv_chunk_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("normalize", "force_pallas"))
def ell_spmm(ids, mask, H, *, normalize: bool = True, force_pallas: bool = False):
    # the differentiable wrapper (custom scatter-add VJP), so grads work
    # through the package-level API on every backend
    if _on_tpu() or force_pallas:
        return ell_spmm_diff(ids, mask, H, normalize=normalize)
    return ref.ell_spmm_ref(ids, mask, H, normalize=normalize)


@functools.partial(jax.jit, static_argnames=("slope", "force_pallas"))
def sddmm(ids, mask, Hw, a_src, a_dst, *, slope: float = 0.2,
          force_pallas: bool = False):
    if _on_tpu() or force_pallas:
        return sddmm_pallas(ids, mask, Hw, a_src, a_dst, slope=slope,
                            interpret=not _on_tpu())
    return ref.sddmm_ref(ids, mask, Hw, a_src, a_dst, slope=slope)


@functools.partial(jax.jit, static_argnames=("causal", "force_pallas"))
def flash_attention(q, k, v, *, causal: bool = True, force_pallas: bool = False):
    if _on_tpu() or force_pallas:
        return flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=not _on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("chunk", "force_pallas"))
def wkv(r, k, v, g, u, *, chunk: int = 64, force_pallas: bool = False):
    if _on_tpu() or force_pallas:
        return wkv_chunk_pallas(r, k, v, g, u, chunk=chunk,
                                interpret=not _on_tpu())
    return ref.wkv_chunk_ref(r, k, v, jnp.clip(g, -1.2, 0.0), u)
