"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ell_spmm import _SUM_ROWS, ell_attend, ell_spmm
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sddmm import sddmm_ell, sddmm_pallas
from repro.kernels.wkv_chunk import wkv_chunk_pallas

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("V,K,D", [(128, 8, 64), (256, 16, 128), (128, 32, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("normalize", [True, False])
def test_ell_spmm(V, K, D, dtype, normalize):
    ids = jnp.asarray(RNG.integers(0, V, (V, K)), jnp.int32)
    mask = jnp.asarray(RNG.random((V, K)) < 0.6, jnp.float32)
    H = jnp.asarray(RNG.standard_normal((V, D)), dtype)
    got = ell_spmm(ids, mask, H, normalize=normalize)
    want = ref.ell_spmm_ref(ids, mask, H, normalize=normalize)
    tol = 1e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("normalize", [True, False])
def test_ell_spmm_pad_slots_read_the_zero_row(normalize):
    """The engine's ELL layout at sage's 100-wide input (not a whole number
    of 128-lane tiles): rows of different degree, pad slots of weight 0
    pointing at the table's last row, which is zero."""
    V, K, D = 200, 38, 100
    deg = RNG.integers(0, K + 1, V)
    mask = jnp.asarray(np.arange(K)[None, :] < deg[:, None], jnp.float32)
    ids = jnp.asarray(np.where(mask > 0, RNG.integers(0, V, (V, K)), V),
                      jnp.int32)
    H = jnp.asarray(RNG.standard_normal((V + 1, D)), jnp.float32).at[V].set(0)
    got = ell_spmm(ids, mask, H, normalize=normalize)
    want = ref.ell_spmm_ref(ids, mask, H, normalize=normalize)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_ell_spmm_row_chunks():
    """More rows than one chunk of the gather-sum, with a last chunk that
    overlaps the one before it: every row is summed once, as the oracle
    sums it."""
    V, K, D = 2 * _SUM_ROWS + 37, 6, 16
    ids = jnp.asarray(RNG.integers(0, V, (V, K)), jnp.int32)
    mask = jnp.asarray(RNG.random((V, K)) < 0.6, jnp.float32)
    H = jnp.asarray(RNG.standard_normal((V, D)), jnp.float32)
    got = ell_spmm(ids, mask, H, normalize=False)
    want = ref.ell_spmm_ref(ids, mask, H, normalize=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("V,K,D", [(128, 8, 32), (256, 12, 64)])
def test_sddmm(V, K, D):
    ids = jnp.asarray(RNG.integers(0, V, (V, K)), jnp.int32)
    mask = jnp.asarray(RNG.random((V, K)) < 0.5, jnp.float32)
    Hw = jnp.asarray(RNG.standard_normal((V, D)), jnp.float32)
    a_src = jnp.asarray(RNG.standard_normal(D), jnp.float32)
    a_dst = jnp.asarray(RNG.standard_normal(D), jnp.float32)
    got = sddmm_pallas(ids, mask, Hw, a_src, a_dst, interpret=True)
    want = ref.sddmm_ref(ids, mask, Hw, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("V,K,N,D", [(13, 5, 17, 8), (130, 7, 150, 16)])
def test_sddmm_ell_differentiable(V, K, N, D):
    """The distributed-GAT logit wrapper: awkward (padded) row counts, halo
    rows appended after the V dst rows, and an analytic VJP that matches
    jnp autodiff of the oracle for Hw / a_src / a_dst."""
    ids = jnp.asarray(RNG.integers(0, N, (V, K)), jnp.int32)
    mask = jnp.asarray(RNG.random((V, K)) < 0.6, jnp.float32)
    Hw = jnp.asarray(RNG.standard_normal((N, D)), jnp.float32)
    a_src = jnp.asarray(RNG.standard_normal(D), jnp.float32)
    a_dst = jnp.asarray(RNG.standard_normal(D), jnp.float32)
    got = sddmm_ell(ids, mask, Hw, a_src, a_dst, interpret=True)
    want = ref.sddmm_ref(ids, mask, Hw, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)

    def masked_loss(fn):
        def loss(hw, a_s, a_d):
            e = fn(ids, mask, hw, a_s, a_d)
            return (jnp.where(mask > 0, jnp.tanh(e), 0.0)).sum()
        return loss

    g1 = jax.grad(masked_loss(
        lambda *a: sddmm_ell(*a, interpret=True)), argnums=(0, 1, 2))(
        Hw, a_src, a_dst)
    g2 = jax.grad(masked_loss(ref.sddmm_ref), argnums=(0, 1, 2))(
        Hw, a_src, a_dst)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("V,K,N,D", [(13, 5, 17, 8), (200, 9, 260, 32)])
def test_ell_attend_differentiable(V, K, N, D):
    """The attention-weighted ELL sum: gradients flow to BOTH the weights
    (GAT's attention coefficients) and the gathered table — `ell_spmm`
    deliberately zeroes the mask cotangent, so the GAT path needs this."""
    ids = jnp.asarray(RNG.integers(0, N, (V, K)), jnp.int32)
    w = jnp.asarray(RNG.random((V, K)), jnp.float32)
    H = jnp.asarray(RNG.standard_normal((N, D)), jnp.float32)

    def jnp_ref(w_, H_):
        return (w_[..., None] * jnp.take(H_, ids, axis=0)).sum(1)

    np.testing.assert_allclose(
        np.asarray(ell_attend(ids, w, H, interpret=True)),
        np.asarray(jnp_ref(w, H)), atol=1e-5, rtol=1e-5)
    g1 = jax.grad(lambda w_, H_: (ell_attend(ids, w_, H_, interpret=True)
                                  ** 2).sum(), argnums=(0, 1))(w, H)
    g2 = jax.grad(lambda w_, H_: (jnp_ref(w_, H_) ** 2).sum(),
                  argnums=(0, 1))(w, H)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(g1[0]).max()) > 0  # weights DO get a gradient


@pytest.mark.parametrize("B,H,S,D", [(1, 2, 128, 64), (2, 4, 256, 64), (1, 1, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, S, D, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,S,K,chunk", [(1, 2, 64, 16, 16), (2, 3, 128, 32, 32),
                                           (1, 1, 128, 64, 64)])
def test_wkv_chunk(B, H, S, K, chunk):
    r = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    g = jnp.asarray(-np.exp(RNG.standard_normal((B, H, S, K)) * 0.5 - 1.0), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, K)) * 0.1, jnp.float32)
    got = wkv_chunk_pallas(r, k, v, g, u, chunk=chunk, interpret=True)
    want = ref.wkv_chunk_ref(r, k, v, jnp.clip(g, -1.2, 0.0), u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-3)


def test_wkv_chunk_invariance():
    """Same result for different chunk sizes (the chunking is exact)."""
    B, H, S, K = 1, 2, 96, 16
    r = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, K)) * 0.5, jnp.float32)
    g = jnp.asarray(np.full((B, H, S, K), -0.3), jnp.float32)
    u = jnp.zeros((H, K), jnp.float32)
    a = wkv_chunk_pallas(r, k, v, g, u, chunk=16, interpret=True)
    b = wkv_chunk_pallas(r, k, v, g, u, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)
