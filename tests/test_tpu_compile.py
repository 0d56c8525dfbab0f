"""Compile the aggregation kernels for a described TPU v5e chip at gcn-paper
width (2^20 vertices, 256 features, K = 38, the widest ELL row of
``er_graph(2**20, avg_degree=16)``).  Nothing runs: the TPU compiler refuses
here what it would refuse on the chip (unaligned DMA slices, VMEM or SMEM
overflow, programs that do not fit HBM).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ell_spmm import ell_attend, ell_spmm
from repro.kernels.sddmm import sddmm_ell

V, K, D = 2**20, 38, 256
N = V + 1  # the engine's tables carry one zero pad row
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes < HBM_BYTES, mem
    return compiled


def _ell_loss(ids, mask, h):
    return jnp.sum(ell_spmm(ids, mask, h, normalize=False) ** 2)


def _attend_loss(ids, w, h):
    return jnp.sum(ell_attend(ids, w, h) ** 2)


def _sddmm_loss(ids, mask, hw, a_src, a_dst):
    e = sddmm_ell(ids, mask, hw, a_src, a_dst)
    return jnp.sum(jnp.where(mask > 0, jnp.tanh(e), 0.0))


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ell_spmm_compiles_at_gcn_paper_width(one_chip, direction):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, D), jnp.float32, one_chip))
    fn = (lambda i, m, h: ell_spmm(i, m, h, normalize=False)) \
        if direction == "forward" else jax.grad(_ell_loss, argnums=2)
    _compile(fn, *args)


# 65 = gat's last layer: 64 classes plus the fused attention-score column,
# a width that is not a whole number of 128-lane tiles
@pytest.mark.parametrize("width", [D, 65])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ell_attend_compiles_at_gcn_paper_width(one_chip, direction, width):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, width), jnp.float32, one_chip))
    fn = ell_attend if direction == "forward" \
        else jax.grad(_attend_loss, argnums=(1, 2))
    _compile(fn, *args)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_sddmm_compiles_at_gcn_paper_width(one_chip, direction):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, D), jnp.float32, one_chip),
            _spec((D,), jnp.float32, one_chip),
            _spec((D,), jnp.float32, one_chip))
    fn = sddmm_ell if direction == "forward" \
        else jax.grad(_sddmm_loss, argnums=(2, 3, 4))
    _compile(fn, *args)
