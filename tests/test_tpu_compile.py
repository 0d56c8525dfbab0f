"""Compile the aggregation kernels for a described TPU v5e chip at gcn-paper
width (2^20 vertices, 256 features, K = 38, the widest ELL row of
``er_graph(2**20, avg_degree=16)``).  Nothing runs: the TPU compiler refuses
here what it would refuse on the chip (unaligned DMA slices, VMEM or SMEM
overflow, programs that do not fit HBM).  The Pallas kernels are GAT's
(``sddmm``, and ``gather_dot`` in ``ell_attend``'s backward); the gather-sum
forward is XLA's gather, under the scope ``gather_sum``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

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


def _compile(fn, *args, pallas: bool):
    """Compile for the described chip; ``pallas``: whether the program
    holds a Pallas kernel (``tpu_custom_call``) or none."""
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert ("tpu_custom_call" in compiled.as_text()) == pallas
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes < HBM_BYTES, mem
    return compiled


def _row_gather_paths(text):
    """The op_name paths of a compiled program's forward gathers of table
    rows (a backward's scatter-add reads rows too, under ``transpose(``)."""
    paths = re.findall(r"= \S+ gather\(.*\bslice_sizes=\{1,\d+\}.*?"
                       r"\bop_name=\"([^\"]*)\"", text)
    return [p for p in paths if "transpose(" not in p]


def _check_gather_sum(compiled, width, forward: bool):
    """The forward's row gathers sit under ``aggregate/gather_sum``; its
    scratch stays under two ``[V, width]`` f32 tables, so no slot's gathered
    rows are kept beside the accumulator for the whole loop."""
    paths = _row_gather_paths(compiled.as_text())
    assert paths and all("/aggregate/gather_sum/" in p for p in paths), paths
    if forward:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 2 * V * width * 4, temp


def _aggregate(fn):
    """``fn`` under the engine's scopes, ``layer0/aggregate``."""
    def scoped(*args):
        with jax.named_scope("layer0"), jax.named_scope("aggregate"):
            return fn(*args)
    return scoped


def _ell(ids, mask, h):
    return ell_spmm(ids, mask, h, normalize=False)


def _ell_loss(ids, mask, h):
    return jnp.sum(_aggregate(_ell)(ids, mask, h) ** 2)


def _attend_loss(ids, w, h):
    return jnp.sum(_aggregate(ell_attend)(ids, w, h) ** 2)


def _sddmm_loss(ids, mask, hw, a_src, a_dst):
    e = sddmm_ell(ids, mask, hw, a_src, a_dst)
    return jnp.sum(jnp.where(mask > 0, jnp.tanh(e), 0.0))


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ell_spmm_compiles_at_gcn_paper_width(one_chip, direction):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, D), jnp.float32, one_chip))
    forward = direction == "forward"
    fn = _aggregate(_ell) if forward else jax.grad(_ell_loss, argnums=2)
    _check_gather_sum(_compile(fn, *args, pallas=False), D, forward)


# 65 = gat's last layer: 64 classes plus the fused attention-score column,
# a width that is not a whole number of 128-lane tiles
@pytest.mark.parametrize("width", [D, 65])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ell_attend_compiles_at_gcn_paper_width(one_chip, direction, width):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, width), jnp.float32, one_chip))
    if direction == "forward":
        _check_gather_sum(_compile(_aggregate(ell_attend), *args,
                                   pallas=False), width, forward=True)
    else:  # the weights' gradient is the gather-dot kernel
        _compile(jax.grad(_attend_loss, argnums=(1, 2)), *args, pallas=True)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_sddmm_compiles_at_gcn_paper_width(one_chip, direction):
    args = (_spec((V, K), jnp.int32, one_chip),
            _spec((V, K), jnp.float32, one_chip),
            _spec((N, D), jnp.float32, one_chip),
            _spec((D,), jnp.float32, one_chip),
            _spec((D,), jnp.float32, one_chip))
    fn = sddmm_ell if direction == "forward" \
        else jax.grad(_sddmm_loss, argnums=(2, 3, 4))
    _compile(fn, *args, pallas=True)


def _scoped_layer_loss(ids, mask, h, w, a_src, a_dst):
    """One GCN and one GAT aggregation under the engine's scopes."""
    with jax.named_scope("layer0"):
        with jax.named_scope("aggregate"):
            nbr = ell_spmm(ids, mask, h, normalize=False)
            e = sddmm_ell(ids, mask, h, a_src, a_dst)
            att = ell_attend(ids, jnp.where(mask > 0, jnp.exp(e), 0.0), h)
        with jax.named_scope("combine"):
            z = (nbr + att) @ w
    with jax.named_scope("loss"):
        return jnp.sum(z ** 2)


def test_kernel_names_and_scopes_reach_the_chip_program(one_chip):
    """The chip's program names each Pallas call after its kernel and keeps
    the scopes in its op_name metadata: the forward's ``sddmm`` kernel and
    its XLA gathers (``gather_sum``) under ``aggregate``, the backward
    (``gather_dot``, the attention weights' gradient, and the scatter-add
    loops) under ``transpose(...)/aggregate``, the products under
    ``combine``."""
    v, k, d = 1024, 12, 128
    args = (_spec((v, k), jnp.int32, one_chip),
            _spec((v, k), jnp.float32, one_chip),
            _spec((v + 1, d), jnp.float32, one_chip),
            _spec((d, d), jnp.float32, one_chip),
            _spec((d,), jnp.float32, one_chip),
            _spec((d,), jnp.float32, one_chip))
    text = _compile(jax.grad(_scoped_layer_loss, argnums=(2, 3, 4, 5)),
                    *args, pallas=True).as_text()
    kernels = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*\bop_name=\"([^\"]*)\"",
                     line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            kernels.setdefault(m.group(1).split(".")[0], set()).add(
                m.group(2).split("/", 1)[1])
        elif m and re.search(r"\b(convolution|dot)\(", line):
            assert "/combine/" in m.group(2), line
    assert set(kernels) == {"gather_dot", "sddmm"}, kernels
    gathers = {p.split("/", 1)[1].split("/while/", 1)[0]
               for p in _row_gather_paths(text)}
    assert gathers == {"jvp(layer0)/aggregate/gather_sum"}, gathers
    assert kernels["sddmm"] == {"jvp(layer0)/aggregate/sddmm/pallas_call"}
    assert kernels["gather_dot"] == {"transpose(jvp(layer0))/aggregate/"
                                     "gather_dot/pallas_call"}
    assert re.search(r"%while[.\d]* = .*op_name=\"[^\"]*/transpose\("
                     r"jvp\(layer0\)\)/aggregate/", text)
