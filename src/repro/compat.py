"""The one place that owns jax API drift for this repo.

Supported version: the installed jax 0.9 (jaxlib 0.9, libtpu 0.0.34).  Code
for older releases is not kept; when the installed jax moves, this module is
where the repo follows it.

* ``shard_map``: ``jax.shard_map`` (its replication check is spelled
  ``check_vma``).
* ``make_mesh``: every mesh in the repo is built here, with ``Auto`` axes.
  ``jax.make_mesh`` defaults to ``Explicit`` axes since 0.7, under which the
  engine's sharded concatenates, ``vmap`` and ``dynamic_update_slice`` are
  refused with ``ShardingTypeError``; the repo's programs place data with
  ``shard_map`` and ``NamedSharding``, which is what ``Auto`` axes mean.
* ``interpret_default``: Pallas kernels compile on a TPU backend and run in
  interpret mode everywhere else.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


shard_map = jax.shard_map


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see the module docstring).
    ``devices`` defaults to ``jax.devices()``; a described topology's devices
    may be passed to build a mesh for a chip that is not attached."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode by default: True on
    anything that is not a TPU backend (CPU hosts and forced-host-device test
    meshes).  The kernels are written for Mosaic (TPU); their GPU lowering is
    untested, so interpret mode is the default there too."""
    return jax.default_backend() != "tpu"
