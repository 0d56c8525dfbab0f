"""Mesh construction. Functions, not module-level constants, so importing this
module never touches jax device state."""
from __future__ import annotations

from repro.compat import make_mesh

# The chip the production meshes are sized for, as ``device_kind`` names it
# (the dry-runs compile on CPU placeholders and read peaks for this kind).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
