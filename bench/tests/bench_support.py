"""Shared set-up for the benchmark's own tests (CPU, small sizes).

Not a ``conftest.py``: the repo's ``tests/conftest.py`` is imported by its
tests as the module ``conftest``, and a second one would shadow it."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the small cells the tests run: every real cell's widths, depth, traffic
# and limits, at a vertex count a test run can hold
SMALL = dict(num_vertices=2048)


@pytest.fixture
def small_cell(monkeypatch, tmp_path):
    """small_cell(name, chips=None): the manifest's cell ``name`` cut to
    SMALL, with its own limits; the compile cache goes to a temp dir."""
    from manifest import Cell, load_manifest

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))

    def make(name, chips=None):
        cell = Cell(load_manifest(), name)
        cell.config = dict(cell.config, **SMALL)
        if chips is not None:
            cell.chips = chips
        return cell

    return make
