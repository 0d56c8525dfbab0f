"""`correct` on small cells: true for the program as it is, false for the
control and for each fault a training cell can have, with the timed path
broken underneath a whole run (the look for a chip skipped)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import check
import control
import run
from bench_support import small_cell  # noqa: F401  (a fixture)
from repro.core.engine import DistGNNEngine

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ONE_CHIP = ["gcn-paper.full.c1", "sage-ogb.full.c1"]


def _run(cell):
    return run.main(["--workload", cell.name, "--seed", str(2 ** 31 + 99),
                     "--seconds", "0.01", "--trace", "0"],
                    require_tpu=False, cell=cell)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(small_cell, name):
    res = _run(small_cell(name))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"epoch_s", "step_hbm_gib", "setup_s"}


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_fails_a_limit(small_cell, name):
    cell = small_cell(name)
    gaps = control.readings(cell, jax.devices()[:1], 5,
                            faults=("control",))["control"]
    judged = check.judge(gaps, cell.limits)
    assert not all(c["ok"] for c in judged.values()), judged


@pytest.mark.parametrize("name", ONE_CHIP)
def test_reordered_reference_is_sound(small_cell, name):
    """The reference with its neighbour sums in the other order is a
    second sound run: within every limit."""
    cell = small_cell(name)
    gaps = control.readings(cell, jax.devices()[:1], 5,
                            faults=("reorder",))["reorder"]
    judged = check.judge(gaps, cell.limits)
    assert all(c["ok"] for c in judged.values()), judged
    assert 0 < gaps["logits_gap"], gaps  # the order did change the sums


def test_state_left_unchanged_is_caught(small_cell, monkeypatch):
    make_step = DistGNNEngine.make_step

    def frozen(self):
        step = make_step(self)
        return lambda state: (state,) + tuple(step(state)[1:])

    monkeypatch.setattr(DistGNNEngine, "make_step", frozen)
    res = _run(small_cell("gcn-paper.full.c1"))
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_half_batch_is_caught(small_cell, monkeypatch, name):
    make_step = DistGNNEngine.make_step

    def half(self):
        keep = jnp.arange(self.train_w.shape[0]) % 2 == 0
        self.train_w = self.train_w * keep
        return make_step(self)

    monkeypatch.setattr(DistGNNEngine, "make_step", half)
    assert not _run(small_cell(name))["correct"]


def test_four_chips_sound_and_without_exchange(tmp_path):
    """On four host devices: a one-chip cell spread over four chips (the
    harness's several-chip path: the engine's p2p halo exchange, the
    reference's all-gathered rows) is correct as it is and not correct
    with its halo all_to_all replaced by zeros."""
    paths = [BENCH, os.path.join(os.path.dirname(BENCH), "src"),
             os.path.join(BENCH, "tests")]
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = {paths!r}
        import jax.numpy as jnp
        import run
        from bench_support import SMALL
        from manifest import Cell, load_manifest
        import repro.core.execution.exchange_api as xa

        def zeros(h, send_rows, axis, k):
            B, _, w = send_rows.shape
            return jnp.zeros((B * k * w, h.shape[1]), h.dtype)

        def cell():
            c = Cell(load_manifest(), "gcn-paper.full.c1")
            c.config = dict(c.config, **SMALL)
            c.chips = 4
            return c

        argv = ["--workload", "gcn-paper.full.c1", "--seed", "4242",
                "--seconds", "0.01", "--trace", "0"]
        for tag in ("sound", "cut"):
            if tag == "cut":
                xa.bucketed_all_to_all = zeros
            res = run.main(argv, require_tpu=False, cell=cell())
            print(tag, res["correct"])
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sound True" in out.stdout and "cut False" in out.stdout, \
        out.stdout + out.stderr[-3000:]
