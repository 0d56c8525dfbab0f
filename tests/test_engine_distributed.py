"""DistGNNEngine integration matrix (subprocess, forced host devices): every
execution model x protocol combination must match the single-device oracle to
<=1e-4 max loss error, on 4 and 8 devices, across partitioners; plus
determinism (same seed -> bitwise-identical losses across runs).

This is the engine's contract: the partition plan, the halo exchange, the
ELL SpMM local multiply and the (deterministic-schedule) staleness protocols
may not change the math — only where it runs.
"""
import pytest

from conftest import run_with_devices
from repro.compat import make_mesh

_MATRIX_CODE = """
    import itertools
    import jax, numpy as np
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph({V}, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
    execs = {execs}
    protocols = {protocols}
    partitioners = {partitioners}
    fails = []
    for i, (exe, proto) in enumerate(itertools.product(execs, protocols)):
        cfg = EngineConfig(execution=exe, protocol=proto,
                           partitioner=partitioners[i % len(partitioners)],
                           hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        losses_d, logits_d = eng.train({epochs})
        # the oracle step must see a one-device state: on a TPU mesh a
        # Mosaic kernel in a multi-device jit is refused
        ref_step, ref_devices = eng.make_reference_step(), set()
        eng._ref_step = lambda s: (
            ref_devices.update(s["step"].sharding.device_set), ref_step(s))[1]
        losses_r, logits_r = eng.train({epochs}, reference=True)
        assert len(ref_devices) == 1, ref_devices
        err = max(abs(a - b) for a, b in zip(losses_d, losses_r))
        lerr = float(abs(logits_d - logits_r).max())
        tag = f"{{exe}}/{{proto}}/{{cfg.partitioner}}"
        print(f"{{tag}}: loss_err={{err:.2e}} logits_err={{lerr:.2e}}")
        if not (err <= 1e-4 and np.isfinite(losses_d[-1])):
            fails.append((tag, err))
    assert not fails, fails
    print("ENGINE_MATRIX_OK")
"""


def test_engine_matrix_4dev():
    """Full 3 execution models x 4 protocols on 4 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=96, epochs=4,
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync", "epoch_fixed", "epoch_adaptive", "variation"),
        partitioners=("metis_like", "ldg", "hash"),
    ), n_devices=4)
    assert "ENGINE_MATRIX_OK" in out


def test_engine_matrix_8dev():
    """All execution models x {sync, async-historical} on 8 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=128, epochs=4,
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync", "epoch_adaptive"),
        partitioners=("metis_like", "hash"),
    ), n_devices=8)
    assert "ENGINE_MATRIX_OK" in out


def test_engine_determinism_4dev():
    """Same seed -> bitwise-identical losses across two runs (the protocol's
    deterministic refresh schedule is part of the SPMD contract)."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        cfg = EngineConfig(execution="p2p", protocol="epoch_adaptive",
                           hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        l1, _ = eng.train(5)
        l2, _ = eng.train(5)
        assert l1 == l2, (l1, l2)
        eng2 = DistGNNEngine(g, cfg=cfg)
        l3, _ = eng2.train(5)
        assert l1 == l3, (l1, l3)
        print("ENGINE_DET_OK", l1[-1])
    """, n_devices=4)
    assert "ENGINE_DET_OK" in out


def test_engine_rejects_bad_config():
    from repro.core.engine import EngineConfig, DistGNNEngine
    from repro.core.graph import er_graph

    g = er_graph(32, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(execution="nope"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(protocol="nope"))


def test_engine_single_device_paths_agree():
    """On one device the distributed step IS the oracle (k=1 partition plan,
    halo cap degenerate): both paths must agree and learn."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.01, seed=1)
    mesh = make_mesh((1,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
        execution="p2p", protocol="sync", hidden=16, lr=0.3))
    ld, _ = eng.train(10)
    lr_, _ = eng.train(10, reference=True)
    assert max(abs(a - b) for a, b in zip(ld, lr_)) < 1e-4
    assert ld[-1] < ld[0]
