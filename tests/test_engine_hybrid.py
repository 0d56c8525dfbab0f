"""DistGNNEngine hybrid-cut tier (subprocess, forced host devices): the
PowerLyra-style degree-threshold family (partition/hybrid_cut.py behind the
layout/exchange interface) must match the single-device oracle to <=1e-4
across the full {broadcast, ring, p2p} x {gcn, sage, gat, gin} matrix on 4
AND 8 devices — low-degree vertices flow edge-cut-local through the halo
exchange while hub replicas combine through the replica-sync GAS, and the
composition may not change the math.

Also locked down here: the degenerate thresholds inside the ENGINE
(threshold=inf runs halo-only with byte accounting equal to the edge-cut
p2p halo model; threshold=0 runs sync-only), bitwise determinism and the
one-compile guard, CommStats exactly == the standalone
`hybrid_bytes_per_step` cost model, the family anchor against the edge-cut
oracle, config validation, and the single-device degeneration.
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
    fails = []
    for i, (model, exe) in enumerate(
            itertools.product({models}, {execs})):
        cfg = EngineConfig(partition_family="hybrid", model=model,
                           execution=exe, hub_threshold={threshold},
                           hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        losses_d, logits_d = eng.train({epochs})
        losses_r, logits_r = eng.train({epochs}, reference=True)
        err = max(abs(a - b) for a, b in zip(losses_d, losses_r))
        lerr = float(abs(logits_d - logits_r).max())
        tag = f"{{model}}/{{exe}}"
        print(f"{{tag}}: loss_err={{err:.2e}} logits_err={{lerr:.2e}}")
        if not (err <= 1e-4 and lerr <= 1e-4
                and np.isfinite(losses_d[-1])):
            fails.append((tag, err, lerr))
    assert not fails, fails
    print("HY_MATRIX_OK")
"""


@pytest.mark.parametrize("model", ["gcn", "sage", "gat", "gin"])
def test_hybrid_matrix_4dev(model):
    """One model x ALL execution models per subprocess at the default (95th
    percentile) hub threshold — together the four parametrizations cover the
    full 4 x 3 matrix on 4 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=80, epochs=3, threshold="None",
        models=(model,), execs=("broadcast", "ring", "p2p"),
    ), n_devices=4, timeout=600)
    assert "HY_MATRIX_OK" in out


@pytest.mark.parametrize("models", [("gcn", "gat"), ("sage", "gin")])
def test_hybrid_matrix_8dev(models):
    """The model matrix on 8 devices (two models x all executions per
    subprocess), with a hand-picked threshold so both vertex classes are
    populated."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=128, epochs=3, threshold=6.0,
        models=models, execs=("broadcast", "ring", "p2p"),
    ), n_devices=8, timeout=600)
    assert "HY_MATRIX_OK" in out


def test_hybrid_degenerate_thresholds_4dev():
    """threshold=inf (halo-only: sync inactive, bytes == the edge-cut p2p
    halo device model) and threshold=0 (sync-only: halo inactive) both match
    the oracle inside the engine."""
    out = run_with_devices("""
        import numpy as np
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(80, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
        for thr in (np.inf, 0.0):
            for exe in ("broadcast", "ring", "p2p"):
                cfg = EngineConfig(partition_family="hybrid",
                                   hub_threshold=thr, execution=exe,
                                   hidden=16, lr=0.3)
                eng = DistGNNEngine(g, cfg=cfg)
                ld, _ = eng.train(3)
                lr_, _ = eng.train(3, reference=True)
                err = max(abs(a - b) for a, b in zip(ld, lr_))
                assert err <= 1e-4, (thr, exe, err)
                lay = eng.playout
                if np.isinf(thr):
                    assert not lay.sync_active and lay.halo_active
                else:
                    assert lay.sync_active and not lay.halo_active
        print("HY_DEGEN_OK")
    """, n_devices=4, timeout=600)
    assert "HY_DEGEN_OK" in out


def test_hybrid_determinism_and_recompile_4dev():
    """Same seed -> bitwise-identical losses across runs AND engines, and
    the jitted step compiles EXACTLY once per config."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        cfg = EngineConfig(partition_family="hybrid", execution="p2p",
                           hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        l1, _ = eng.train(5)
        n = eng._jit_step._cache_size()
        assert n == 1, f"expected 1 compile, got {n}"
        l2, _ = eng.train(5)
        assert l1 == l2, (l1, l2)
        assert eng._jit_step._cache_size() == 1
        eng2 = DistGNNEngine(g, cfg=cfg)
        l3, _ = eng2.train(5)
        assert l1 == l3, (l1, l3)
        print("HY_DET_OK", l1[-1])
    """, n_devices=4)
    assert "HY_DET_OK" in out


def test_hybrid_comm_stats_cross_check_4dev():
    """Engine-reported halo_bytes + replica_sync_bytes exactly == the
    standalone `hybrid_bytes_per_step` cost model over the engine's layout,
    per execution model and for gcn AND gat widths; both fields count as
    wire bytes in total()."""
    out = run_with_devices("""
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph
        from repro.core.partition.cost_models import hybrid_bytes_per_step

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        for model in ("gcn", "gat"):
            for exe in ("broadcast", "ring", "p2p"):
                cfg = EngineConfig(partition_family="hybrid", model=model,
                                   execution=exe, hidden=16, lr=0.3)
                eng = DistGNNEngine(g, cfg=cfg)
                eng.train(4)
                lay = eng.playout
                expected = 4 * hybrid_bytes_per_step(
                    lay.halo_rows_exec if lay.halo_active else 0,
                    lay._vc_rows_per_layer if lay.sync_active else 0,
                    eng.dims, model=model)
                got = (eng.comm_stats.halo_bytes
                       + eng.comm_stats.replica_sync_bytes)
                assert got == expected and got > 0, (model, exe, got,
                                                     expected)
                assert eng.comm_stats.total() == got
        print("HY_BYTES_OK")
    """, n_devices=4, timeout=600)
    assert "HY_BYTES_OK" in out


def test_hybrid_anchors_to_edge_cut_oracle_4dev():
    """Family anchor: under sync the hybrid family computes the same global
    GCN as the edge-cut oracle from the same param init — the hybrid
    dataflow is pinned to the real graph math, not just to itself."""
    out = run_with_devices("""
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        cfgh = EngineConfig(partition_family="hybrid", execution="p2p",
                            hidden=16, lr=0.3)
        cfge = EngineConfig(execution="p2p", hidden=16, lr=0.3)
        engh = DistGNNEngine(g, cfg=cfgh)
        lh_dist, _ = engh.train(4)
        le_ref, _ = DistGNNEngine(g, cfg=cfge).train(4, reference=True)
        gap = max(abs(a - b) for a, b in zip(lh_dist, le_ref))
        assert gap <= 1e-4, gap
        print("HY_ANCHOR_OK", gap)
    """, n_devices=4)
    assert "HY_ANCHOR_OK" in out


def test_hybrid_rejects_bad_config():
    import numpy as np

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(32, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="hybrid",
                                          hub_threshold=-1.0))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="hybrid",
                                          hub_threshold=np.nan))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="hybrid",
                                          batching="node_wise"))


def test_hybrid_single_device_paths_agree():
    """On one device the distributed hybrid step IS the oracle (halo and
    sync tables degenerate) and still learns."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.01, seed=1)
    mesh = make_mesh((1,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
        partition_family="hybrid", execution="p2p", hidden=16, lr=0.3))
    ld, _ = eng.train(8)
    lr_, _ = eng.train(8, reference=True)
    assert max(abs(a - b) for a, b in zip(ld, lr_)) < 1e-4
    assert ld[-1] < ld[0]
