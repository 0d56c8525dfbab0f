"""DistGNNEngine vertex-cut tier (subprocess, forced host devices): the full
{vertex-cut partitioner} x {broadcast, ring, p2p} x {sync, epoch_fixed,
epoch_adaptive, variation} matrix must match the single-device oracle to
<=1e-4 — the replica layout, the owned-edge partial aggregation, the
replica-sync combine (all_gather / ring ppermute / master-based two-phase
all_to_all GAS) and the master-masked loss may not change the math.

Also locked down here: bitwise determinism across runs and engines, the
one-compile-per-config contract, the agreement between engine-reported
CommStats.replica_sync_bytes and the standalone replication-aware cost model,
and the family anchor: under protocol='sync' the vertex-cut oracle computes
the SAME global GCN as the edge-cut oracle (same params init), so the two
families' reference losses must agree — the whole vertex-cut dataflow is
pinned to the real graph math, not just to itself.
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
    for i, (vcut, exe, proto) in enumerate(
            itertools.product({vcuts}, {execs}, {protocols})):
        cfg = EngineConfig(partition_family="vertex_cut", vertex_cut=vcut,
                           execution=exe, protocol=proto, hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        losses_d, logits_d = eng.train({epochs})
        losses_r, logits_r = eng.train({epochs}, reference=True)
        err = max(abs(a - b) for a, b in zip(losses_d, losses_r))
        lerr = float(abs(logits_d - logits_r).max())
        tag = f"{{vcut}}/{{exe}}/{{proto}}"
        print(f"{{tag}}: loss_err={{err:.2e}} logits_err={{lerr:.2e}}")
        if not (err <= 1e-4 and np.isfinite(losses_d[-1])):
            fails.append((tag, err))
    assert not fails, fails
    print("VC_MATRIX_OK")
"""


@pytest.mark.parametrize("vcut", ["random", "cartesian2d", "libra"])
def test_vertex_cut_matrix_4dev(vcut):
    """One vertex-cut partitioner x ALL execution models x ALL protocols per
    subprocess — together the three parametrizations cover the full
    3 x 3 x 4 matrix on 4 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=80, epochs=3,
        vcuts=(vcut,),
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync", "epoch_fixed", "epoch_adaptive", "variation"),
    ), n_devices=4, timeout=600)
    assert "VC_MATRIX_OK" in out


def test_vertex_cut_matrix_8dev():
    """All vertex cuts x all execution models x {sync, epoch_adaptive} on 8
    devices (2x4 cartesian grid)."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=128, epochs=3,
        vcuts=("random", "cartesian2d", "libra"),
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync", "epoch_adaptive"),
    ), n_devices=8, timeout=600)
    assert "VC_MATRIX_OK" in out


def test_vertex_cut_determinism_and_recompile_4dev():
    """Same seed -> bitwise-identical losses across runs AND engines, and the
    jitted step compiles EXACTLY once per config."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        cfg = EngineConfig(partition_family="vertex_cut", vertex_cut="libra",
                           execution="p2p", protocol="epoch_adaptive",
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
        print("VC_DET_OK", l1[-1])
    """, n_devices=4)
    assert "VC_DET_OK" in out


def test_vertex_cut_comm_stats_cross_check_4dev():
    """Engine-reported CommStats.replica_sync_bytes == the standalone
    replication-aware cost model over a layout rebuilt from scratch, for
    every execution model; p2p (master-based GAS) must move fewer bytes than
    broadcast/ring (full partial-block exchange)."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph
        from repro.core.partition.cost_models import replica_sync_bytes_per_step
        from repro.core.partition.vertex_cut import VERTEX_CUTS
        from repro.core.partition.vertex_layout import build_vertex_layout

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        seen = {}
        for exe in ("broadcast", "ring", "p2p"):
            cfg = EngineConfig(partition_family="vertex_cut",
                               vertex_cut="libra", execution=exe,
                               hidden=16, lr=0.3)
            eng = DistGNNEngine(g, cfg=cfg)
            eng.train(4)
            lay = build_vertex_layout(g, VERTEX_CUTS["libra"](g, 4, seed=0), 4)
            expected = 4 * replica_sync_bytes_per_step(
                lay.rep_count, 4, lay.nv, exe, eng.dims)
            got = eng.comm_stats.replica_sync_bytes
            assert got == expected and got > 0, (exe, got, expected)
            assert eng.comm_stats.total() == got  # counted as wire bytes
            seen[exe] = got
        assert seen["p2p"] < seen["broadcast"] == seen["ring"], seen
        print("VC_BYTES_OK", seen)
    """, n_devices=4)
    assert "VC_BYTES_OK" in out


def test_vertex_cut_anchors_to_edge_cut_oracle_4dev():
    """Family anchor: under sync the two families compute the same global
    GCN from the same param init, so their single-device references must
    produce the same losses — and the vertex-cut DISTRIBUTED run matches
    both."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        cfgv = EngineConfig(partition_family="vertex_cut",
                            vertex_cut="cartesian2d", execution="p2p",
                            hidden=16, lr=0.3)
        cfge = EngineConfig(execution="p2p", hidden=16, lr=0.3)
        engv = DistGNNEngine(g, cfg=cfgv)
        lv_dist, _ = engv.train(4)
        lv_ref, _ = engv.train(4, reference=True)
        le_ref, _ = DistGNNEngine(g, cfg=cfge).train(4, reference=True)
        gap_fam = max(abs(a - b) for a, b in zip(lv_ref, le_ref))
        gap_dist = max(abs(a - b) for a, b in zip(lv_dist, le_ref))
        assert gap_fam <= 1e-4, gap_fam
        assert gap_dist <= 1e-4, gap_dist
        print("VC_ANCHOR_OK", gap_fam, gap_dist)
    """, n_devices=4)
    assert "VC_ANCHOR_OK" in out


def test_vertex_cut_rejects_bad_config():
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph
    from repro.core.partition.edge_cut import hash_partition

    g = er_graph(32, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="nope"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="vertex_cut",
                                          vertex_cut="nope"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="vertex_cut",
                                          batching="node_wise"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(partition_family="vertex_cut"),
                      partition=hash_partition(g, 1))


def test_vertex_cut_single_device_paths_agree():
    """On one device the distributed vertex-cut step IS the oracle (every
    replica table degenerate) and still learns."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.01, seed=1)
    mesh = make_mesh((1,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
        partition_family="vertex_cut", vertex_cut="libra", execution="p2p",
        hidden=16, lr=0.3))
    ld, _ = eng.train(8)
    lr_, _ = eng.train(8, reference=True)
    assert max(abs(a - b) for a, b in zip(ld, lr_)) < 1e-4
    assert ld[-1] < ld[0]


def test_sorted_masters_layout_equivalent_4dev():
    """``sorted_masters=True`` reorders each device's replica slots
    master-first (the contiguous-prefix layout the autotuner weighs) — a
    pure relabeling: training must still match the oracle, and the
    de-layouted global embeddings must equal the default layout's (the
    prefix-slice read path agrees with the boolean-mask read path)."""
    out = run_with_devices("""
        import numpy as np
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(100, avg_degree=8, seed=2)
        embs = {}
        for sm in (False, True):
            cfg = EngineConfig(partition_family="vertex_cut",
                               vertex_cut="libra", execution="p2p",
                               sorted_masters=sm, hidden=16, lr=0.3)
            eng = DistGNNEngine(g, cfg=cfg)
            ld, _ = eng.train(4)
            lr_, _ = eng.train(4, reference=True)
            err = max(abs(a - b) for a, b in zip(ld, lr_))
            assert err <= 1e-4, (sm, err)
            lay = eng.playout.layout
            if sm:
                # masters ARE the per-device slot prefix
                for d in range(eng.k):
                    n = int(lay.master_counts[d])
                    mm = lay.master_mask[d] > 0.5
                    assert mm[:n].all() and not mm[n:].any(), d
            state = eng.init_state()
            embs[sm] = eng.global_embeddings(
                eng.infer_full_graph(state))
        np.testing.assert_array_equal(embs[False], embs[True])
        print("VC_SORTED_OK")
    """, n_devices=4, timeout=600)
    assert "VC_SORTED_OK" in out
