"""DistGNNEngine MODEL-AXIS tier (subprocess, forced host devices): the
survey's §3 models {sage, gat, gin} through every jitted path — full-graph
edge-cut and vertex-cut (all execution models) and sampled mini-batches —
must match the extended single-device oracle to <=1e-4 (gcn is pinned by the
older tiers).  The model may not change where the math runs: sage/gin's self
features stay resident, gat's edge-wise attention rides the SDDMM logits +
masked segment-softmax (two-pass max/sum replica sync under vertex_cut), and
pad slots stay inert everywhere.

Also locked down here: bitwise determinism and the one-compile guard on the
hairiest path (gat x vertex_cut x p2p), CommStats == the model-aware
replica-sync cost model (gat pays the attention-coefficient bytes; sage/gin
pay exactly gcn's), and the bucketed mini-batch frontier fetch (satellite:
power-of-two installments replace the monolithic fcap send buffer,
loss-identical to the monolithic plan).
"""
import pytest

from conftest import run_with_devices
from repro.compat import make_mesh

_FULL_GRAPH_CODE = """
    import itertools
    import jax, numpy as np
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph({V}, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
    fails = []
    for i, (model, exe) in enumerate(
            itertools.product({models}, {execs})):
        proto = {protocols}[i % len({protocols})]
        cfg = EngineConfig(model=model, execution=exe, protocol=proto,
                           partition_family={family!r},
                           vertex_cut="cartesian2d", hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        losses_d, logits_d = eng.train({epochs})
        losses_r, logits_r = eng.train({epochs}, reference=True)
        err = max(abs(a - b) for a, b in zip(losses_d, losses_r))
        lerr = float(abs(logits_d - logits_r).max())
        tag = f"{{model}}/{{exe}}/{{proto}}"
        print(f"{{tag}}: loss_err={{err:.2e}} logits_err={{lerr:.2e}}")
        if not (err <= 1e-4 and np.isfinite(losses_d[-1])):
            fails.append((tag, err))
    assert not fails, fails
    print("MODEL_MATRIX_OK")
"""


def test_model_matrix_edge_cut_4dev():
    """models x execution models on the edge-cut full-graph path, cycling
    the protocols so async history rides every model."""
    out = run_with_devices(_FULL_GRAPH_CODE.format(
        V=96, epochs=3, family="edge_cut",
        models=("sage", "gat", "gin"),
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync", "epoch_adaptive", "variation"),
    ), n_devices=4, timeout=600)
    assert "MODEL_MATRIX_OK" in out


def test_model_matrix_vertex_cut_4dev():
    """models x replica-sync execution models on the vertex-cut path — the
    gat combination exercises the two-pass (max, then sum) replica sync."""
    out = run_with_devices(_FULL_GRAPH_CODE.format(
        V=80, epochs=3, family="vertex_cut",
        models=("sage", "gat", "gin"),
        execs=("broadcast", "ring", "p2p"),
        protocols=("sync",),
    ), n_devices=4, timeout=600)
    assert "MODEL_MATRIX_OK" in out


def test_model_matrix_8dev():
    """Both partition families x all models on 8 devices (p2p exchange)."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(128, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
        for model in ("sage", "gat", "gin"):
            for family in ("edge_cut", "vertex_cut"):
                cfg = EngineConfig(model=model, execution="p2p",
                                   partition_family=family,
                                   vertex_cut="cartesian2d",
                                   hidden=16, lr=0.3)
                eng = DistGNNEngine(g, cfg=cfg)
                ld, _ = eng.train(3)
                lr_, _ = eng.train(3, reference=True)
                err = max(abs(a - b) for a, b in zip(ld, lr_))
                assert err <= 1e-4 and np.isfinite(ld[-1]), (
                    model, family, err)
                print(f"{model}/{family}: err={err:.2e}")
        print("MODEL_8DEV_OK")
    """, n_devices=8, timeout=600)
    assert "MODEL_8DEV_OK" in out


def test_model_matrix_minibatch_4dev():
    """models x execution models on sampled mini-batches: the padded dense
    blocks + resident self_idx tables vs the vmapped oracle; gat's
    attention runs over the folded self-loop blocks."""
    out = run_with_devices("""
        import itertools
        import jax, numpy as np
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
        batchings = ("node_wise", "layer_wise", "subgraph")
        for i, (model, exe) in enumerate(
                itertools.product(("sage", "gat", "gin"),
                                  ("broadcast", "ring", "p2p"))):
            cfg = EngineConfig(model=model, execution=exe,
                               batching=batchings[i % 3], batch_size=8,
                               fanouts=(3, 3), layer_sizes=(16, 16),
                               walk_length=3, hidden=16, lr=0.3,
                               cache_policy="static_degree",
                               cache_capacity=12)
            eng = DistGNNEngine(g, cfg=cfg)
            ld, logits_d = eng.train(3)
            lr_, logits_r = eng.train(3, reference=True)
            err = max(abs(a - b) for a, b in zip(ld, lr_))
            lerr = float(abs(logits_d - logits_r).max())
            tag = f"{model}/{exe}/{cfg.batching}"
            assert err <= 1e-4 and lerr <= 1e-4, (tag, err, lerr)
            print(f"{tag}: err={err:.2e} lerr={lerr:.2e}")
        print("MODEL_MB_OK")
    """, n_devices=4, timeout=600)
    assert "MODEL_MB_OK" in out


def test_model_determinism_and_recompile_4dev():
    """gat x vertex_cut x p2p (the most plan-heavy path): bitwise-identical
    losses across runs AND engines, exactly one compile per config."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        cfg = EngineConfig(model="gat", partition_family="vertex_cut",
                           vertex_cut="libra", execution="p2p",
                           protocol="epoch_adaptive", hidden=16, lr=0.3)
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
        # mini-batch gat: one compile too (self_idx tables are static)
        cfgm = EngineConfig(model="gat", execution="p2p",
                            batching="node_wise", batch_size=8,
                            fanouts=(3, 3), hidden=16, lr=0.3)
        engm = DistGNNEngine(g, cfg=cfgm)
        m1, _ = engm.train(4)
        assert engm._jit_mb_step._cache_size() == 1
        m2, _ = engm.train(4)
        assert m1 == m2, (m1, m2)
        print("MODEL_DET_OK", l1[-1], m1[-1])
    """, n_devices=4)
    assert "MODEL_DET_OK" in out


def test_model_comm_stats_cross_check_4dev():
    """Engine-reported replica-sync bytes == the MODEL-AWARE cost model for
    every model x execution; gat pays the attention-coefficient + max-pass
    bytes, sage/gin pay exactly gcn's bytes (self features are resident)."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph
        from repro.core.partition.cost_models import (
            model_exchange_widths, replica_sync_bytes_per_step)
        from repro.core.partition.vertex_cut import VERTEX_CUTS
        from repro.core.partition.vertex_layout import build_vertex_layout

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        lay = build_vertex_layout(g, VERTEX_CUTS["libra"](g, 4, seed=0), 4)
        per_model = {}
        for model in ("gcn", "sage", "gat", "gin"):
            for exe in ("broadcast", "ring", "p2p"):
                cfg = EngineConfig(model=model, partition_family="vertex_cut",
                                   vertex_cut="libra", execution=exe,
                                   hidden=16, lr=0.3)
                eng = DistGNNEngine(g, cfg=cfg)
                eng.train(3)
                expected = 3 * replica_sync_bytes_per_step(
                    lay.rep_count, 4, lay.nv, exe, eng.dims, model=model)
                got = eng.comm_stats.replica_sync_bytes
                assert got == expected and got > 0, (model, exe, got, expected)
            per_model[model] = got
            widths = model_exchange_widths(model, eng.dims, "vertex_cut")
            print(model, "widths", widths, "p2p bytes", got)
        assert per_model["sage"] == per_model["gcn"]
        assert per_model["gin"] == per_model["gcn"]
        assert per_model["gat"] != per_model["gcn"]
        print("MODEL_BYTES_OK", per_model)
    """, n_devices=4, timeout=600)
    assert "MODEL_BYTES_OK" in out


def test_minibatch_fcap_bucketing_4dev():
    """Satellite: the p2p frontier fetch rides power-of-two installments —
    bucketed plans are loss-identical (bitwise) to the monolithic fcap
    buffer and still match the oracle; the per-round send operand is
    ~buckets x narrower."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        kw = dict(execution="p2p", batching="node_wise", batch_size=12,
                  fanouts=(4, 4), hidden=16, lr=0.3)
        e1 = DistGNNEngine(g, cfg=EngineConfig(**kw))
        eB = DistGNNEngine(g, cfg=EngineConfig(p2p_buckets=4, **kw))
        assert len(eB.fcap_widths) > 1, (eB.fcap, eB.fcap_widths)
        assert eB.fcap_widths[0] < e1.fcap_widths[0]
        assert sum(eB.fcap_widths) >= eB.fcap  # still covers the halo cap
        l1, _ = e1.train(4)
        lB, _ = eB.train(4)
        assert l1 == lB, (l1, lB)
        lr_, _ = eB.train(4, reference=True)
        err = max(abs(a - b) for a, b in zip(lB, lr_))
        assert err <= 1e-4, err
        print("FCAP_BUCKETS_OK", e1.fcap, eB.fcap_widths)
    """, n_devices=4)
    assert "FCAP_BUCKETS_OK" in out


def test_stale_protocol_config_fails_fast():
    """Satellite: a config mutated to an async protocol AFTER construction
    fails at epoch entry with an actionable message, not deep in jit."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(32, avg_degree=4, seed=0)
    mesh = make_mesh((1,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
        batching="node_wise", batch_size=4, fanouts=(2, 2), hidden=8))
    eng.cfg.protocol = "epoch_adaptive"  # stale mutation
    with pytest.raises(ValueError, match="protocol='sync'"):
        eng.run_epoch_minibatch(2)
    with pytest.raises(ValueError, match="protocol='sync'"):
        eng.train(2)
    eng.cfg.protocol = "sync"
    _, losses, _ = eng.run_epoch_minibatch(2)  # recovers once fixed
    assert len(losses) == 2
    # full-graph engines reject the mini-batch epoch entry too
    eng2 = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(hidden=8))
    with pytest.raises(ValueError, match="full_graph"):
        eng2.run_epoch_minibatch(2)


def test_model_single_device_paths_agree():
    """On one device every model's distributed step IS its oracle, and it
    learns."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.01, seed=1)
    mesh = make_mesh((1,), ("w",))
    for model in ("sage", "gat", "gin"):
        eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
            model=model, execution="p2p", hidden=16, lr=0.2))
        ld, _ = eng.train(8)
        lr_, _ = eng.train(8, reference=True)
        assert max(abs(a - b) for a, b in zip(ld, lr_)) < 1e-4, model
        assert ld[-1] < ld[0], (model, ld)


def test_gat_fused_s_column_chunk_invariance_4dev():
    """The edge-cut GAT attention-coefficient column rides CHUNK 0 of the
    chunked exchange (fused with the first Hw columns) instead of a separate
    width-1 pre-pass — so the forward pass must be BITWISE identical for any
    ``exchange_chunks`` (per-column math never changes with the chunking),
    and training must stay on the oracle contract."""
    out = run_with_devices("""
        import numpy as np
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(60, num_blocks=4, p_in=0.1, p_out=0.02, seed=0)
        for exe in ("broadcast", "p2p"):
            base_fwd = base_loss1 = None
            for C in (1, 2, 3):
                cfg = EngineConfig(model="gat", execution=exe,
                                   exchange_chunks=C, hidden=12, lr=0.3)
                eng = DistGNNEngine(g, cfg=cfg)
                fwd = np.asarray(eng.infer_full_graph(
                    eng.init_state())).tobytes()
                losses, _ = eng.train(3)
                lr_, _ = eng.train(3, reference=True)
                err = max(abs(a - b) for a, b in zip(losses, lr_))
                assert err <= 1e-4, (exe, C, err)
                if base_fwd is None:
                    base_fwd, base_loss1 = fwd, losses[0]
                else:
                    # forward sweep: bitwise equal across chunk counts
                    assert fwd == base_fwd, (exe, C)
                    # first loss is forward-only -> bitwise equal too
                    assert losses[0] == base_loss1, (exe, C)
        print("GAT_FUSE_OK")
    """, n_devices=4, timeout=600)
    assert "GAT_FUSE_OK" in out
