"""DistGNNEngine mini-batch tier (subprocess, forced host devices): every
sampler x execution model x cache configuration must match the single-device
`reference_minibatch_step` oracle to <=1e-4 — the oracle consumes the EXACT
same sampled, padded batches (host sampling is deterministic in
(seed, step, device)), so partition-block target draws, static padding, the
feature-fetch exchange, and the resident cache may not change the math.

Also locked down here: bitwise determinism across runs, the one-compile-per-
fanout-config contract (recompile-count guard), and the agreement between the
engine's reported feature bytes and the standalone
`feature_fetch_bytes` / `CommStats` cost model.
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
    for batching, exe in itertools.product({batchings}, {execs}):
        cfg = EngineConfig(
            execution=exe, batching=batching, batch_size=8,
            fanouts=(3, 3), layer_sizes=(16, 16), walk_length=3,
            hidden=16, lr=0.3,
            cache_policy={cache_policy!r}, cache_capacity={cache_capacity})
        eng = DistGNNEngine(g, cfg=cfg)
        losses_d, logits_d = eng.train({epochs})
        losses_r, logits_r = eng.train({epochs}, reference=True)
        err = max(abs(a - b) for a, b in zip(losses_d, losses_r))
        lerr = float(abs(logits_d - logits_r).max())
        tag = f"{{batching}}/{{exe}}/cache={{cfg.cache_policy}}"
        print(f"{{tag}}: loss_err={{err:.2e}} logits_err={{lerr:.2e}}")
        if not (err <= 1e-4 and lerr <= 1e-4 and np.isfinite(losses_d[-1])):
            fails.append((tag, err, lerr))
    assert not fails, fails
    print("MB_MATRIX_OK")
"""


def test_minibatch_matrix_4dev_nocache():
    """All samplers x all execution models, no cache, 4 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=96, epochs=3,
        batchings=("node_wise", "layer_wise", "subgraph"),
        execs=("broadcast", "ring", "p2p"),
        cache_policy="none", cache_capacity=0,
    ), n_devices=4, timeout=600)
    assert "MB_MATRIX_OK" in out


def test_minibatch_matrix_4dev_cached():
    """All samplers x all execution models with the static-degree resident
    cache: hits must short-circuit the exchange without changing the math."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=96, epochs=3,
        batchings=("node_wise", "layer_wise", "subgraph"),
        execs=("broadcast", "ring", "p2p"),
        cache_policy="static_degree", cache_capacity=12,
    ), n_devices=4, timeout=600)
    assert "MB_MATRIX_OK" in out


def test_minibatch_matrix_8dev():
    """Execution models x {node_wise, subgraph}, cache on, 8 devices."""
    out = run_with_devices(_MATRIX_CODE.format(
        V=128, epochs=3,
        batchings=("node_wise", "subgraph"),
        execs=("broadcast", "ring", "p2p"),
        cache_policy="static_degree", cache_capacity=12,
    ), n_devices=8, timeout=600)
    assert "MB_MATRIX_OK" in out


def test_minibatch_determinism_and_recompile_4dev():
    """Same seed -> bitwise-identical losses (host sampling is part of the
    SPMD contract), and the jitted step compiles EXACTLY once across steps
    with fixed fanouts (static padding caps)."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        cfg = EngineConfig(execution="p2p", batching="node_wise",
                           batch_size=8, fanouts=(3, 3), hidden=16, lr=0.3,
                           cache_policy="static_degree", cache_capacity=12)
        eng = DistGNNEngine(g, cfg=cfg)
        l1, _ = eng.train(5)
        n_compiles = eng._jit_mb_step._cache_size()
        assert n_compiles == 1, f"expected 1 compile, got {n_compiles}"
        l2, _ = eng.train(5)
        assert l1 == l2, (l1, l2)
        assert eng._jit_mb_step._cache_size() == 1
        eng2 = DistGNNEngine(g, cfg=cfg)
        l3, _ = eng2.train(5)
        assert l1 == l3, (l1, l3)
        print("MB_DET_OK", l1[-1])
    """, n_devices=4)
    assert "MB_DET_OK" in out


def test_minibatch_comm_stats_cross_check_4dev():
    """Engine-reported feature bytes == the standalone feature_fetch_bytes
    cost model over the same deterministic frontiers; the cache strictly
    reduces wire bytes while total requested bytes stay identical."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph
        from repro.core.sampling import CommStats, feature_fetch_bytes

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        cfg = EngineConfig(execution="p2p", batching="node_wise",
                           batch_size=8, fanouts=(3, 3), hidden=16, lr=0.3,
                           cache_policy="static_degree", cache_capacity=12)
        eng = DistGNNEngine(g, cfg=cfg)
        eng.train(4)
        stats = eng.comm_stats
        # recompute from a FRESH engine: deterministic sampling means the
        # standalone cost model must reproduce the engine's accounting
        eng2 = DistGNNEngine(g, cfg=cfg)
        expected = CommStats()
        D = g.features.shape[1]
        for i in range(4):
            for d, mb in enumerate(eng2._sample_host(i)):
                feature_fetch_bytes(
                    eng2.part, d, mb.layer_vertices[0], D,
                    cached_ids=set(int(v) for v in eng2.cache_old_ids[d]),
                    stats=expected)
        assert stats.pull_bytes == expected.pull_bytes, (stats, expected)
        assert stats.cache_hit_bytes == expected.cache_hit_bytes
        assert stats.cache_hit_bytes > 0, "cache never hit on a power-law graph"
        # cache off: same requested bytes, strictly more on the wire
        cfg0 = EngineConfig(execution="p2p", batching="node_wise",
                            batch_size=8, fanouts=(3, 3), hidden=16, lr=0.3)
        eng0 = DistGNNEngine(g, cfg=cfg0)
        eng0.train(4)
        assert eng0.comm_stats.cache_hit_bytes == 0
        assert eng0.comm_stats.pull_bytes > stats.pull_bytes
        assert eng0.comm_stats.requested() == stats.requested()
        print("MB_BYTES_OK", stats.pull_bytes, stats.cache_hit_bytes)
    """, n_devices=4)
    assert "MB_BYTES_OK" in out


def test_minibatch_pipeline_schedules_4dev():
    """The §6.1 schedules drive the engine's real sampler / extract / jitted
    train stages and agree on the losses (the schedule only reorders work)."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        cfg = EngineConfig(execution="broadcast", batching="node_wise",
                           batch_size=8, fanouts=(3, 3), hidden=16, lr=0.3)
        eng = DistGNNEngine(g, cfg=cfg)
        ref = None
        for sched in ("conventional", "factored", "operator_parallel"):
            _, losses, times = eng.run_epoch_minibatch(3, schedule=sched)
            assert times.wall > 0 and times.busy() > 0
            if ref is None:
                ref = losses
            else:
                assert losses == ref, (sched, losses, ref)
        print("MB_SCHED_OK", ref)
    """, n_devices=4)
    assert "MB_SCHED_OK" in out


def test_minibatch_rejects_bad_config():
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(32, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(batching="nope"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(batching="node_wise",
                                          protocol="variation"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(cache_policy="nope"))
    with pytest.raises(ValueError):
        DistGNNEngine(g, cfg=EngineConfig(batching="node_wise", fanouts=(3,)))


def test_p2p_fcap_tight_at_256_parts():
    """ROADMAP follow-up from PR 2: the p2p halo cap derives from the
    MEASURED hops-hop halo instead of the worst case caps[0], so the 256-part
    all_to_all buffer shrinks >10x on the power-law config (host-side plan
    math only — no devices needed)."""
    import numpy as np

    from repro.core.graph import powerlaw_graph
    from repro.core.partition.edge_cut import hash_partition
    from repro.core.sampling.partition_batch import p2p_frontier_halo_cap
    from repro.core.sampling.samplers import frontier_caps

    g = powerlaw_graph(4096, avg_degree=8, seed=0)
    part = hash_partition(g, 256)
    caps = frontier_caps("node_wise", 2, 1024, fanouts=(4, 4),
                         num_vertices=g.num_vertices)
    fcap = p2p_frontier_halo_cap(g, part, 2, caps[0])
    assert caps[0] / fcap > 10, (caps[0], fcap)
    # the cap stays a TRUE upper bound: it can never be smaller than the
    # largest single-owner 2-hop halo share, which bounds any sampled batch
    owned = np.bincount(part.assignment, minlength=256)
    assert fcap <= owned.max()


def test_p2p_fcap_is_safe_upper_bound_4dev():
    """Engine-level: the tightened fcap never overflows across many sampled
    batches (the overflow assert in _make_batch stays silent) and the
    exchange still matches the oracle."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import powerlaw_graph

        g = powerlaw_graph(120, avg_degree=8, seed=2)
        for batching, kw in (("node_wise", dict(fanouts=(4, 4))),
                             ("subgraph", dict(walk_length=4))):
            eng = DistGNNEngine(g, cfg=EngineConfig(
                execution="p2p", batching=batching, batch_size=12,
                hidden=16, lr=0.3, **kw))
            assert eng.fcap <= eng.caps[0]
            for i in range(6):
                eng.sample_minibatch(i)  # would assert on overflow
            ld, _ = eng.train(3)
            lr_, _ = eng.train(3, reference=True)
            err = max(abs(a - b) for a, b in zip(ld, lr_))
            assert err <= 1e-4, (batching, err)
            print(f"{batching}: fcap={eng.fcap} caps0={eng.caps[0]} "
                  f"err={err:.2e}")
        print("FCAP_SAFE_OK")
    """, n_devices=4)
    assert "FCAP_SAFE_OK" in out


def test_minibatch_single_device_paths_agree():
    """On one device the distributed mini-batch step IS the oracle."""
    import jax

    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import sbm_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.01, seed=1)
    mesh = make_mesh((1,), ("w",))
    eng = DistGNNEngine(g, mesh=mesh, cfg=EngineConfig(
        execution="p2p", batching="node_wise", batch_size=8, fanouts=(3, 3),
        hidden=16, lr=0.3, cache_policy="static_degree", cache_capacity=8))
    ld, _ = eng.train(8)
    lr_, _ = eng.train(8, reference=True)
    assert max(abs(a - b) for a, b in zip(ld, lr_)) < 1e-4
    assert min(ld) < ld[0]  # it learns
