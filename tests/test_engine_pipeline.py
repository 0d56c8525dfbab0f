"""Pipelined hot-path tier (ISSUE 4): the double-buffered sampler epoch and
the chunked/bucketed exchange may not change ANY math.

Locked down here:

* ``run_epoch_minibatch(schedule="pipelined")`` is bitwise-identical to the
  blocking schedules — losses, final params, and CommStats — across
  batching x execution, with the one-compile-per-config guard intact;
* feature-chunked + bucketed exchanges match the single-device oracle for
  BOTH partition families and all three execution models, and the chunked
  full-graph step reproduces the monolithic one;
* the `PrefetchWorker` shuts down cleanly when either lane dies mid-epoch;
* the overlap-aware cost models (bucketed cap widths, gathered-table peak,
  overlapped step time, pipelined wall) hold their structural invariants,
  and the pipelined wall model is cross-checked against MEASURED lanes.
"""
import time

import numpy as np
import pytest

from conftest import run_with_devices


def test_pipelined_equals_blocking_4dev():
    """Pipelined epoch == blocking epoch bitwise (losses, params, CommStats)
    for every sampler x execution model, with chunked exchange + bucketed
    p2p caps on, and exactly ONE compile per config."""
    out = run_with_devices("""
        import itertools
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
        for batching, exe in itertools.product(
                ("node_wise", "layer_wise", "subgraph"),
                ("broadcast", "ring", "p2p")):
            cfg = EngineConfig(
                execution=exe, batching=batching, batch_size=8,
                fanouts=(3, 3), layer_sizes=(16, 16), walk_length=3,
                hidden=16, lr=0.3, cache_policy="static_degree",
                cache_capacity=12, exchange_chunks=2, p2p_buckets=2,
                prefetch_depth=2)
            eng = DistGNNEngine(g, cfg=cfg)
            s1, l1, t1 = eng.run_epoch_minibatch(4, schedule="conventional")
            stats1 = eng.comm_stats
            s2, l2, t2 = eng.run_epoch_minibatch(4, schedule="pipelined")
            tag = f"{batching}/{exe}"
            assert l1 == l2, (tag, l1, l2)
            eq = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                        s1["params"], s2["params"])
            assert all(jax.tree_util.tree_leaves(eq)), (tag, eq)
            assert eng.comm_stats == stats1, (tag, eng.comm_stats, stats1)
            assert eng._jit_mb_step._cache_size() == 1, (
                tag, eng._jit_mb_step._cache_size())
            print(f"{tag}: pipelined == blocking bitwise, 1 compile")
        print("PIPE_EQ_OK")
    """, n_devices=4, timeout=600)
    assert "PIPE_EQ_OK" in out


def test_process_pipelined_equals_blocking_4dev():
    """The GIL-free data plane may not change ANY math: a process-pool
    pipelined epoch (shared-memory graph + batch ring, forkserver workers)
    is bitwise-identical to the blocking epoch — losses, params, CommStats —
    across samplers and execution models, the pool is REUSED across epochs,
    and close_prefetch_pool() leaves /dev/shm empty."""
    out = run_with_devices("""
        import os
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=8, p_in=0.08, p_out=0.01, seed=0)
        for batching, exe in (("node_wise", "p2p"), ("layer_wise", "ring"),
                              ("subgraph", "broadcast")):
            cfg = EngineConfig(
                execution=exe, batching=batching, batch_size=8,
                fanouts=(3, 3), layer_sizes=(16, 16), walk_length=3,
                hidden=16, lr=0.3, cache_policy="static_degree",
                cache_capacity=12, exchange_chunks=2, p2p_buckets=2,
                prefetch_depth=2, prefetch_mode="process",
                num_sample_workers=2)
            eng = DistGNNEngine(g, cfg=cfg)
            s1, l1, t1 = eng.run_epoch_minibatch(4, schedule="conventional")
            stats1 = eng.comm_stats
            s2, l2, t2 = eng.run_epoch_minibatch(4, schedule="pipelined")
            tag = f"{batching}/{exe}"
            assert l1 == l2, (tag, l1, l2)
            eq = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                        s1["params"], s2["params"])
            assert all(jax.tree_util.tree_leaves(eq)), (tag, eq)
            assert eng.comm_stats == stats1, (tag, eng.comm_stats, stats1)
            assert eng._jit_mb_step._cache_size() == 1, (
                tag, eng._jit_mb_step._cache_size())
            # epoch 2 on the SAME pool: workers + shm ring reused
            pool = eng._proc_pool
            s3, l3, t3 = eng.run_epoch_minibatch(4, schedule="pipelined")
            assert l3 == l2, (tag, l3, l2)
            assert eng._proc_pool is pool and pool.alive
            eng.close_prefetch_pool()
            litter = [f for f in os.listdir('/dev/shm')
                      if f.startswith('repro-')]
            assert not litter, (tag, litter)
            print(f"{tag}: process-pipelined == blocking bitwise, "
                  "pool reused, shm clean")
        print("PROC_PIPE_EQ_OK")
    """, n_devices=4, timeout=600)
    assert "PROC_PIPE_EQ_OK" in out


def test_chunked_bucketed_matches_oracle_4dev():
    """Feature-chunked exchange + bucketed p2p installments across BOTH
    partition families and all execution models: the full-graph step must
    match the single-device oracle (<=1e-4) and the chunked losses must
    reproduce the monolithic ones."""
    out = run_with_devices("""
        import jax
        from repro.core.engine import DistGNNEngine, EngineConfig
        from repro.core.graph import sbm_graph

        g = sbm_graph(96, num_blocks=4, p_in=0.08, p_out=0.01, seed=0)
        for family, vc in (("edge_cut", None), ("vertex_cut", "cartesian2d")):
            for exe in ("broadcast", "ring", "p2p"):
                kw = dict(partition_family=family, execution=exe,
                          hidden=16, lr=0.3)
                if vc:
                    kw["vertex_cut"] = vc
                eng = DistGNNEngine(g, cfg=EngineConfig(
                    exchange_chunks=3, p2p_buckets=2, **kw))
                ld, _ = eng.train(3)
                lr_, _ = eng.train(3, reference=True)
                err = max(abs(a - b) for a, b in zip(ld, lr_))
                assert err <= 1e-4, (family, exe, err)
                mono = DistGNNEngine(g, cfg=EngineConfig(**kw))
                lm, _ = mono.train(3)
                merr = max(abs(a - b) for a, b in zip(ld, lm))
                assert merr <= 1e-6, (family, exe, merr)
                print(f"{family}/{exe}: oracle={err:.2e} "
                      f"chunked-vs-monolithic={merr:.2e}")
        print("CHUNK_ORACLE_OK")
    """, n_devices=4, timeout=600)
    assert "CHUNK_ORACLE_OK" in out


def test_prefetch_worker_exception_shutdown():
    """Either lane dying mid-epoch must stop and join the worker thread —
    no hang, no orphaned producer."""
    from repro.core.execution.minibatch_pipeline import run_pipelined
    from repro.core.sampling.prefetch import PrefetchWorker

    # producer raises at item 2: the error surfaces at its position and the
    # thread has exited by the time the consumer sees it
    def bad_produce(i):
        if i == 2:
            raise ValueError("sampler died")
        return i * 10

    w = PrefetchWorker(range(5), bad_produce, depth=2)
    got = []
    with pytest.raises(ValueError, match="sampler died"):
        for item in w:
            got.append(item)
    assert got == [0, 10]
    w.close()
    assert not w.alive

    # consumer abandons mid-iteration while the queue is full: close() must
    # unblock the producer's pending put and join
    w = PrefetchWorker(range(100), lambda i: i, depth=1)
    assert next(iter(w)) == 0
    w.close()
    assert not w.alive

    # train_fn raising propagates out of run_pipelined with the worker closed
    def bad_train(mb, feats):
        raise RuntimeError("device step died")

    with pytest.raises(RuntimeError, match="device step died"):
        run_pipelined(list(range(50)), lambda i: i, lambda mb: mb, bad_train)
    # results arrive strictly in order under a slow consumer
    seen = []
    run_pipelined(list(range(20)), lambda i: i, lambda mb: mb,
                  lambda mb, feats: (time.sleep(0.001), seen.append(feats)))
    assert seen == list(range(20))


def test_prefetch_depth_validation():
    from repro.core.engine import DistGNNEngine, EngineConfig
    from repro.core.graph import er_graph
    from repro.core.sampling.prefetch import PrefetchWorker

    with pytest.raises(ValueError):
        PrefetchWorker([1], lambda i: i, depth=0)
    g = er_graph(32, avg_degree=4, seed=0)
    for kw in (dict(exchange_chunks=0), dict(p2p_buckets=0),
               dict(prefetch_depth=0)):
        with pytest.raises(ValueError):
            DistGNNEngine(g, cfg=EngineConfig(**kw))


def test_chunked_overlap_unit():
    """chunked_overlap == monolithic for any chunk count, including uneven
    feature widths (pure consumer math, no devices)."""
    import jax.numpy as jnp

    from repro.core.execution.pipeline_exchange import chunked_overlap

    h = jnp.arange(5 * 7, dtype=jnp.float32).reshape(5, 7)
    exchange = lambda hc: hc * 2.0  # noqa: E731
    consume = lambda gc: gc + 1.0  # noqa: E731
    ref = consume(exchange(h))
    for C in (1, 2, 3, 5, 7, 16):
        out = chunked_overlap(h, C, exchange, consume)
        assert out.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref)), C


def test_ell_spmm_block_kwargs():
    """The chunk-friendly kernel call path: explicit row blocks (as a
    chunked caller with a narrow table would pick) reproduce the default
    grid bit for bit, forward AND backward.  ``row_block`` tiles the Pallas
    gather-dot, the gradient of `ell_attend`'s weights; `ell_spmm` is the
    same sum with the weights as structure."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ell_spmm import ell_attend, ell_spmm

    rng = np.random.default_rng(0)
    V, K, N, D = 20, 4, 24, 9  # D narrow, like one feature chunk
    ids = jnp.asarray(rng.integers(0, N, (V, K)), jnp.int32)
    mask = jnp.asarray((rng.random((V, K)) < 0.7).astype(np.float32))
    H = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))

    def run(**kw):
        def loss(w, h):
            out = ell_attend(ids, w, h, interpret=True, **kw)
            return (out * out).sum(), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(mask, H)
        return [np.asarray(x) for x in (out, *grads)]

    ref = run()
    _, spmm_dh = jax.value_and_grad(
        lambda h: (ell_spmm(ids, mask, h, normalize=False) ** 2).sum())(H)
    np.testing.assert_array_equal(np.asarray(spmm_dh), ref[2])
    for kw in (dict(row_block=8), dict(row_block=16), dict(row_block=32)):
        for got, want in zip(run(**kw), ref):
            np.testing.assert_array_equal(got, want, err_msg=str(kw))


def test_bucketed_cap_widths_invariants():
    from repro.core.execution.pipeline_exchange import (
        bucketed_cap_widths,
        halo_slot,
    )

    for cap in (1, 2, 5, 6, 17, 100, 1000):
        for buckets in (1, 2, 4, 8):
            widths = bucketed_cap_widths(cap, buckets)
            assert sum(widths) >= cap, (cap, buckets, widths)
            assert len(widths) <= max(buckets, 1), (cap, buckets, widths)
            assert len(set(widths)) == 1  # equal installments
            if len(widths) > 1:
                w = widths[0]
                assert w & (w - 1) == 0  # power of two
                # the point: each installment buffer is smaller than the cap
                assert w < cap
    # the slot layout is a bijection into [base, base + B*k*w)
    cap, buckets, k, base = 11, 4, 3, 7
    widths = bucketed_cap_widths(cap, buckets)
    B, w = len(widths), widths[0]
    slots = set()
    for s in range(k):
        for t in range(cap):
            slot = int(halo_slot(t, s, w, k, base))
            assert base <= slot < base + B * k * w
            slots.add(slot)
    assert len(slots) == k * cap
    # single bucket reproduces the classic base + s*cap + t layout
    assert halo_slot(3, 2, cap, k, base) == base + 2 * cap + 3


def test_overlap_step_time_model():
    from repro.core.partition.cost_models import overlapped_step_time

    comm, comp = 8.0, 5.0
    assert overlapped_step_time(comm, comp, 1) == comm + comp
    prev = comm + comp
    for C in (2, 4, 8, 64):
        t = overlapped_step_time(comm, comp, C)
        assert max(comm, comp) <= t <= prev + 1e-12  # monotone toward max
        prev = t
    assert abs(overlapped_step_time(comm, comp, 10**6) - comm) < 1e-3


def test_pipelined_wall_model_crosscheck_measured_lanes():
    """The overlap-aware wall model against MEASURED lanes: with sleepy
    (GIL-releasing) stages the pipelined executor must land between the
    model's two-lane bound and the blocking serial sum."""
    from repro.core.execution.minibatch_pipeline import (
        pipelined_wall_model,
        run_conventional,
        run_pipelined,
    )

    ids = list(range(6))
    sample = lambda i: time.sleep(0.008) or i  # noqa: E731
    extract = lambda mb: time.sleep(0.002) or mb  # noqa: E731
    train = lambda mb, f: time.sleep(0.012)  # noqa: E731
    blocking = run_conventional(ids, sample, extract, train)
    piped = run_pipelined(ids, sample, extract, train, prefetch_depth=2)
    model = pipelined_wall_model(piped, len(ids))
    # real overlap: below the serial sum, above the slower measured lane
    assert piped.wall < 0.9 * blocking.wall, (piped.wall, blocking.wall)
    assert piped.wall >= 0.8 * model, (piped.wall, model)
    assert piped.busy() > piped.wall  # lanes genuinely ran concurrently
