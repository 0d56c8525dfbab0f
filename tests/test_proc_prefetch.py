"""Process-pool prefetch failure-mode tier (sampling/proc_prefetch.py).

The GIL-free sampler pool must uphold the thread `PrefetchWorker`'s
contracts across a PROCESS boundary: strict in-order delivery (bitwise
reuse across epochs of one pool), producer exceptions relayed to the
consumer at the batch index they occurred (including BaseException and
unpicklable exceptions), the consumer abandoning mid-epoch never strands a
worker blocked on the full shared-memory ring, the tightest ring
(depth=1, workers > slots) completes in order without deadlock, and
close() always unlinks every shared-memory segment — no /dev/shm litter,
no resource-tracker "leaked shared_memory" warnings at interpreter exit.

Everything here is numpy-only by construction: workers must never import
jax (`host_batch` keeps the producer import chain clean), so this tier
runs without devices.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

from repro.core.sampling.proc_prefetch import (  # noqa: E402
    ProcPrefetchPool,
    ProcPrefetchWorker,
    WorkerFailure,
)

LAYOUT = {"a": ((4,), np.dtype(np.int64)),
          "b": ((2, 3), np.dtype(np.float32))}


def _produce(i):
    return ({"a": np.arange(4, dtype=np.int64) + i,
             "b": np.full((2, 3), float(i), np.float32)},
            {"item": i, "sample_seconds": 0.0, "extract_seconds": 0.0})


def _produce_env(i):
    arrays, meta = _produce(i)
    meta.update(jax_loaded="jax" in sys.modules,
                platforms=os.environ.get("JAX_PLATFORMS"))
    return arrays, meta


def _shm_litter():
    return [f for f in os.listdir("/dev/shm") if f.startswith("repro-")]


def _fail_at(i):
    if i == _fail_at.at:
        raise ValueError(f"boom at {i}")
    return _produce(i)


_fail_at.at = None


def _fail_first(i):
    _fail_at.at = 0
    return _fail_at(i)


def _fail_mid(i):
    _fail_at.at = 3
    return _fail_at(i)


def _fail_last(i):
    _fail_at.at = 5
    return _fail_at(i)


def _fail_base(i):
    if i == 1:
        raise KeyboardInterrupt
    return _produce(i)


class _Unpicklable(Exception):
    def __init__(self):
        super().__init__("cursed")
        self.payload = lambda: None  # lambdas don't pickle


def _fail_unpicklable(i):
    if i == 2:
        raise _Unpicklable()
    return _produce(i)


def test_in_order_delivery_and_pool_reuse():
    """Strict input order, correct slot contents, and the SAME pool serving
    multiple epochs (monotone global indices, shm ring reused).
    cache_items=0 keeps every epoch on the ring — the LRU fast path has its
    own test below."""
    pool = ProcPrefetchPool(_produce, LAYOUT, depth=2, num_workers=3,
                            cache_items=0)
    try:
        for _epoch in range(3):
            out = list(pool.run(list(range(7))))
            assert [o[0] for o in out] == list(range(7))
            for item, arrays, meta in out:
                np.testing.assert_array_equal(
                    arrays["a"], np.arange(4, dtype=np.int64) + item)
                assert arrays["b"][0, 0] == item
                assert meta["item"] == item
                # delivered arrays are COPIES — ring reuse can't alias them
                arrays["a"][:] = -1
    finally:
        pool.close()
    assert not pool.alive
    assert _shm_litter() == []


_SEEN: dict = {}  # per-worker-process memory for _produce_once


def _produce_once(i):
    if i in _SEEN:
        raise RuntimeError(f"resampled item {i}")
    _SEEN[i] = True
    return _produce(i)


def test_finished_batch_cache_skips_workers():
    """Deterministic producers are pure functions of their item, so the
    pool's LRU serves repeat items without touching a worker: a producer
    that FAILS on re-request proves epoch 2 never resampled."""
    pool = ProcPrefetchPool(_produce_once, LAYOUT, depth=2, num_workers=1)
    try:
        out1 = list(pool.run(list(range(5))))
        out2 = list(pool.run(list(range(5))))  # all hits — no worker calls
        for (i1, a1, m1), (i2, a2, m2) in zip(out1, out2):
            assert i1 == i2
            np.testing.assert_array_equal(a1["a"], a2["a"])
            assert m2["cache_hit"] and m2["sample_seconds"] == 0.0
            a2["a"][:] = -7  # hits hand out copies too
        out2b = list(pool.run(list(range(5))))
        assert out2b[0][1]["a"][0] == 0  # mutation did not reach the cache
        # mixed epoch: cached 0/1 around a fresh item — order preserved
        out3 = list(pool.run([0, 6, 1]))
        assert [o[0] for o in out3] == [0, 6, 1]
        np.testing.assert_array_equal(out3[2][1]["a"],
                                      np.arange(4, dtype=np.int64) + 1)
        assert len(pool._cache) <= pool.cache_items
    finally:
        pool.close()
    assert _shm_litter() == []


def test_cache_pinned_hits_survive_eviction():
    """A hit planned at run() start must deliver even if this epoch's own
    misses evict its LRU entry before its turn (cache_items=1)."""
    pool = ProcPrefetchPool(_produce, LAYOUT, depth=1, num_workers=1,
                            cache_items=1)
    try:
        list(pool.run([0, 1]))           # cache = {1}
        out = list(pool.run([1, 0, 1]))  # miss 0 evicts 1 mid-epoch
        assert [o[0] for o in out] == [1, 0, 1]
        np.testing.assert_array_equal(out[2][1]["a"],
                                      np.arange(4, dtype=np.int64) + 1)
    finally:
        pool.close()
    assert _shm_litter() == []


def test_depth1_more_workers_than_slots_no_deadlock():
    """The tightest ring with more workers than slots: the released-counter
    protocol keeps the writer of the next-released index unblocked."""
    pool = ProcPrefetchPool(_produce, LAYOUT, depth=1, num_workers=3)
    try:
        out = list(pool.run(list(range(12))))
        assert [o[0] for o in out] == list(range(12))
    finally:
        pool.close()
    assert _shm_litter() == []


@pytest.mark.parametrize("produce,at,n", [(_fail_first, 0, 5),
                                          (_fail_mid, 3, 6),
                                          (_fail_last, 5, 6)])
def test_exception_relayed_at_batch_index(produce, at, n):
    """A producer exception surfaces in the consumer exactly after the
    preceding batches — first, mid-epoch, and last position."""
    pool = ProcPrefetchPool(produce, LAYOUT, depth=2, num_workers=2)
    got = []
    try:
        with pytest.raises(ValueError, match=f"boom at {at}"):
            for item, arrays, meta in pool.run(list(range(n))):
                got.append(item)
        assert got == list(range(at))
    finally:
        pool.close()
    assert _shm_litter() == []


def test_base_exception_relays():
    """KeyboardInterrupt in a worker must not vanish into the pool."""
    pool = ProcPrefetchPool(_fail_base, LAYOUT, depth=1, num_workers=2)
    try:
        it = pool.run(list(range(3)))
        assert next(it)[0] == 0
        with pytest.raises(KeyboardInterrupt):
            next(it)
    finally:
        pool.close()


def test_unpicklable_exception_becomes_worker_failure():
    """An exception that can't cross the process boundary still relays — as
    a WorkerFailure carrying the remote traceback."""
    pool = ProcPrefetchPool(_fail_unpicklable, LAYOUT, depth=2,
                            num_workers=1)
    try:
        with pytest.raises(WorkerFailure, match="cursed") as ei:
            list(pool.run(list(range(4))))
        assert "remote traceback" in str(ei.value)
    finally:
        pool.close()


def test_consumer_death_unblocks_full_ring_producer():
    """Consumer abandons mid-epoch with workers blocked on the full ring:
    close() must stop, join, and unlink within bounded time."""
    w = ProcPrefetchWorker(list(range(10_000)), _produce, LAYOUT, depth=1,
                           num_workers=2)
    item, arrays, meta = next(iter(w))  # consume one, then abandon
    assert item == 0
    t0 = time.monotonic()
    w.close()
    w.close()  # idempotent
    assert time.monotonic() - t0 < 10.0
    assert not w.alive
    assert _shm_litter() == []


def test_run_iterator_close_resyncs_pool():
    """Abandoning one run() mid-epoch and starting another on the SAME pool:
    the drain must resynchronize the ring so the next epoch is clean."""
    pool = ProcPrefetchPool(_produce, LAYOUT, depth=2, num_workers=2)
    try:
        it = pool.run(list(range(6)))
        assert next(it)[0] == 0
        it.close()  # abandon with 5 outstanding
        out = list(pool.run(list(range(4))))
        assert [o[0] for o in out] == list(range(4))
    finally:
        pool.close()
    assert _shm_litter() == []


def test_validation():
    with pytest.raises(ValueError, match="depth"):
        ProcPrefetchPool(_produce, LAYOUT, depth=0)
    with pytest.raises(ValueError, match="num_sample_workers"):
        ProcPrefetchPool(_produce, LAYOUT, num_workers=0)
    with pytest.raises(ValueError, match="cache_items"):
        ProcPrefetchPool(_produce, LAYOUT, cache_items=-1)
    pool = ProcPrefetchPool(_produce, LAYOUT, depth=1, num_workers=1)
    it = pool.run([0])
    with pytest.raises(RuntimeError, match="one run"):
        pool.run([1])
    list(it)
    pool.close()
    with pytest.raises(RuntimeError, match="closed"):
        pool.run([2])


def test_shared_graph_roundtrip_and_worker_jax_hygiene():
    """share_graph -> materialize reproduces the graph read-only; the
    producer import chain (host_batch + proc_prefetch) stays jax-free."""
    import importlib

    from repro.core.graph import sbm_graph
    from repro.core.sampling.proc_prefetch import share_graph

    g = sbm_graph(64, num_blocks=4, p_in=0.1, p_out=0.02, seed=0)
    shared, arena = share_graph(g)
    try:
        g2 = shared.materialize()
        np.testing.assert_array_equal(g2.indptr, g.indptr)
        np.testing.assert_array_equal(g2.indices, g.indices)
        np.testing.assert_array_equal(g2.labels, g.labels)
        np.testing.assert_array_equal(g2.train_mask, g.train_mask)
        assert g2.num_vertices == g.num_vertices
        assert not g2.indices.flags.writeable
        del g2
    finally:
        arena.close()
    assert _shm_litter() == []

    # the import-chain contract, in a pristine interpreter
    code = ("import sys\n"
            "import repro.core.sampling.host_batch\n"
            "import repro.core.sampling.proc_prefetch\n"
            "assert 'jax' not in sys.modules, 'jax leaked'\n"
            "print('JAX_FREE')\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "JAX_FREE" in proc.stdout


def test_workers_never_load_jax_and_are_pinned_to_cpu():
    """A sampling worker must not bring up an accelerator backend: a chip
    belongs to the one process that holds it.  Workers never import jax,
    and run with JAX_PLATFORMS=cpu in case a producer ever does."""
    pool = ProcPrefetchPool(_produce_env, LAYOUT, depth=2, num_workers=2)
    try:
        metas = [o[2] for o in pool.run(list(range(4)))]
    finally:
        pool.close()
    assert [m["jax_loaded"] for m in metas] == [False] * 4
    assert [m["platforms"] for m in metas] == ["cpu"] * 4


def test_no_leaked_shm_warnings_at_interpreter_exit():
    """A full pool lifecycle in a fresh interpreter must exit with clean
    stderr: no resource-tracker 'leaked shared_memory' warnings, no
    KeyErrors from double-unregistration, and an empty /dev/shm."""
    code = """
# produce must live in an importable module: the forkserver/spawn workers
# unpickle it by qualified name (the engine's HostBatchBuilder.produce
# satisfies this by construction)
from test_proc_prefetch import LAYOUT, _produce
from repro.core.sampling.proc_prefetch import ProcPrefetchPool

pool = ProcPrefetchPool(_produce, LAYOUT, depth=2, num_workers=2)
assert [o[0] for o in pool.run(list(range(5)))] == list(range(5))
pool.close()
# second pool reclaimed by GC only — the finalizer must unlink for it
pool2 = ProcPrefetchPool(_produce, LAYOUT, depth=1, num_workers=1)
next(iter(pool2.run(list(range(3)))))
del pool2
print("LIFECYCLE_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=180, env=env)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "LIFECYCLE_OK" in proc.stdout
    assert "leaked shared_memory" not in proc.stderr, proc.stderr
    assert "KeyError" not in proc.stderr, proc.stderr
    assert _shm_litter() == []
