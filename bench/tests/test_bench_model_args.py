"""A configuration's ``model_args`` and multi-label data through the
full-graph harness.

(a) The cells as they are get the same engine configuration, the same
    data and the same reference arithmetic as before ``model_args`` and
    ``labels`` existed: golden values recorded from the harness before that
    change, at `bench_support.SMALL`.
(b) ``model_args`` reach the engine, the reference's ``init_params`` and
    layers, and the counts, through `Cell.model_args`; a key the engine
    lacks is a `ManifestError` before the graph is built.
(c) Multi-label data: seeded, at its label rate, trained by the reference
    as a dense oracle trains it, and read by the control.
"""
import dataclasses
import hashlib
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import check
import control
import graphs
import manifest
import reference
import run
from bench_support import SMALL, small_cell  # noqa: F401  (a fixture)
from manifest import Cell, ManifestError, load_manifest, load_module
from repro.core.engine import DistGNNEngine

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOB = load_module("jobs", "full_graph_train")
CELLS = {"gcn-paper.full.c1": "gcn", "sage-ogb.full.c1": "sage"}
SEEDS = (7, 2 ** 31 + 99)

# --- (a) golden values of the harness before model_args and labels ---------

ENGINE = dict(
    execution="p2p", protocol="sync", partition_family="edge_cut",
    partitioner="range", vertex_cut="cartesian2d", hub_threshold=None,
    sorted_masters=False, batching="full_graph", batch_size=16,
    fanouts=(4, 4), layer_sizes=(32, 32), walk_length=4, cache_policy="none",
    cache_capacity=0, exchange_chunks=1, p2p_buckets=1, prefetch_depth=2,
    prefetch_mode="thread", num_sample_workers=2, trainable_features=False,
    embed_lr=0.1, embed_b1=0.9, embed_b2=0.999, embed_eps=1e-08,
    embed_weight_decay=0.0, hidden=256, num_layers=3, lr=0.05, staleness=2,
    eps_v=0.05, hard_bound=4, seed=0, use_pallas=True, interpret=None)

# sha256 of each array's bytes, first 16 hex digits
EDGES = dict(indptr="bc144d52b60e8f85", indices="238fa4b3fd7da211")
DATA = {
    ("gcn-paper.full.c1", SEEDS[0]): dict(
        features="f8358b09ef6cbfea", labels="48681a1ba3506e2e",
        train_mask="0911444563f6b364", val_mask="13bb00528b1b7096",
        test_mask="7c0edd8b311f81af"),
    ("gcn-paper.full.c1", SEEDS[1]): dict(
        features="c70c00225e04891e", labels="5cabbc79faaed9e0",
        train_mask="f9020f26c99462ed", val_mask="867f5d7c62d192a0",
        test_mask="fab4939b91a36353"),
    ("sage-ogb.full.c1", SEEDS[0]): dict(
        features="bc5616c91c96a14f", labels="514b776a8d433304",
        train_mask="a3520cb2a84b62b7", val_mask="ca2ab74468c4e5e3",
        test_mask="18f6c972afd586db"),
    ("sage-ogb.full.c1", SEEDS[1]): dict(
        features="b2faf94857e624a9", labels="e4654ad681f3a46e",
        train_mask="4f45f38518ab3357", val_mask="d69298421f5a9dc4",
        test_mask="442c259b91abf949"),
}

# the reference's losses over the mix's two steps and the first gradient's
# leaf norms (float64 of float32 leaves), float.hex, from seed 7's data and
# weights on one CPU thread
REFERENCE = {
    "gcn-paper.full.c1": dict(
        losses=["0x1.9e0fa20000000p+2", "0x1.c8dfd00000000p+1"],
        grad1={
            "layers.0.b": "0x1.037e49921b487p-1",
            "layers.0.w": "0x1.16e08e927ee2cp+1",
            "layers.1.b": "0x1.f19da9e32ed90p-2",
            "layers.1.w": "0x1.e9f1cbebed149p+2",
            "layers.2.b": "0x1.5823725abae0ap-2",
            "layers.2.w": "0x1.155c7f314c471p+3"}),
    "sage-ogb.full.c1": dict(
        losses=["0x1.1ab6f80000000p+2", "0x1.fd3e5e0000000p+1"],
        grad1={
            "layers.0.b": "0x1.14d7ec1dde00ap-3",
            "layers.0.w_nbr": "0x1.c2e897ca488e7p-3",
            "layers.0.w_self": "0x1.976f9406e9b6ap-1",
            "layers.1.b": "0x1.454de4c6b5bb8p-3",
            "layers.1.w_nbr": "0x1.3bf43ed313a79p+0",
            "layers.1.w_self": "0x1.9b63f9df3482fp+0",
            "layers.2.b": "0x1.4a0b193f0a51bp-3",
            "layers.2.w_nbr": "0x1.74c2211d241e8p+0",
            "layers.2.w_self": "0x1.c522f7389ed15p+0"}),
}


def _sha(a) -> str:
    return hashlib.sha256(memoryview(np.ascontiguousarray(a)).cast("B")
                          ).hexdigest()[:16]


def _small(name):
    cell = Cell(load_manifest(), name)
    cell.config = dict(cell.config, **SMALL)
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_engine_config_is_unchanged(name):
    cell = _small(name)
    ecfg = JOB.engine_config(cell.config, cell.traffic, cell.model_args)
    assert cell.model_args == {}
    assert dataclasses.asdict(ecfg) == dict(ENGINE, model=CELLS[name])


@pytest.mark.parametrize("name,seed", list(DATA))
def test_graph_data_is_unchanged(name, seed):
    d = graphs.build(_small(name).config, seed)
    got = {f.name: _sha(getattr(d, f.name)) for f in dataclasses.fields(d)}
    assert got == dict(EDGES, **DATA[name, seed])
    assert d.labels.dtype == np.int32 and d.labels.shape == (2048,)


@pytest.fixture(scope="module")
def reference_readings(tmp_path_factory):
    """REFERENCE's readings, in a process held to one CPU: the CPU
    backend's matrix products split their sums over the threads it may
    use, so the last bits depend on the host's core count."""
    paths = [BENCH, os.path.join(os.path.dirname(BENCH), "src"),
             os.path.join(BENCH, "tests")]
    code = textwrap.dedent(f"""
        import os, sys, json
        os.sched_setaffinity(0, {{min(os.sched_getaffinity(0))}})
        sys.path[:0] = {paths!r}
        import jax
        import check, graphs, reference
        from bench_support import SMALL
        from manifest import Cell, load_manifest

        out = {{}}
        for name in {list(CELLS)!r}:
            cell = Cell(load_manifest(), name)
            cell.config = cfg = dict(cell.config, **SMALL)
            jax.config.update("jax_default_matmul_precision",
                              cfg["matmul_precision"])
            data = graphs.build(cfg, {SEEDS[0]})
            K = int(data.in_degree().max())
            ref = reference.for_cell(cell, data, jax.devices()[:1], K)
            p0 = jax.device_get(reference.init_params(
                ref.model, cell.dims, {SEEDS[0]}, cell.model_args))
            r = ref.train(p0, cfg["lr"], int(cell.traffic["ref_steps"]))
            out[name] = dict(
                losses=[x.hex() for x in r["losses"]],
                grad1={{k: check._norm(v).hex()
                        for k, v in check.leaves(r["grad1"]).items()}})
        print(json.dumps(out))
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jax")))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_reference_is_bit_for_bit_unchanged(reference_readings, name):
    assert reference_readings[name] == REFERENCE[name]


# --- (b) model_args pass through -------------------------------------------

STUB = """
import importlib.util
import json

_spec = importlib.util.spec_from_file_location("real_{kind}_gcn", {real!r})
_real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_real)


def _log(what, args):
    with open({log!r}, "a") as f:
        f.write(json.dumps([what, args]) + "\\n")
"""
STUB_REF = """
def init_params(key, dims, **args):
    _log("init_params", args)
    return _real.init_params(key, dims)


def layer(p, H, ctx, last):
    _log("layer", ctx.args)
    return _real.layer(p, H, ctx, last)
"""
STUB_COUNTS = """
def flops(V, E, dims, **args):
    _log("flops", args)
    return _real.flops(V, E, dims)


def agg_bytes(N, V, E, dims, **args):
    _log("agg_bytes", args)
    return _real.agg_bytes(N, V, E, dims)
"""
ARGS = {"fanouts": [3, 5, 7], "walk_length": 6}  # unused by full-graph steps


def _run(cell, trace=0):
    return run.main(["--workload", cell.name, "--seed", str(2 ** 31 + 7),
                     "--seconds", "0.01", "--trace", str(trace)],
                    require_tpu=False, cell=cell)


def test_model_args_reach_engine_reference_and_counts(small_cell, tmp_path,
                                                      monkeypatch):
    """A stub gcn reference and counts, found by name in a bench directory
    of their own, log what they are given; the run stays correct."""
    log = tmp_path / "calls.jsonl"
    stubs = tmp_path / "bench"
    for kind, body in (("refs", STUB_REF), ("counts", STUB_COUNTS)):
        (stubs / kind).mkdir(parents=True)
        (stubs / kind / "gcn.py").write_text(STUB.format(
            kind=kind, real=os.path.join(BENCH, kind, "gcn.py"),
            log=str(log)) + body)
    for kind in ("jobs", "metrics"):
        (stubs / kind).symlink_to(os.path.join(BENCH, kind))
    engines = []
    init = DistGNNEngine.__init__

    def recording(self, g, mesh=None, cfg=None, **kw):
        engines.append(cfg)
        init(self, g, mesh=mesh, cfg=cfg, **kw)

    monkeypatch.setattr(DistGNNEngine, "__init__", recording)
    cell = small_cell("gcn-paper.full.c1")
    cell.config = dict(cell.config, model_args=ARGS)
    assert cell.model_args == {"fanouts": (3, 5, 7), "walk_length": 6}
    monkeypatch.setattr(manifest, "BENCH", str(stubs))
    res = _run(cell, trace=1)
    assert res["correct"], res["checks"]
    assert [(e.fanouts, e.walk_length) for e in engines] == [((3, 5, 7), 6)]
    calls = [json.loads(line) for line in log.read_text().splitlines()]
    seen = {}
    for what, args in calls:
        seen.setdefault(what, []).append(args)
    assert set(seen) == {"init_params", "layer", "flops", "agg_bytes"}
    for what, got in seen.items():
        assert all(a == ARGS for a in got), (what, got)
    assert len(seen["layer"]) >= 3  # each layer of the traced step


def test_unknown_engine_key_fails_before_the_graph(small_cell, monkeypatch):
    def no_graph(*a, **kw):
        raise AssertionError("the graph was built")

    monkeypatch.setattr(graphs, "build", no_graph)
    cell = small_cell("gcn-paper.full.c1")
    cell.config = dict(cell.config, model_args={"heads": [2, 2]})
    with pytest.raises(ManifestError, match="heads") as e:
        _run(cell)
    assert "gcn-paper" in str(e.value)


@pytest.mark.parametrize("args", [
    [1, 2], "heads", {"bad key": 1}, {"x": {"a": 1}}, {"x": [[1]]},
    {"x": [{"a": 1}]}])
def test_malformed_model_args_are_refused(args):
    cell = _small("gcn-paper.full.c1")
    cell.config = dict(cell.config, model_args=args)
    with pytest.raises(ManifestError):
        cell.model_args


def test_model_args_may_not_override_the_harness():
    cell = _small("gcn-paper.full.c1")
    cell.config = dict(cell.config, model_args={"hidden": 1024})
    with pytest.raises(ManifestError, match="hidden"):
        JOB.engine_config(cell.config, cell.traffic, cell.model_args)


# --- (c) multi-label data ---------------------------------------------------

MULTI = dict(num_vertices=64, avg_degree=4, feature_dim=16, hidden_dim=32,
             num_classes=6, labels="multi", label_rate=0.25, train_frac=0.5)


@pytest.fixture
def multi_cell(small_cell):
    cell = small_cell("gcn-paper.full.c1")
    cell.config = dict(cell.config, **MULTI)
    return cell


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_multi_label_data_follows_the_seed_and_its_rate(multi_cell, seed):
    cfg = multi_cell.config
    a, b = graphs.build(cfg, seed), graphs.build(cfg, seed)
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(getattr(a, f.name), getattr(b, f.name))
    other = graphs.build(cfg, seed + 1)
    assert not np.array_equal(a.labels, other.labels)
    assert not np.array_equal(a.features, other.features)
    y = a.labels
    assert y.dtype == np.float32 and y.shape == (64, 6)
    assert set(np.unique(y)) <= {0.0, 1.0}
    p, n = cfg["label_rate"], y.size
    assert abs(y.mean() - p) <= 3 * math.sqrt(p * (1 - p) / n)


def test_multi_label_needs_its_rate(multi_cell):
    cfg = dict(multi_cell.config)
    del cfg["label_rate"]
    with pytest.raises(ValueError, match="label_rate"):
        graphs.build(cfg, 1)
    with pytest.raises(ValueError, match="labels"):
        graphs.build(dict(cfg, labels="many"), 1)


def _dense_loss(params, A, X, y, w):
    """GCN over a dense adjacency with the sigmoid cross-entropy written
    out: max(z, 0) - z*y + log(1 + exp(-|z|)), the mean over labels, the
    mean over train vertices."""
    hi = jax.lax.Precision.HIGHEST
    deg = jnp.maximum(A.sum(1, keepdims=True), 1.0)
    H = X
    L = len(params["layers"])
    for l, p in enumerate(params["layers"]):
        nbr = jnp.dot(A, H, precision=hi) / deg
        H = jnp.dot(nbr + H, p["w"], precision=hi) + p["b"]
        if l < L - 1:
            H = jax.nn.relu(H)
    bce = (jnp.maximum(H, 0) - H * y
           + jnp.log1p(jnp.exp(-jnp.abs(H)))).mean(1)
    return (bce * w).sum() / jnp.maximum(w.sum(), 1.0)


def test_multi_label_reference_matches_a_dense_oracle(multi_cell):
    cfg, seed = multi_cell.config, 11
    data = graphs.build(cfg, seed)
    V = data.num_vertices
    K = int(data.in_degree().max())
    ref = reference.for_cell(multi_cell, data, jax.devices()[:1], K)
    p0 = jax.device_get(reference.init_params(ref.model, multi_cell.dims,
                                              seed, multi_cell.model_args))
    r = ref.train(p0, cfg["lr"], 1)
    A = np.zeros((V, V), np.float32)
    rows = np.repeat(np.arange(V), data.in_degree())
    np.add.at(A, (rows, data.indices), 1.0)
    loss, g = jax.value_and_grad(_dense_loss)(
        p0, A, data.features, data.labels,
        data.train_mask.astype(np.float32))
    assert abs(r["losses"][0] - float(loss)) <= 1e-6 * abs(float(loss))
    want, got = check.leaves(g), check.leaves(r["grad1"])
    assert set(want) == set(got)
    for k in want:
        assert np.linalg.norm(got[k] - want[k]) <= 1e-6 * np.linalg.norm(
            want[k]), k


def test_control_reads_a_multi_label_configuration(multi_cell):
    out = control.readings(multi_cell, jax.devices()[:1], 5)
    for fault in ("control", "half_batch"):
        for name, v in out[fault].items():
            assert math.isfinite(v) and v > 0, (fault, name, v)
