"""The benchmark's graph generator against the program's `er_graph`."""
import numpy as np
import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import graphs
from repro.core.graph import er_graph


@pytest.mark.parametrize("V,deg,seed", [(2048, 16, 0), (1000, 8, 7)])
def test_edges_match_er_graph(V, deg, seed):
    g = er_graph(V, avg_degree=deg, feature_dim=4, num_classes=3, seed=seed)
    indptr, indices = graphs.er_edges(V, deg, seed)
    np.testing.assert_array_equal(indptr, g.indptr)
    np.testing.assert_array_equal(indices, g.indices)
    d, dg = np.diff(indptr), g.degree()
    assert (d.max(), d.mean(), d.min()) == (dg.max(), dg.mean(), dg.min())


def test_vertex_data_follows_the_seed_and_er_graph_statistics():
    a = graphs.vertex_data(4096, 32, 8, 0.3, seed=5)
    b = graphs.vertex_data(4096, 32, 8, 0.3, seed=5)
    c = graphs.vertex_data(4096, 32, 8, 0.3, seed=6)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    feats, labels, train, val, test = a
    assert feats.dtype == np.float32 and labels.dtype == np.int32
    assert 0.27 < train.mean() < 0.33 and 0.08 < val.mean() < 0.12
    assert not np.any(train & val) and np.all(train | val | test)
    # class centres plus 0.5-scaled noise, as er_graph draws them
    g = er_graph(4096, avg_degree=4, feature_dim=32, num_classes=8, seed=5)
    for f, y in ((feats, labels), (g.features, g.labels)):
        noise = np.concatenate([f[y == c] - f[y == c].mean(0)
                                for c in range(8)])
        assert abs(noise.std() - 0.5) < 0.01


def test_large_seed_is_accepted():
    f1 = graphs.vertex_data(64, 4, 2, 0.3, seed=2 ** 31 + 17)[0]
    f2 = graphs.vertex_data(64, 4, 2, 0.3, seed=2 ** 33 + 17)[0]
    assert not np.array_equal(f1, f2)


def test_ell_holds_every_edge_once():
    indptr, indices = graphs.er_edges(512, 6, 3)
    K = int(np.diff(indptr).max())
    ids, mask = graphs.ell(indptr, indices, K)
    for v in (0, 17, 511):
        real = ids[v][mask[v] > 0]
        np.testing.assert_array_equal(real, indices[indptr[v]:indptr[v + 1]])
        assert np.all(ids[v][mask[v] == 0] == 512)
    with pytest.raises(ValueError):
        graphs.ell(indptr, indices, K - 1)
