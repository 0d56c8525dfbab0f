"""Model FLOPs and compulsory aggregation bytes against hand-worked
shapes."""
import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
from manifest import load_module


def test_gcn_counts_by_hand():
    gcn = load_module("counts", "gcn")
    V, E, dims = 10, 30, [4, 3, 2]
    # layer 0 (4->3): fwd 2*30*4 + 2*10*4*3, weight grad 2*10*4*3
    # layer 1 (3->2): fwd 2*30*3 + 2*10*3*2, weight grad 2*10*3*2,
    #                 input grad 2*10*3*2 + 2*30*3
    want = (240 + 240 + 240) + (180 + 120 + 120 + 120 + 180)
    assert gcn.flops(V, E, dims) == want
    # gather: 4*(N*d + V*d + 2E); layer 0 once, layer 1 forward + backward
    N = 9
    want_b = 4 * (9 * 4 + 10 * 4 + 60) + 2 * 4 * (9 * 3 + 10 * 3 + 60)
    assert gcn.agg_bytes(N, V, E, dims) == want_b


def test_sage_counts_by_hand():
    sage = load_module("counts", "sage")
    V, E, dims = 10, 30, [4, 3, 2]
    # layer 0 (4->3): fwd 2*30*4 + 4*10*4*3, weight grads 4*10*4*3
    # layer 1 (3->2): fwd 2*30*3 + 4*10*3*2, weight grads 4*10*3*2,
    #                 input grads 4*10*3*2 + 2*30*3
    want = (240 + 480 + 480) + (180 + 240 + 240 + 240 + 180)
    assert sage.flops(V, E, dims) == want
    gcn = load_module("counts", "gcn")
    assert sage.agg_bytes(9, V, E, dims) == gcn.agg_bytes(9, V, E, dims)


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_gcn_paper_step_is_near_the_issue_estimate(model):
    """gcn-paper's graph: about a TFLOP and 10 GB of compulsory
    aggregation bytes a step, far under the ~200 GB that fetching a row
    per padded slot would count."""
    counts = load_module("counts", model)
    V, E = 2 ** 20, 16 * 2 ** 20
    dims = {"gcn": [128, 256, 256, 172], "sage": [100, 256, 256, 47]}[model]
    f = counts.flops(V, E, dims)
    b = counts.agg_bytes(V, V, E, dims)
    # gcn: fwd 2E(128+256+256) + 2V(128*256 + 256*256 + 256*172) = 0.320
    # TFLOP, weight grads 0.298, input grads of layers 1-2 and their
    # scatters 0.247; sage doubles every product
    if model == "gcn":
        assert 0.86e12 < f < 0.87e12
    else:
        assert 1.2e12 < f < 1.26e12
    assert 1.0e10 < b < 1.04e10
