"""The benchmark's seeded graph generator.

The edges follow `repro.core.graph.er_graph`'s arithmetic draw for draw
(uniform sources and destinations, self-loops dropped, CSR of in-neighbours
by a stable sort on the destination), so for one seed the CSR is the same.
The vertex data (labels, features, masks) is drawn from a second seed, with
float32 normals drawn directly and `bincount` in place of `np.add.at`; a
multi-label configuration (``"labels": "multi"``) draws 0/1 labels over
every class in place of one class a vertex.
Edges are fixed per configuration: the ELL width and the halo caps, and so
every compiled program, stay the same from run seed to run seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class GraphData:
    """CSR of in-neighbours plus the per-vertex data of one run."""

    indptr: np.ndarray  # [V+1] int64
    indices: np.ndarray  # [E] int32, the in-neighbours of each vertex
    features: np.ndarray  # [V, D] float32
    labels: np.ndarray  # [V] int32, or [V, C] float32 0/1 (multi-label)
    train_mask: np.ndarray  # [V] bool
    val_mask: np.ndarray
    test_mask: np.ndarray

    @property
    def num_vertices(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.indices.shape[0])

    def in_degree(self) -> np.ndarray:
        return np.diff(self.indptr)


def er_edges(num_vertices: int, avg_degree: int, seed: int):
    """(indptr, indices): `er_graph`'s edge draw from ``seed``."""
    rng = np.random.default_rng(seed)
    E = num_vertices * avg_degree
    src = rng.integers(0, num_vertices, E)
    dst = rng.integers(0, num_vertices, E)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    order = np.argsort(dst, kind="stable")
    indptr = np.zeros(num_vertices + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(dst, minlength=num_vertices))
    return indptr, src[order].astype(np.int32)


def vertex_data(num_vertices: int, feature_dim: int, num_classes: int,
                train_frac: float, seed: int, label_rate=None):
    """(features, labels, train, val, test): `er_graph`'s vertex data —
    features are class centres plus 0.5-scaled noise, so a GNN can learn.

    With ``label_rate`` the labels are [V, C] float32 0/1, each positive
    with that probability, and a vertex's centre is the mean of its
    positive labels' centres (none: the noise alone)."""
    rng = np.random.default_rng(seed)
    if label_rate is None:
        labels = rng.integers(0, num_classes, num_vertices).astype(np.int32)
    else:
        labels = (rng.random((num_vertices, num_classes)) < label_rate
                  ).astype(np.float32)
    centers = rng.standard_normal((num_classes, feature_dim), np.float32)
    feats = rng.standard_normal((num_vertices, feature_dim), np.float32)
    feats *= 0.5
    if label_rate is None:
        feats += centers[labels]
    else:  # float64 sums, so the rounding is the same on every host
        positives = np.maximum(labels.sum(1, keepdims=True), 1.0)
        feats += ((labels.astype(np.float64) @ centers) / positives
                  ).astype(np.float32)
    u = rng.random(num_vertices)
    train = u < train_frac
    val = (u >= train_frac) & (u < train_frac + 0.1)
    return feats, labels, train, val, u >= train_frac + 0.1


def build(cfg: dict, seed: int) -> GraphData:
    """The configuration's graph (edges from ``cfg["graph_seed"]``) with the
    vertex data of run seed ``seed``."""
    if cfg["graph"] != "er":
        raise ValueError(f"unknown graph generator {cfg['graph']!r}")
    kind = cfg.get("labels", "single")
    if kind not in ("single", "multi"):
        raise ValueError(f"labels must be single or multi, not {kind!r}")
    if kind == "multi" and "label_rate" not in cfg:
        raise ValueError("a multi-label configuration gives label_rate")
    V = int(cfg["num_vertices"])
    indptr, indices = er_edges(V, int(cfg["avg_degree"]),
                               int(cfg["graph_seed"]))
    feats, labels, train, val, test = vertex_data(
        V, int(cfg["feature_dim"]), int(cfg["num_classes"]),
        float(cfg["train_frac"]), seed,
        float(cfg["label_rate"]) if kind == "multi" else None)
    return GraphData(indptr, indices, feats, labels, train, val, test)


def ell(indptr: np.ndarray, indices: np.ndarray, width: int):
    """ELL form of a CSR: ids [V, width] int32 (pads point at row V) and a
    float32 mask of the real slots; duplicate edges keep a slot each."""
    V = indptr.shape[0] - 1
    deg = np.diff(indptr)
    if deg.max(initial=0) > width:
        raise ValueError(f"in-degree {deg.max()} exceeds ELL width {width}")
    row = np.repeat(np.arange(V), deg)
    slot = np.arange(indices.shape[0]) - indptr[row]
    ids = np.full((V, width), V, np.int32)
    ids[row, slot] = indices
    mask = np.zeros((V, width), np.float32)
    mask[row, slot] = 1.0
    return ids, mask
