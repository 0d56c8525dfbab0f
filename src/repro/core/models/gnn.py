"""GNN models (GCN, GraphSAGE, GAT, GIN) as pure functions over dense
normalized adjacency blocks (tests / small graphs) — the sparse local
aggregation for large graphs is the ELL SpMM in repro.kernels.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _dense(key, path, fan_in, fan_out):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return jax.random.normal(k, (fan_in, fan_out), jnp.float32) / np.sqrt(fan_in)


def init_gnn_params(model: str, dims: Sequence[int], key) -> Dict:
    """dims = [in, hidden, ..., out]; one layer per consecutive pair."""
    layers = []
    for l, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        p = {}
        if model == "gcn":
            p["w"] = _dense(key, f"l{l}/w", di, do)
            p["b"] = jnp.zeros((do,), jnp.float32)
        elif model == "sage":
            p["w_self"] = _dense(key, f"l{l}/ws", di, do)
            p["w_nbr"] = _dense(key, f"l{l}/wn", di, do)
            p["b"] = jnp.zeros((do,), jnp.float32)
        elif model == "gat":
            p["w"] = _dense(key, f"l{l}/w", di, do)
            p["a_src"] = _dense(key, f"l{l}/as", do, 1)[:, 0]
            p["a_dst"] = _dense(key, f"l{l}/ad", do, 1)[:, 0]
        elif model == "gin":
            p["w1"] = _dense(key, f"l{l}/w1", di, do)
            p["w2"] = _dense(key, f"l{l}/w2", do, do)
            p["eps"] = jnp.zeros(())
        else:
            raise ValueError(model)
        layers.append(p)
    return {"layers": layers}


def gnn_layer(model: str, p: Dict, A: jnp.ndarray, H_src: jnp.ndarray,
              self_idx: Optional[jnp.ndarray] = None, *, last: bool = False,
              aggregate: Callable = None) -> jnp.ndarray:
    """One layer. A [n_dst, n_src] (normalized); H_src [n_src, d_in];
    self_idx maps dst rows into src rows (for self features).  The neighbour
    sum runs under the ``aggregate`` scope and the dense transforms under
    ``combine``; gat's attention is all ``aggregate``."""
    H_self = H_src if self_idx is None else H_src[self_idx]
    if model == "gat":
        with jax.named_scope("aggregate"):
            Hw_src = H_src @ p["w"]
            Hw_dst = H_self @ p["w"]
            e = ((Hw_dst @ p["a_dst"])[:, None]
                 + (Hw_src @ p["a_src"])[None, :])
            e = jax.nn.leaky_relu(e, 0.2)
            mask = A > 0
            e = jnp.where(mask, e, -1e30)
            att = jax.nn.softmax(e, axis=1)
            att = jnp.where(mask, att, 0.0)
            # Rows whose neighbors are ALL masked (isolated vertices, padded
            # rows) fall back to the self-loop Hw_dst instead of silently
            # emitting zeros — the padded-engine contract, and what the
            # distributed ELL GAT path computes for degree-0 rows.
            has_nbr = mask.any(axis=1, keepdims=True)
            z = jnp.where(has_nbr, att @ Hw_src, Hw_dst)
        return z if last else jax.nn.relu(z)
    if model not in ("gcn", "sage", "gin"):
        raise ValueError(model)
    with jax.named_scope("aggregate"):
        nbr = A @ H_src if aggregate is None else aggregate(A, H_src)
    with jax.named_scope("combine"):
        if model == "gcn":
            z = nbr @ p["w"] + p["b"]
        elif model == "sage":
            z = H_self @ p["w_self"] + nbr @ p["w_nbr"] + p["b"]
        else:  # gin
            z = jax.nn.relu(((1 + p["eps"]) * H_self + nbr) @ p["w1"]
                            ) @ p["w2"]
        return z if last else jax.nn.relu(z)


def full_graph_forward(model: str, params: Dict, A: jnp.ndarray, X: jnp.ndarray,
                       aggregate: Callable = None) -> jnp.ndarray:
    H = X
    L = len(params["layers"])
    for l, p in enumerate(params["layers"]):
        H = gnn_layer(model, p, A, H, self_idx=None, last=(l == L - 1),
                      aggregate=aggregate)
    return H


def minibatch_forward(model: str, params: Dict, layer_adj: List[jnp.ndarray],
                      self_indices: List[jnp.ndarray], X: jnp.ndarray) -> jnp.ndarray:
    H = X
    L = len(params["layers"])
    for l, p in enumerate(params["layers"]):
        H = gnn_layer(model, p, layer_adj[l], H, self_idx=self_indices[l],
                      last=(l == L - 1))
    return H


def padded_minibatch_forward(params: Dict, layer_adj: Sequence[jnp.ndarray],
                             X: jnp.ndarray, *, model: str = "gcn",
                             self_idx: Optional[Sequence[jnp.ndarray]] = None
                             ) -> jnp.ndarray:
    """Model-aware forward over statically PADDED dense sampled blocks (the
    DistGNNEngine mini-batch contract), delegating each layer to `gnn_layer`:
    self-loops are folded into the row-normalized blocks, so GCN is
    H <- A_l @ H @ W + b; sage/gin/gat read their RESIDENT self features
    through ``self_idx`` (self_idx[l] maps layer-(l+1) rows into layer-l rows
    — pad rows point at slot 0, inert because no real row ever reads a pad
    row: pad rows/cols of A_l are zero and real self_idx entries point at
    real slots).  Required for every model except gcn."""
    if model != "gcn" and self_idx is None:
        raise ValueError(f"model={model!r} needs self_idx (resident self "
                         "features); only gcn folds self into the blocks")
    H = X
    L = len(params["layers"])
    for l, p in enumerate(params["layers"]):
        si = None if self_idx is None else self_idx[l]
        with jax.named_scope(f"layer{l}"):
            H = gnn_layer(model, p, layer_adj[l], H, self_idx=si,
                          last=(l == L - 1))
    return H


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = lse - ll
    if mask is not None:
        return (loss * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss.mean()


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray,
             mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if mask is not None:
        return (correct * mask).sum() / jnp.maximum(mask.sum(), 1)
    return correct.mean()
