"""Plain float32 reference for full-graph GNN training.

It shares nothing with the program: the graph, features and weights come
from the benchmark's own generator, the layers from ``bench/refs/<model>.py``
and the ELL slot table from `graphs.ell`.  Rows are split evenly over the
cell's chips: each chip all-gathers the layer's input table and aggregates
its own rows in blocks (`jax.lax.map` with `jax.checkpoint`, so no
``[rows, K, D]`` gather outlives its block, forward or backward).  The loss
follows the labels: for one class a vertex ([V] int labels) the softmax
cross-entropy, for multi-label data ([V, C] 0/1 labels) the mean over
labels of the sigmoid cross-entropy (the GAT authors'
``masked_sigmoid_cross_entropy``), each averaged over the train vertices.
The optimizer is plain SGD, as the configurations state.  `for_cell`
builds a cell's reference, with the configuration's ``model_args``.

Matrix products go through ``mm``: `mm_highest` is float32 at the
``highest`` matmul precision; `mm_3pass` is the control, the same product
computed as the TPU's ``high`` precision computes it (three bfloat16 passes,
float32 accumulation), spelled out so that it reads the same on any backend.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from graphs import ell
from manifest import load_module

AXIS = "r"
BLOCK_ELEMS = 1 << 27  # gathered floats per block: 512 MiB at float32


def mm_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _bf16(x):
    """x rounded to bfloat16's 8-bit significand, kept in float32:
    `reduce_precision` is an operation XLA keeps, where a pair of converts
    may be folded away under excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _three_pass(a, b):
    def split(x):
        hi = _bf16(x)
        return (hi.astype(jnp.bfloat16),
                _bf16(x - hi).astype(jnp.bfloat16))

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


@jax.custom_vjp
def mm_3pass(a, b):
    return _three_pass(a, b)


def _mm3_fwd(a, b):
    return _three_pass(a, b), (a, b)


def _mm3_bwd(res, ct):
    a, b = res
    return _three_pass(ct, b.T), _three_pass(a.T, ct)


mm_3pass.defvjp(_mm3_fwd, _mm3_bwd)

PRECISIONS = {"highest": mm_highest, "high": mm_3pass}


@dataclasses.dataclass
class Ctx:
    """What a reference layer sees, on one chip's rows (inside shard_map)."""

    ids: jnp.ndarray  # [nblk, R, K] global ids; V is the zero row
    mask: jnp.ndarray  # [nblk, R, K] 1.0 on real slots
    deg: jnp.ndarray  # [rows, 1] max(in-degree, 1)
    mm: callable
    args: dict  # the configuration's model_args

    def table(self, h):
        """All chips' rows of ``h`` plus one zero row at index V."""
        full = jax.lax.all_gather(h, AXIS, axis=0, tiled=True)
        return jnp.concatenate([full, jnp.zeros((1, h.shape[1]), h.dtype)])

    def blocks(self, x):
        return x.reshape(self.ids.shape[:2] + x.shape[1:])

    def rows(self, x):
        return x.reshape((-1,) + x.shape[2:])

    def map_blocks(self, fn, *xs):
        """``fn(ids_b, mask_b, *x_b)`` over row blocks -> rows."""
        out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a),
                          (self.ids, self.mask) + tuple(map(self.blocks, xs)))
        return jax.tree_util.tree_map(self.rows, out)

    def gather_sum(self, tab):
        """sum over real slots of tab[neighbour] for each own row."""
        return self.map_blocks(
            lambda ids, m: (m[..., None] * tab[ids]).sum(1))


def mesh(devices):
    """The reference's 1-D mesh over the cell's chips."""
    return jax.sharding.Mesh(np.array(devices), (AXIS,))


def init_params(model, dims, seed: int, args: dict):
    """The seed's weights, made on the device in one jitted call;
    ``args`` are the configuration's ``model_args``."""
    key = jax.random.key(seed)
    return jax.jit(functools.partial(model.init_params, dims=tuple(dims),
                                     **args))(key)


def for_cell(cell, data, devices, K: int, **kw):
    """The reference of ``cell`` over its graph ``data`` on ``devices``:
    the configuration's model with its ``model_args``.  ``kw`` goes to
    `Reference` (``reverse_slots``)."""
    return Reference(load_module("refs", cell.config["model"]),
                     mesh(devices), data.indptr, data.indices, data.features,
                     data.labels, data.train_mask, K, cell.model_args, **kw)


def _loss_terms(H, y):
    """Each vertex's loss: softmax cross-entropy for int class labels [V],
    the mean over labels of sigmoid cross-entropy for 0/1 labels [V, C]."""
    if y.ndim == 1:
        lse = jax.scipy.special.logsumexp(H, axis=-1)
        return lse - jnp.take_along_axis(H, y[:, None], axis=-1)[:, 0]
    return (jax.nn.softplus(H) - y * H).mean(-1)


class Reference:
    """The reference's full-graph training step, laid out on ``mesh``."""

    def __init__(self, model, mesh, indptr, indices, X, y, train, K: int,
                 args: dict, reverse_slots: bool = False):
        self.model, self.mesh, self.args = model, mesh, args
        V, D = X.shape
        n = mesh.devices.size
        rows = V // n
        if rows * n != V:
            raise ValueError(f"{V} vertices do not split over {n} chips")
        R = 1 << max(BLOCK_ELEMS // (K * max(D, 256)), 1).bit_length() - 1
        while rows % R:
            R //= 2
        ids, mask = ell(indptr, indices, K)
        if reverse_slots:  # the same sums, added in the other order
            ids, mask = ids[:, ::-1].copy(), mask[:, ::-1].copy()
        deg = np.maximum(mask.sum(1, keepdims=True), 1.0)
        row = NamedSharding(mesh, P(AXIS))
        blk = NamedSharding(mesh, P(None, AXIS))
        nblk = rows // R

        def by_block(a):  # [V, K] -> [nblk, n*R, K], chip c's rows together
            a = a.reshape(n, nblk, R, K).transpose(1, 0, 2, 3)
            return a.reshape(nblk, n * R, K)

        self.ids = jax.device_put(by_block(ids), blk)
        self.mask = jax.device_put(by_block(mask), blk)
        self.deg = jax.device_put(deg.astype(np.float32), row)
        self.X = jax.device_put(X, row)
        self.y = jax.device_put(y, row)
        self.w = jax.device_put(train.astype(np.float32), row)
        self._steps = {}

    def _step(self, precision: str):
        if precision in self._steps:
            return self._steps[precision]
        model, mm, args = self.model, PRECISIONS[precision], self.args

        def local(params, ids, mask, deg, X, y, w):
            ctx = Ctx(ids, mask, deg, mm, args)

            def num_fn(p):
                H = X
                L = len(p["layers"])
                for l, p_l in enumerate(p["layers"]):
                    H = model.layer(p_l, H, ctx, last=(l == L - 1))
                return (_loss_terms(H, y) * w).sum(), H

            (num, logits), g = jax.value_and_grad(num_fn, has_aux=True)(
                params)
            den = jnp.maximum(jax.lax.psum(w.sum(), AXIS), 1.0)
            g = jax.tree_util.tree_map(lambda x: jax.lax.psum(x, AXIS) / den,
                                       g)
            return jax.lax.psum(num, AXIS) / den, g, logits

        fn = jax.jit(shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(None, AXIS), P(None, AXIS), P(AXIS), P(AXIS),
                      P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(AXIS)), check_vma=False))
        self._steps[precision] = fn
        return fn

    def train(self, params0, lr: float, steps: int, precision="highest",
              weights=None):
        """``steps`` SGD steps from ``params0``: losses, the first gradient,
        the params after the first and after the last step, and the last
        step's logits, all on the host.  ``weights`` replaces the train
        weights (a planted fault)."""
        fn = self._step(precision)
        w = self.w if weights is None else jax.device_put(
            weights.astype(np.float32), self.w.sharding)
        rep = NamedSharding(self.mesh, P())
        p = jax.device_put(params0, rep)
        losses, g1, p1, logits = [], None, None, None
        for i in range(steps):
            loss, g, logits = fn(p, self.ids, self.mask, self.deg, self.X,
                                 self.y, w)
            losses.append(float(loss))
            p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
            if i == 0:
                g1, p1 = jax.device_get((g, p))
        return dict(losses=losses, grad1=g1, params1=p1,
                    params=jax.device_get(p), logits=np.asarray(logits))
