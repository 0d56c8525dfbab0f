"""The comparison that decides ``correct`` for a training cell.

The program's run and the reference's run both start from the seed's
weights ``p0`` and take the same SGD steps.  Numbers (each a relative gap;
0 is perfect agreement):

  loss_gap    largest |L_prog - L_ref| / |L_ref| over the compared steps
  grad_gap    worst leaf of | ||g_prog|| - ||g_ref|| |, where g_prog is the
              first gradient as the optimizer took it, (p0 - p1) / lr
  update_gap  worst leaf of | ||p_S - p0||_prog - ||p_S - p0||_ref |
  logits_gap  ||Z_prog - Z_ref|| / ||Z_ref|| over the last compared step's
              logits (every real vertex)

A leaf's gap is measured against the larger of its own reference norm and
the median leaf's.  Leaves whose reference gradient is under a thousandth
of the median leaf's move by round-off alone and are left out of
grad_gap and update_gap.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def leaves(tree, prefix=""):
    """{"layers.0.w": array, ...} of a params tree (host arrays)."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], f"{prefix}{k}."))
        return out
    if isinstance(tree, (list, tuple)):
        out = {}
        for i, v in enumerate(tree):
            out.update(leaves(v, f"{prefix}{i}."))
        return out
    return {prefix[:-1]: np.asarray(tree, np.float64)}


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


def _worst_leaf(prog: dict, ref: dict, kept) -> float:
    ref_n = {k: _norm(ref[k]) for k in kept}
    med = float(np.median(list(ref_n.values())))
    return max(abs(_norm(prog[k]) - ref_n[k]) / max(ref_n[k], med, 1e-30)
               for k in kept)


def compare(p0, prog: dict, ref: dict, lr: float) -> dict:
    """Gaps between the program's run and the reference's.

    ``prog``: losses [S], params after step 1 and after step S, logits of
    step S.  ``ref``: losses [S], first gradient, params after step S,
    logits of step S."""
    p0 = leaves(p0)
    g_ref = leaves(ref["grad1"])
    g_prog = {k: (p0[k] - v) / lr for k, v in leaves(prog["params1"]).items()}
    gn = {k: _norm(v) for k, v in g_ref.items()}
    med = float(np.median(list(gn.values())))
    kept = [k for k in g_ref if gn[k] >= NEGLIGIBLE * med]
    d_prog = {k: v - p0[k] for k, v in leaves(prog["params"]).items()}
    d_ref = {k: v - p0[k] for k, v in leaves(ref["params"]).items()}
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf
    zp = np.asarray(prog["logits"], np.float64)
    zr = np.asarray(ref["logits"], np.float64)
    logits_gap = _norm(zp - zr) / max(_norm(zr), 1e-30)
    return dict(loss_gap=loss_gap, grad_gap=_worst_leaf(g_prog, g_ref, kept),
                update_gap=_worst_leaf(d_prog, d_ref, kept),
                logits_gap=logits_gap)


def leaf_norms(p0, prog: dict, ref: dict, lr: float) -> dict:
    """{leaf: [||g1|| program, reference, ||p_S - p0|| program, reference]},
    the norms behind grad_gap and update_gap, for the run's log."""
    p0 = leaves(p0)
    g_ref = leaves(ref["grad1"])
    g_prog = {k: (p0[k] - v) / lr for k, v in leaves(prog["params1"]).items()}
    d_prog = {k: v - p0[k] for k, v in leaves(prog["params"]).items()}
    d_ref = {k: v - p0[k] for k, v in leaves(ref["params"]).items()}
    return {k: [_norm(g_prog[k]), _norm(g_ref[k]), _norm(d_prog[k]),
                _norm(d_ref[k])] for k in g_ref}


def judge(gaps: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number with a limit; a number
    that is NaN counts as over its limit."""
    out = {}
    for name, limit in limits.items():
        v = float(gaps[name])
        out[name] = dict(value=v, limit=float(limit),
                         ok=bool(v <= limit))  # NaN compares False
    return out
