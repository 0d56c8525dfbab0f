"""Operations and compulsory bytes of one full-graph GraphSAGE training
step.

Shapes: V vertices, E real in-edges, dims = [in, hidden.., classes].  Layer
l aggregates its input (width d_l = dims[l]) and multiplies the mean and
the vertex's own row each by a d_l x d_{l+1} matrix.  Layer 0's input is
data, so its aggregation and products get no input gradient.

FLOPs (multiply and add count one each):
  forward   per layer  2*E*d_l (neighbour sum) + 4*V*d_l*d_{l+1}
  backward  per layer  4*V*d_l*d_{l+1} (weight gradients), and for l > 0
                       4*V*d_l*d_{l+1} (input gradients) + 2*E*d_l
Elementwise terms (bias, ReLU, degree division, softmax) are left out.

Compulsory aggregation bytes are GCN's: one gather per layer forward and
one scatter-add per layer l > 0 backward, each reading its table's N_c
distinct source rows once, its V_c destination rows once and one id and
one weight per real edge.
"""
from manifest import load_module


def flops(V: int, E: int, dims) -> float:
    total = 0.0
    for l, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        total += 2.0 * E * di + 4.0 * V * di * do  # forward
        total += 4.0 * V * di * do  # weight gradients
        if l > 0:
            total += 4.0 * V * di * do + 2.0 * E * di
    return total


agg_bytes = load_module("counts", "gcn").agg_bytes
