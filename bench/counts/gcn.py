"""Operations and compulsory bytes of one full-graph GCN training step.

Shapes: V vertices, E real in-edges, dims = [in, hidden.., classes].  Layer
l aggregates its input (width d_l = dims[l]) and multiplies by a
d_l x d_{l+1} matrix.  Layer 0's input is data, so its aggregation and
product get no input gradient.

FLOPs (multiply and add count one each):
  forward   per layer  2*E*d_l (weighted neighbour sum) + 2*V*d_l*d_{l+1}
  backward  per layer  2*V*d_l*d_{l+1} (weight gradient), and for l > 0
                       2*V*d_l*d_{l+1} (input gradient) + 2*E*d_l
Elementwise terms (bias, ReLU, degree division, softmax) are left out.

Compulsory aggregation bytes, float32 and int32 at 4 bytes: each call
reads its table's distinct source rows once (N_c of them on chip c), writes
or reads its V_c destination rows once, and reads one id and one weight per
real edge (E_c on chip c).  Forward: one gather per layer; backward: one
scatter-add per layer l > 0, with the same bytes.
"""


def flops(V: int, E: int, dims) -> float:
    total = 0.0
    for l, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        total += 2.0 * E * di + 2.0 * V * di * do  # forward
        total += 2.0 * V * di * do  # weight gradient
        if l > 0:
            total += 2.0 * V * di * do + 2.0 * E * di
    return total


def agg_bytes(N: int, V: int, E: int, dims) -> float:
    """Compulsory bytes on one chip with N distinct source rows, V own rows
    and E real in-edges."""
    total = 0.0
    for l, di in enumerate(dims[:-1]):
        call = 4.0 * (N * di + V * di + 2 * E)
        total += call * (2 if l > 0 else 1)
    return total
