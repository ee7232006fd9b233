"""Operations and bytes of one forward of the binary GraphSAINT network
(two GraphConv layers with a self and an aggregated branch each, then a
fully connected layer), from shapes alone, whatever implements it:
2*rows*F_in*F_out per transform, 2*nnz*F per aggregation; bytes of each
layer's input activations (f32), the weights (1 bit plus an f32 scale per
output column), the 0/1 adjacency (int32 index per nonzero, int32 row
pointer per row) and the outputs."""
from __future__ import annotations

from typing import Tuple


def forward(rows: int, nnz: int, f_in: int, hidden: int,
            classes: int) -> Tuple[float, float]:
    """(operations, bytes) of one forward over a graph of ``rows`` nodes
    and ``nnz`` directed edges."""
    def weight(k, n):
        return k * n / 8.0 + n * 4.0

    adj = nnz * 4.0 + (rows + 1) * 4.0
    ops = (2 * 2.0 * rows * f_in * hidden + 2.0 * nnz * hidden    # layer 1
           + 2 * 2.0 * rows * hidden * hidden + 2.0 * nnz * hidden  # layer 2
           + 2.0 * rows * hidden * classes)                       # fc
    byt = (rows * f_in * 4.0 + 2 * weight(f_in, hidden) + adj
           + rows * hidden * 4.0                                  # h1 out
           + rows * hidden * 4.0 + 2 * weight(hidden, hidden) + adj
           + rows * hidden * 4.0                                  # h2 out
           + rows * hidden * 4.0 + weight(hidden, classes)
           + rows * classes * 4.0)                                # logits
    return ops, byt
