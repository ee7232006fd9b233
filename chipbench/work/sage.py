"""Operations and bytes of one forward of the binary GraphSAGE network
(two layers, each a self and an aggregated branch, mean aggregation, no
fully connected layer), from shapes alone, whatever implements it:
2*rows*F_in*F_out per transform, 2*nnz*F per aggregation plus rows*F for
the mean's row scale; bytes of each layer's input activations (f32), the
weights (1 bit plus an f32 scale per output column), the mean adjacency
(int32 index per nonzero, int32 row pointer and f32 scale per row) and
the outputs."""
from __future__ import annotations

from typing import Tuple


def forward(rows: int, nnz: int, f_in: int, hidden: int,
            classes: int) -> Tuple[float, float]:
    """(operations, bytes) of one forward over a graph of ``rows`` nodes
    and ``nnz`` directed edges."""
    def weight(k, n):
        return k * n / 8.0 + n * 4.0

    def aggregation(f):
        return 2.0 * nnz * f + 1.0 * rows * f

    adj = nnz * 4.0 + (rows + 1) * 4.0 + rows * 4.0
    ops = (2 * 2.0 * rows * f_in * hidden + aggregation(hidden)    # layer 1
           + 2 * 2.0 * rows * hidden * classes + aggregation(classes))
    byt = (rows * f_in * 4.0 + 2 * weight(f_in, hidden) + adj
           + rows * hidden * 4.0                                  # h1 out
           + rows * hidden * 4.0 + 2 * weight(hidden, classes) + adj
           + rows * classes * 4.0)                                # logits
    return ops, byt
