"""Pallas TPU kernels for the paper's hot spots (BSpMM, BMM, bit packing)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Kernels compile natively on a TPU backend and run in Pallas
    interpret mode anywhere else; ``None`` picks by the default backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
