"""What one run leaves for the per-layer metric readers.

A reader (``metrics/<name>.py``) gets a :class:`Run` and returns a number
or None; everything it may read is here: the benchmark's own tick spans,
the engine's stage totals over the window, the batches served, the
reduced profiler trace, the family's work count and the chip's peaks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import xplane

# Names XLA gives the programs today: the session's jitted full-graph
# forward (``full``, with BN calibration; ``full_frozen`` without) and the
# serve core's bucketed subgraph forward (``_serve``; ``_serve_many`` for a
# co-launch). The trace's module events are named ``jit_<function>(<id>)``.
FULL_FORWARD = r"^jit_full(_frozen)?(\(|$)"
SERVE_FORWARD = r"^jit__serve(_many)?(\(|$)"


@dataclasses.dataclass
class Run:
    tick_start: np.ndarray                # (T,) perf_counter seconds
    tick_end: np.ndarray
    tick_answered: np.ndarray             # (T,) queries answered
    tick_invalidated: np.ndarray          # (T,) bool: the tick refreshed
    batches: List[np.ndarray]             # seeds of each batch served
    stages: Dict[str, float]              # engine stage totals, window
    n_nodes: int
    indptr: np.ndarray                    # CSR of the graph (receivers)
    indices: np.ndarray
    work: Callable[[int, int], Tuple[float, float]]   # (ops, bytes)
    peaks: dict
    full_cache: bool                      # served from the full-graph cache
    trace: Optional[xplane.Trace] = None

    @property
    def forward_pattern(self) -> str:
        """Name of the forward program this run serves from."""
        return FULL_FORWARD if self.full_cache else SERVE_FORWARD

    # ---------------------------------------------------------- host ----
    def tick_ms(self, refreshed: bool) -> List[float]:
        """Wall times of the ticks that answered queries, split by whether
        they raised the session's invalidation counter."""
        sel = (self.tick_answered > 0) & (self.tick_invalidated == refreshed)
        return list((self.tick_end[sel] - self.tick_start[sel]) * 1e3)

    def per_batch_ms(self, stage: str) -> Optional[float]:
        b = self.stages.get("batches", 0)
        return self.stages[stage] / b * 1e3 if b else None

    @functools.cached_property
    def closures(self) -> List[Tuple[int, int]]:
        """(nodes, directed edges) of the 2-hop closure of each served
        batch, extracted again here from the CSR."""
        out = []
        for seeds in self.batches:
            seen = np.zeros(self.n_nodes, bool)
            frontier = np.unique(seeds)
            seen[frontier] = True
            for _ in range(2):
                nb = np.unique(self._neighbors(frontier))
                frontier = nb[~seen[nb]]
                seen[frontier] = True
            nodes = np.nonzero(seen)[0]
            nnz = int(seen[self._neighbors(nodes)].sum())
            out.append((int(nodes.size), nnz))
        return out

    def _neighbors(self, nodes: np.ndarray) -> np.ndarray:
        lo, hi = self.indptr[nodes], self.indptr[nodes + 1]
        cnt = hi - lo
        if cnt.sum() == 0:
            return np.zeros(0, np.int64)
        offs = np.arange(cnt.sum()) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        return self.indices[np.repeat(lo, cnt) + offs]

    # ---------------------------------------------------------- work ----
    def least_seconds(self, ops: float, byt: float) -> float:
        """The chip's least time for that work: the larger of operations
        over the bf16 peak and bytes over HBM bandwidth."""
        return max(ops / self.peaks["bf16_flops"],
                   byt / self.peaks["hbm_bytes_per_s"])

    def forward_work(self) -> List[Tuple[float, float]]:
        """(operations, bytes) of each forward the traced window ran: the
        whole graph per refresh on the full-cache path, each served
        closure on the subgraph path."""
        if self.full_cache:
            n = len(self.program_seconds(FULL_FORWARD))
            return [self.work(self.n_nodes, int(self.indices.size))] * n
        return [self.work(rows, nnz) for rows, nnz in self.closures]

    # --------------------------------------------------------- trace ----
    def _window(self) -> Optional[Tuple[int, int]]:
        return None if self.trace is None else xplane.window(self.trace)

    def program_seconds(self, pattern: str) -> List[float]:
        w = self._window()
        if w is None:
            return []
        return xplane.program_times(self.trace, pattern, *w)

    def idle_share(self) -> Optional[float]:
        w = self._window()
        if w is None:
            return None
        busy = xplane.busy_seconds(self.trace, *w)
        if busy is None:
            return None
        return 100.0 * (1.0 - busy / ((w[1] - w[0]) * 1e-9))

    def roofline(self, pattern: str, work: List[Tuple[float, float]]
                 ) -> Optional[float]:
        """Least time of ``work`` over the device time of the programs that
        did it, in %. None unless the trace holds as many executions as
        there are pieces of work."""
        dev = self.program_seconds(pattern)
        if not dev or len(dev) != len(work):
            return None
        least = sum(self.least_seconds(o, b) for o, b in work)
        return 100.0 * least / sum(dev)

    def mfu(self, pattern: str, ops_each: List[float]) -> Optional[float]:
        """Model operations of the forwards that ran in the traced window,
        over the window times the bf16 peak, in %."""
        w = self._window()
        dev = self.program_seconds(pattern)
        if w is None or not dev or len(dev) != len(ops_each):
            return None
        return 100.0 * sum(ops_each) / (
            (w[1] - w[0]) * 1e-9 * self.peaks["bf16_flops"])
