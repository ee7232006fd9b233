"""The one traffic generator: every mix is a data file it reads.

A mix file (``traffic/<mix>.json``) holds::

    {"mode": "auto" | "full" | "subgraph",   # engine serve path
     "popularity": {"kind": "zipf" | "uniform", "exponent": 0.99,
                    "permutation_seed": 12},
     "updates": null | {"rows": 64, "versions": 4}}

and a cell file (``cells/<cell>.json``) the rates: ``query_rate_per_s``
and, where the mix has updates, ``update_rate_per_s``.

Queries arrive as an open-loop Poisson process. Which node a query asks
for follows the popularity law over a permutation of node ids fixed by the
mix (not by the run's seed), so every seed draws from the same hot set and
the same amount of work, in another order. Feature updates come at fixed
intervals; each changes ``rows`` rows drawn with the same popularity. The
feature versions are full matrices made before the window: matrix k + 1 is
matrix k with the rows of step k redrawn, and the updates walk the chain
up and back down (0, 1, ..., V-1, V-2, ..., 0, 1, ...), so every update
changes exactly ``rows`` rows of the matrix the engine holds.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Schedule:
    """What the open loop offers in one window. Times are seconds from the
    window's start."""
    query_t: np.ndarray          # (Q,) float64, ascending
    query_node: np.ndarray       # (Q,) int64
    update_t: np.ndarray         # (U,) float64, ascending
    update_version: np.ndarray   # (U,) int64 index into the feature versions


class Popularity:
    """Draws node ids from the mix's popularity law."""

    def __init__(self, spec: dict, n_nodes: int):
        self.n = int(n_nodes)
        kind = spec.get("kind", "uniform")
        self.perm = np.random.default_rng(
            int(spec.get("permutation_seed", 0))).permutation(self.n)
        if kind == "zipf":
            w = np.arange(1, self.n + 1, dtype=np.float64) ** -float(
                spec["exponent"])
            self.cdf = np.cumsum(w / w.sum())
        elif kind == "uniform":
            self.cdf = None
        else:
            raise ValueError(f"unknown popularity kind {kind!r}")

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        if self.cdf is None:
            return rng.integers(0, self.n, size=size).astype(np.int64)
        rank = np.searchsorted(self.cdf, rng.random(size), side="right")
        return self.perm[np.minimum(rank, self.n - 1)].astype(np.int64)

    def draw_distinct(self, rng: np.random.Generator,
                      size: int) -> np.ndarray:
        """``size`` distinct node ids, drawn with the popularity law."""
        out: List[int] = []
        seen = set()
        while len(out) < size:
            for v in self.draw(rng, 4 * size):
                if v not in seen:
                    seen.add(int(v))
                    out.append(int(v))
                    if len(out) == size:
                        break
        return np.asarray(out, np.int64)


def version_walk(n_versions: int, n_updates: int, start: int = 0
                 ) -> np.ndarray:
    """Indices the updates move to: up the chain and back down."""
    if n_versions < 2:
        raise ValueError("a feature-update mix needs at least 2 versions")
    period = 2 * (n_versions - 1)
    k = (np.arange(1, n_updates + 1) + start) % period
    return np.where(k < n_versions, k, period - k).astype(np.int64)


def feature_versions(x0: np.ndarray, spec: dict, pop: Popularity,
                     rng: np.random.Generator) -> List[np.ndarray]:
    """The chain of full feature matrices an update mix walks. New rows are
    bag-of-words rows at the matrix's own density."""
    density = float(np.mean(x0 != 0))
    out = [x0]
    for _ in range(int(spec["versions"]) - 1):
        x = out[-1].copy()
        rows = pop.draw_distinct(rng, int(spec["rows"]))
        x[rows] = (rng.random((rows.size, x.shape[1])) < density).astype(
            x.dtype)
        out.append(x)
    return out


def make_schedule(mix: dict, cell: dict, pop: Popularity,
                  rng: np.random.Generator, seconds: float,
                  start_version: int = 0) -> Schedule:
    """Arrivals and updates of one window, from the run's generator."""
    rate = float(cell["query_rate_per_s"])
    # draw a little past the window so the Poisson tail is never cut short
    n_draw = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n_draw))
    t = t[t < seconds]
    nodes = pop.draw(rng, t.size)
    upd: Optional[dict] = mix.get("updates")
    if upd:
        interval = 1.0 / float(cell["update_rate_per_s"])
        # the last update lies one interval before the close, so queries
        # due after it can show it before the window ends
        n_upd = max(int(np.floor(seconds / interval)) - 1, 0)
        ut = interval * np.arange(1, n_upd + 1, dtype=np.float64)
        uv = version_walk(int(upd["versions"]), n_upd, start_version)
    else:
        ut = np.zeros(0)
        uv = np.zeros(0, np.int64)
    return Schedule(query_t=t, query_node=nodes, update_t=ut,
                    update_version=uv)
