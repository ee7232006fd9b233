"""The comparison that decides ``correct``.

Every answer the engine gave (node, the feature version it answered at,
its logits row) is held against the plain reference
(``reference/<family>.py``), run after the window over the whole graph at
that version, in float32 at the highest matmul precision. The reference
takes the seeded float parameters and the COO edge list, never anything
the program built.

Numbers, each with the limit in the cell's file:

* ``median_err``: median over the answers of the row error
  max_j |served_j - ref_j| / S, with S the median over the same answers
  of the reference row's largest |logit| (one scale for the whole run, so
  a row of small logits does not blow its error up);
* ``max_err``: the largest row error over the answers the reference
  calls robust: a family's reference may name, by a rule on its own
  float32 values, the nodes whose logits can move under rounding alone
  (``fragile``: a value the network binarises lies within
  ``FRAGILE_EPS`` of zero, so a batch-norm statistic reduced in another
  order can flip its sign and move the logits by a binary step); their
  answers count in ``median_err`` and are left out of ``max_err``;
* ``missing``: queries the engine owed an answer that never came (in a
  cell with updates every query due in the window, after a drain; else
  every query of a batch the engine served) and queries that failed;
* ``unshown``: feature updates applied in the window that no answer ever
  reflected (none came at their version or a later one).

The share of answers whose class differs from the reference's is printed
beside them and not compared.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Tuple

import numpy as np

COMPARED = ("median_err", "max_err", "missing", "unshown")
# standardised units: float32 reduction-order differences in a batch-norm
# statistic move a normalised value by about 1e-7..1e-6
FRAGILE_EPS = 1e-5


def reference_logits(forward: Callable, params, xs: Dict[int, np.ndarray],
                     edges: np.ndarray, dtype: str = "float32"
                     ) -> Dict[int, np.ndarray]:
    """Logits of every node for each feature matrix in ``xs`` (keyed by
    version index). ``dtype="bfloat16"`` gives the control: the same
    reference one precision step down."""
    import jax.numpy as jnp
    return _per_version(forward, params, xs, edges, jnp.dtype(dtype),
                        lambda out: out.astype(jnp.float32))


def reference_fragile(module, params, xs: Dict[int, np.ndarray],
                      edges: np.ndarray) -> Dict[int, np.ndarray]:
    """Per version, the nodes the family's reference calls fragile; none
    where the reference names no such rule."""
    import jax.numpy as jnp
    if not hasattr(module, "fragile"):
        return {k: np.zeros(x.shape[0], bool) for k, x in xs.items()}

    def fn(p, x, e, n):
        return module.fragile(p, x, e, n, FRAGILE_EPS)
    return _per_version(fn, params, xs, edges, jnp.dtype("float32"),
                        lambda out: out)


def _per_version(fn: Callable, params, xs, edges, dt, post):
    import jax
    import jax.numpy as jnp

    n = int(next(iter(xs.values())).shape[0])
    e = (jnp.asarray(edges[0]), jnp.asarray(edges[1]))
    p = tuple(jnp.asarray(w, dt) for w in params)
    jfn = jax.jit(fn, static_argnums=3)
    out = {}
    with jax.default_matmul_precision("highest"):
        for k, x in xs.items():
            out[k] = np.asarray(post(jfn(p, jnp.asarray(x, dt), e, n)))
    return out


def row_errors(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = max(float(np.median(np.abs(ref).max(axis=1))),
                float(np.finfo(np.float32).tiny))
    return np.abs(served - ref).max(axis=1) / scale


def numbers(served: np.ndarray, ref: np.ndarray, missing: int,
            unshown: int = 0, fragile=None) -> Dict[str, float]:
    """The compared numbers, and beside them the class disagreement and
    the share of answers left out of ``max_err`` as fragile."""
    fragile = (np.zeros(served.shape[0], bool) if fragile is None
               else np.asarray(fragile, bool))
    if served.shape[0] == 0:
        err = np.array([np.inf])
        robust = err
        disagree = float("nan")
    else:
        err = row_errors(served, ref)
        robust = err[~fragile] if (~fragile).any() else np.array([np.inf])
        disagree = float(np.mean(served.argmax(1) != ref.argmax(1)))
    return {"median_err": float(np.median(err)),
            "max_err": float(np.max(robust)),
            "missing": int(missing),
            "unshown": int(unshown),
            "answers": int(served.shape[0]),
            "fragile_share": float(fragile.mean()) if fragile.size else 0.0,
            "max_err_all": float(np.max(err)),
            "class_disagree": disagree}


def gather(answers: Iterable[Tuple[int, int, np.ndarray]],
           refs: Dict[int, np.ndarray], fragile: Dict[int, np.ndarray]
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(served, reference, fragile) rows of ``answers`` = (node, version
    index, logits) triples."""
    served: List[np.ndarray] = []
    ref: List[np.ndarray] = []
    frag: List[bool] = []
    for node, ver, logits in answers:
        served.append(np.asarray(logits, np.float32))
        ref.append(refs[ver][node])
        frag.append(bool(fragile[ver][node]))
    if not served:
        z = np.zeros((0, 1), np.float32)
        return z, z, np.zeros(0, bool)
    return np.stack(served), np.stack(ref), np.asarray(frag)


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and each compared number beside its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in COMPARED
              if k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
