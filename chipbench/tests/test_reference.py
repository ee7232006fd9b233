"""The plain references against the program's own binarized forwards
(``models/gnn.py``) on a small graph, on the CPU."""
import jax
import numpy as np
import pytest

from chipbench import correctness
from chipbench.reference import saint as ref_saint
from repro.graphs.datasets import make_dataset
from repro.models import gnn


@pytest.fixture(scope="module")
def graph():
    return make_dataset("flickr", seed=0, scale=0.02)


def _ref(mod, params, d, dtype="float32"):
    return correctness.reference_logits(
        mod.forward, [np.asarray(p) for p in params], {0: d.x}, d.edges,
        dtype=dtype)[0]


@pytest.mark.parametrize("seed,hidden", [(0, 64), (1, 64), (0, 256),
                                         (1, 256)])
def test_saint_reference_matches(graph, seed, hidden):
    d = graph
    p = gnn.init_saint(jax.random.PRNGKey(seed), d.x.shape[1], hidden, 7)
    prog = np.asarray(gnn.saint_forward_bitgnn(
        gnn.quantize_saint(p), d.x, d.adjacency("binary")))
    ref = _ref(ref_saint, p, d)
    err = correctness.row_errors(prog, ref)
    assert err.max() < 1e-4, err.max()


@pytest.mark.parametrize("hidden", [64, 256])
def test_bfloat16_control_departs(graph, hidden):
    d = graph
    p = gnn.init_saint(jax.random.PRNGKey(0), d.x.shape[1], hidden, 7)
    hi, lo = _ref(ref_saint, p, d), _ref(ref_saint, p, d, "bfloat16")
    assert np.median(correctness.row_errors(lo, hi)) > 1e-3


def test_saint_fragile_rule(graph):
    d = graph
    p = [np.asarray(w) for w in
         gnn.init_saint(jax.random.PRNGKey(0), d.x.shape[1], 64, 7)]
    masks = {eps: correctness.reference_fragile(
        _Eps(eps), p, {0: d.x}, d.edges)[0] for eps in (0.0, 1e-5, 1e9)}
    assert not masks[0.0].any()
    assert masks[1e9].all()
    assert masks[1e-5].sum() <= masks[1e9].sum()
    # a family without the rule calls nothing fragile
    none = correctness.reference_fragile(_NoRule(), p, {0: d.x},
                                         d.edges)[0]
    assert not none.any()


class _NoRule:
    """A family reference that names no fragile nodes."""


class _Eps:
    """The SAINT reference with its fragility threshold fixed."""

    def __init__(self, eps):
        self.eps = eps

    def fragile(self, params, x, edges, n, _eps):
        return ref_saint.fragile(params, x, edges, n, self.eps)


def test_fragile_answers_leave_max_only():
    ref = np.array([[1.0, 0.0], [2.0, 0.0], [1.0, 1.0]])
    served = ref.copy()
    served[1, 0] = 5.0                       # off by 3 x the median scale
    nums = correctness.numbers(served, ref, 0, 0,
                               np.array([False, True, False]))
    assert nums["max_err"] == 0.0 and nums["max_err_all"] == 3.0
    assert nums["fragile_share"] == pytest.approx(1 / 3)
    nums = correctness.numbers(served, ref, 0, 0, None)
    assert nums["max_err"] == 3.0
