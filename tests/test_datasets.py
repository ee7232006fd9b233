"""The stat-matched generator: its edge dedupe on one int64 key gives what
``np.unique(edges, axis=1)`` gives, so every Table-2 graph, and the
benchmark's Flickr graph above all, stays the same to the bit."""
import hashlib

import numpy as np
import pytest

from repro.graphs import datasets
from repro.graphs.datasets import make_dataset, unique_edges


@pytest.mark.parametrize("n,e,seed", [(1, 5, 0), (7, 60, 1), (300, 5000, 2),
                                      (89_250, 200_000, 3),
                                      (232_965, 300_000, 4)])
def test_unique_edges_is_column_unique(n, e, seed):
    edges = np.random.default_rng(seed).integers(0, n, size=(2, e))
    got = unique_edges(edges, n)
    want = np.unique(edges, axis=1)
    assert got.dtype == want.dtype == np.int64
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name,scale", [("cora", 1.0), ("citeseer", 1.0),
                                        ("pubmed", 1.0), ("flickr", 0.2),
                                        ("reddit", 0.002)])
def test_every_table2_graph_is_unchanged(monkeypatch, name, scale):
    new = make_dataset(name, seed=0, scale=scale)
    monkeypatch.setattr(datasets, "unique_edges",
                        lambda edges, n: np.unique(edges, axis=1))
    old = make_dataset(name, seed=0, scale=scale)
    np.testing.assert_array_equal(new.edges, old.edges)
    np.testing.assert_array_equal(new.x, old.x)
    np.testing.assert_array_equal(new.y, old.y)


def test_benchmark_flickr_graph_is_pinned():
    """The graph of the ``saint-flickr`` cells: its edge count and a
    checksum of its edge list, as the generator has always drawn them."""
    d = make_dataset("flickr", seed=0)
    assert d.edges.shape == (2, 842_900) and d.edges.dtype == np.int64
    assert hashlib.sha256(np.ascontiguousarray(d.edges).tobytes()) \
        .hexdigest() == ("8ff50a37ca216e6e3a9e5a882cf715de"
                         "2b78652f208fa20bc6055bf3b5b875cd")
