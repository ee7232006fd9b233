"""The generator: deterministic for a seed, with the Zipf and Poisson
statistics its mix files ask for."""
import numpy as np
import pytest

from chipbench import traffic

ZIPF = {"kind": "zipf", "exponent": 0.99, "permutation_seed": 12}


def _sched(seed, mix=None, cell=None, n=5000, seconds=4.0):
    mix = mix or {"popularity": ZIPF, "updates": {"rows": 8, "versions": 4}}
    cell = cell or {"query_rate_per_s": 3000.0, "update_rate_per_s": 10.0}
    pop = traffic.Popularity(mix["popularity"], n)
    return traffic.make_schedule(mix, cell, pop,
                                 np.random.default_rng(seed), seconds)


def test_same_seed_same_schedule():
    a, b = _sched(2 ** 32 + 5), _sched(2 ** 32 + 5)
    for f in ("query_t", "query_node", "update_t", "update_version"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    c = _sched(6)
    assert not np.array_equal(a.query_node[:100], c.query_node[:100])


def test_poisson_arrivals():
    rate, seconds = 3000.0, 4.0
    s = _sched(1, seconds=seconds)
    n = s.query_t.size
    assert abs(n - rate * seconds) < 5 * np.sqrt(rate * seconds)
    gaps = np.diff(s.query_t)
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05   # exponential: CV 1
    assert s.query_t.max() < seconds


def test_zipf_popularity():
    n = 2000
    pop = traffic.Popularity(ZIPF, n)
    draws = pop.draw(np.random.default_rng(3), 400_000)
    freq = np.bincount(draws, minlength=n)[pop.perm]   # by rank
    w = np.arange(1, n + 1) ** -0.99
    p = w / w.sum()
    for rank in (0, 1, 9, 99):
        expect = p[rank] * draws.size
        assert abs(freq[rank] - expect) < 5 * np.sqrt(expect)
    # the hot set is the mix's, not the run's: same permutation any seed
    assert np.array_equal(traffic.Popularity(ZIPF, n).perm, pop.perm)


def test_uniform_popularity():
    pop = traffic.Popularity({"kind": "uniform"}, 100)
    draws = pop.draw(np.random.default_rng(0), 100_000)
    freq = np.bincount(draws, minlength=100)
    assert freq.min() > 800 and freq.max() < 1200


def test_updates_fixed_interval_and_walk():
    s = _sched(1)
    np.testing.assert_allclose(np.diff(s.update_t), 0.1)
    assert s.update_t[-1] <= 4.0 - 0.1 + 1e-9
    w = traffic.version_walk(4, 12)
    assert list(w) == [1, 2, 3, 2, 1, 0, 1, 2, 3, 2, 1, 0]
    assert np.all(np.abs(np.diff(np.concatenate([[0], w]))) == 1)
    with pytest.raises(ValueError):
        traffic.version_walk(1, 3)


def test_feature_versions_change_rows_each_step():
    rng = np.random.default_rng(0)
    x0 = (rng.random((300, 200)) < 0.05).astype(np.float32)
    pop = traffic.Popularity(ZIPF, 300)
    xs = traffic.feature_versions(x0, {"rows": 8, "versions": 4}, pop,
                                  np.random.default_rng(1))
    assert len(xs) == 4 and xs[0] is x0
    for a, b in zip(xs, xs[1:]):
        changed = np.nonzero((a != b).any(axis=1))[0]
        assert 0 < changed.size <= 8
    # the versions are never written again once made
    assert not any(np.shares_memory(a, b) for a, b in zip(xs, xs[1:]))


def test_no_updates_without_update_mix():
    mix = {"popularity": ZIPF, "updates": None}
    s = _sched(1, mix=mix, cell={"query_rate_per_s": 100.0})
    assert s.update_t.size == 0 and s.query_t.size > 0
