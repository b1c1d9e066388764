"""The LFR-style generator of the skewed-degree configuration: the same
graph for the same seed, symmetric, one component, and the configured
mean degree, maximum degree and mixing."""
import numpy as np
import pytest

from harness import graphs_lfr as L

CONF = {"n": 8192, "blocks": 4, "tau1": 2.0, "mean_degree": 20.0,
        "k_max": 256, "mu": 0.2, "seed": 0}


def test_same_seed_same_graph_other_seed_other_graph():
    n, a, ta = L.lfr_graph(CONF)
    _, b, tb = L.lfr_graph(CONF)
    _, c, _ = L.lfr_graph(dict(CONF, seed=1))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(ta, tb)
    assert len(a[0]) != len(c[0]) or not np.array_equal(a[1], c[1])


def test_symmetric_sorted_simple_and_connected():
    n, (rows, cols, vals), truth = L.lfr_graph(CONF)
    key = rows * n + cols
    assert (np.diff(key) > 0).all()                 # sorted, no repeats
    assert (rows != cols).all() and (vals == 1.0).all()
    back = np.sort(cols * n + rows)
    np.testing.assert_array_equal(back, key)        # both directions
    assert L.n_components(rows, cols, n) == 1
    assert np.bincount(truth).tolist() == [n // 4] * 4


@pytest.mark.parametrize("n,k_max", [(8192, 256), (32768, 1024)])
def test_degrees_and_mixing(n, k_max):
    conf = dict(CONF, n=n, k_max=k_max)
    _, (rows, cols, _), truth = L.lfr_graph(conf)
    deg = np.bincount(rows, minlength=n)
    assert abs(deg.mean() / 20.0 - 1.0) <= 0.03
    assert 0.8 * k_max <= deg.max() <= k_max
    assert deg.min() >= 1
    assert abs(np.mean(truth[rows] != truth[cols]) - 0.2) <= 0.01


def test_power_law_kmin_meets_the_mean():
    k0 = L.power_law_kmin(2.0, 20.0, 1024)
    assert 3.4 < k0 < 3.6
    deg = L.degree_sequence(200_000, 2.0, 20.0, 1024,
                            np.random.default_rng(0))
    assert abs(deg.mean() / 20.0 - 1.0) < 0.03


def test_a_graph_off_its_mean_degree_is_refused():
    with pytest.raises(ValueError, match="mean degree"):
        L.lfr_graph(dict(CONF, n=2048, k_max=1024))
