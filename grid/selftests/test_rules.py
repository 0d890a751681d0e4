"""The plain rules against a second, slower statement of the same rules."""

import numpy as np

from cell import load_module


def _slow_scores(rows, f):
    n = len(rows)
    dist = np.array([[np.sum((a - b) ** 2) for b in rows] for a in rows])
    return np.array([np.sort(np.delete(dist[i], i))[: n - f - 2].sum() for i in range(n)])


def test_krum_averages_the_best_scored_rows():
    rows = np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32)
    best = np.argsort(_slow_scores(rows.astype(np.float64), 2), kind="stable")[:4]
    got = np.asarray(load_module("rules", "krum").aggregate(rows, 2))
    np.testing.assert_allclose(got, rows[best].mean(axis=0), rtol=1e-5, atol=1e-6)


def test_bulyan_against_a_coordinate_loop():
    rng = np.random.default_rng(1)
    n, f, d = 11, 2, 40
    rows = rng.normal(size=(n, d)).astype(np.float32)
    bulyan = load_module("rules", "bulyan")
    dist = np.array([[np.sum((a - b) ** 2) for b in rows] for a in rows], np.float64)
    np.fill_diagonal(dist, np.inf)
    weights = bulyan.selection_weights(dist, f)
    t, b = n - 2 * f - 2, n - 4 * f - 2
    assert weights.shape == (t, n)
    np.testing.assert_allclose(weights.sum(axis=1), 1.0, rtol=1e-6)
    assert [int((w > 0).sum()) for w in weights] == [n - f - 2 - k for k in range(t)]
    selections = weights.astype(np.float64) @ rows
    want = np.empty(d)
    for x in range(d):
        column = selections[:, x]
        median = np.sort(column)[t // 2]
        want[x] = column[np.argsort(np.abs(column - median), kind="stable")[:b]].mean()
    np.testing.assert_allclose(np.asarray(bulyan.aggregate(rows, f)), want, rtol=1e-5, atol=1e-6)


def test_average():
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    np.testing.assert_allclose(np.asarray(load_module("rules", "average").aggregate(rows, 0)),
                               rows.mean(axis=0))
