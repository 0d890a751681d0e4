"""Plain Multi-Krum: average the m = n - f - 2 rows with the smallest scores
(score = sum of the n - f - 2 smallest squared distances to the others)."""

import jax
import jax.numpy as jnp
import numpy as np

from rules._distances import krum_scores, pairwise_sq_distances


def selected(rows, f):
    n = rows.shape[0]
    scores = krum_scores(pairwise_sq_distances(rows), f)
    return np.sort(np.argsort(scores, kind="stable")[: n - f - 2])


def aggregate(rows, f):
    chosen = selected(rows, f)
    weights = np.zeros((rows.shape[0],), np.float32)
    weights[chosen] = 1.0 / len(chosen)
    return jnp.einsum("n,nd->d", jnp.asarray(weights), rows,
                      precision=jax.lax.Precision.HIGHEST)


def least_bytes(n, f, d, width=4):
    """Read every row once for the distances, the m chosen rows once more for
    their mean, write the result."""
    return (n + (n - f - 2) + 1) * d * width


def flops(n, f, d):
    """Differences, squares and sums of n(n-1)/2 pairs, then the mean."""
    return 3 * d * n * (n - 1) // 2 + (n - f - 2) * d
