"""All-pairs squared distances and Multi-Krum scores, the plain way."""

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _row_distances(rows, i):
    delta = rows - jax.lax.dynamic_index_in_dim(rows, i, 0, keepdims=True)
    return jnp.sum(delta * delta, axis=1)


def pairwise_sq_distances(rows):
    """(n, n) float64 on the host; one fused pass over the rows per worker,
    by differences (nothing cancels), accumulated in float32 on the device."""
    n = rows.shape[0]
    dist = np.stack([np.asarray(_row_distances(rows, i), np.float64) for i in range(n)])
    dist = 0.5 * (dist + dist.T)
    np.fill_diagonal(dist, np.inf)
    return np.where(np.isfinite(dist) | np.eye(n, dtype=bool), dist, np.inf)


def krum_scores(dist, f):
    """score(i) = sum of worker i's n - f - 2 smallest distances to others."""
    n = dist.shape[0]
    return np.sort(dist, axis=1)[:, : n - f - 2].sum(axis=1)
