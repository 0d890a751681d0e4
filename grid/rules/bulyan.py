"""Plain Bulyan of Multi-Krum (reference native/op_bulyan/cpu.cpp): t = n - 2f - 2
rounds, each emitting the mean of the m - k best-scoring rows and then removing
the best one (scores updated through the pruned distances); then per coordinate
the mean of the b = t - 2f values closest to the median of the t selections."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from rules._distances import pairwise_sq_distances

#: columns handled at a time by the coordinate stage, to bound its memory
BLOCK = 1 << 22


def selection_weights(dist, f):
    """(t, n) weights: row k averages the m - k smallest-scoring live workers."""
    n = dist.shape[0]
    m, t = n - f - 2, n - 2 * f - 2
    pruned = np.zeros_like(dist)
    for i in range(n):
        kept = np.argsort(dist[i], kind="stable")[:m]
        pruned[i, kept] = dist[i, kept]
    scores = pruned.sum(axis=1)
    weights = np.zeros((t, n), np.float32)
    for k in range(t):
        order = np.argsort(scores, kind="stable")
        weights[k, order[: m - k]] = 1.0 / (m - k)
        best = order[0]
        scores = scores - pruned[:, best]
        scores[best] = np.inf
    return weights


@functools.partial(jax.jit, static_argnames=("closest",))
def _coordinate_stage(weights, block, closest):
    selections = jnp.dot(weights, block, precision=jax.lax.Precision.HIGHEST)
    t = selections.shape[0]
    median = jnp.sort(selections, axis=0)[t // 2]
    order = jnp.argsort(jnp.abs(selections - median[None, :]), axis=0, stable=True)
    nearest = jnp.take_along_axis(selections, order[:closest], axis=0)
    return jnp.mean(nearest, axis=0)


def aggregate(rows, f):
    n, d = rows.shape
    weights = jnp.asarray(selection_weights(pairwise_sq_distances(rows), f))
    closest = (n - 2 * f - 2) - 2 * f
    parts = [_coordinate_stage(weights, rows[:, start:start + BLOCK], closest)
             for start in range(0, d, BLOCK)]
    return jnp.concatenate(parts)


def least_bytes(n, f, d, width=4):
    """Read every row once for the distances; read the m rows the selections
    draw on once more (means and the coordinate stage fused); write the result."""
    return (n + (n - f - 2) + 1) * d * width


def flops(n, f, d):
    """Pair distances, t selection means over up to m rows, and a t-row sort."""
    t, m = n - 2 * f - 2, n - f - 2
    return 3 * d * n * (n - 1) // 2 + 2 * t * m * d + t * t * d
