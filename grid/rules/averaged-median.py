"""Plain averaged median (reference aggregators/averaged-median.py), and what
it must cost: per coordinate the median (the value at index n // 2 of the
ascending order, non-finite values last), then the mean of the n - f values
nearest to it, ties to the lower row (a stable order of the rows as they lie;
a non-finite value is infinitely far)."""

import jax.numpy as jnp

#: columns ordered at a time, to bound the sorts' memory at d = 389 M
BLOCK = 1 << 24


def _columns(block, closest):
    n = block.shape[0]
    median = jnp.sort(jnp.where(jnp.isfinite(block), block, jnp.inf), axis=0)[n // 2]
    away = jnp.abs(block - median[None, :])
    order = jnp.argsort(jnp.where(jnp.isfinite(away), away, jnp.inf), axis=0, stable=True)
    return jnp.mean(jnp.take_along_axis(block, order[:closest], axis=0), axis=0)


def aggregate(rows, f):
    n, d = rows.shape
    return jnp.concatenate([_columns(rows[:, lo:lo + BLOCK], n - f)
                            for lo in range(0, d, BLOCK)])


def least_bytes(n, f, d, width=4):
    """Read every row once, write the result."""
    return (n + 1) * d * width


def flops(n, f, d):
    """The compares of two rank selections (the median, then the nearest):
    every pair of a coordinate's n values, twice."""
    return n * (n - 1) * d
