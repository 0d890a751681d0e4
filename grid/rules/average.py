"""Plain mean of the rows, and what it must cost."""

import jax.numpy as jnp


def aggregate(rows, f):
    del f
    return jnp.mean(rows, axis=0)


def least_bytes(n, f, d, width=4):
    """Read every row once, write the result."""
    return (n + 1) * d * width


def flops(n, f, d):
    return n * d
