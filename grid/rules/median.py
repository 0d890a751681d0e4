"""Plain coordinate-wise median, and what it must cost: per coordinate the
value at index n // 2 of the ascending order (the upper median for even n, as
gars/median.py and the reference's ``nth_element``), non-finite values last."""

import jax.numpy as jnp

#: columns sorted at a time, to bound the sort's memory at d = 305 M
BLOCK = 1 << 24


def aggregate(rows, f):
    del f
    n, d = rows.shape
    parts = [jnp.sort(jnp.where(jnp.isfinite(rows[:, lo:lo + BLOCK]), rows[:, lo:lo + BLOCK],
                                jnp.inf), axis=0)[n // 2]
             for lo in range(0, d, BLOCK)]
    return jnp.concatenate(parts)


def least_bytes(n, f, d, width=4):
    """Read every row once, write the result."""
    return (n + 1) * d * width


def flops(n, f, d):
    """The compares of a rank selection: every pair of a coordinate's n values."""
    return n * (n - 1) // 2 * d
