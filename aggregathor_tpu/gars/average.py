"""Plain averaging GAR (not Byzantine-tolerant; the f=0 baseline).

Reference: aggregators/average.py:40-60 (``tf.add_n(gradients)/n``).
Coordinate-wise, so in distributed mode this lowers to a plain mean over the
worker axis — exactly a psum/allreduce, the non-robust fast path.
"""

import jax.numpy as jnp

from . import GAR, register


class AverageGAR(GAR):
    coordinate_wise = True

    def aggregate_block(self, block, dist2=None):
        return jnp.mean(block, axis=0)

    def aggregate_leaf(self, leaf):
        return jnp.mean(leaf.astype(jnp.float32), axis=0)


register("average", AverageGAR)
