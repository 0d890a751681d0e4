"""Averaged-median GAR: per coordinate, average the beta = n - f values
closest to the (upper) median.

Reference: aggregators/averaged-median.py:40-67 (beta = nbworkers - nbbyzwrks)
backed by deprecated_native/native.cpp:714-747 (nth_element to the median,
then nth_element by |x - median| and average of the first beta).

Non-finite coordinates get +inf deviation so they are only selected when beta
forces it (the reference's comparator leaves NaN ordering unspecified; the
explicit mask makes this tier deterministic).
"""

import jax.numpy as jnp

from . import GAR, register
from .common import nonfinite_to_inf, use_pallas_coordinate_tier


def averaged_median_columns(block, nb_rows, beta):
    """Per-column averaged-median over the first axis: median, then mean of
    the ``beta`` entries closest to it.  Shared with Bulyan's final phase.

    On TPU, large blocks dispatch to the fused Pallas kernel (identical
    selection; the largest measured tier gap — 16 ms vs 3871 ms at d=8.4M,
    see ``use_pallas_coordinate_tier``)."""
    from .median import median_columns

    if block.shape[0] == nb_rows and use_pallas_coordinate_tier(block):
        from ..ops import pallas_kernels as pk

        return pk.coordinate_averaged_median(block, beta)
    median = median_columns(block, nb_rows)
    deviation = nonfinite_to_inf(jnp.abs(block - median[None, :]))
    order = jnp.argsort(deviation, axis=0)[:beta]
    closest = jnp.take_along_axis(block, order, axis=0)
    return jnp.mean(closest, axis=0)


class AveragedMedianGAR(GAR):
    coordinate_wise = True
    # NOT nan_row_tolerant: with more dead rows than the beta = n - f budget
    # covers, inf-deviation rows are force-selected and the mean goes NaN

    def __init__(self, nb_workers, nb_byz_workers, args=None):
        super().__init__(nb_workers, nb_byz_workers, args)
        self.beta = self.nb_workers - self.nb_byz_workers
        if self.beta < 1:
            from ..utils import UserException

            raise UserException("averaged-median needs n - f >= 1 (got n=%d, f=%d)" % (nb_workers, nb_byz_workers))

    def aggregate_block(self, block, dist2=None):
        return averaged_median_columns(block, self.nb_workers, self.beta)

    def leaf_kernel(self, leaf):
        from ..ops import pallas_kernels as pk

        return pk.coordinate_averaged_median_leaf(leaf, beta=self.beta)


register("averaged-median", AveragedMedianGAR)
