"""Coordinate-wise trimmed mean GAR (Yin et al. 2018, "Byzantine-Robust
Distributed Learning: Towards Optimal Statistical Rates").

An extension beyond the reference's rule set (aggregators/ has no trimmed
mean): per coordinate, drop the ``b`` largest and ``b`` smallest values and
average the middle ``n - 2b``.  With ``b = f`` (the default) the estimator
achieves order-optimal statistical rates under up to ``f`` Byzantine
workers.  Non-finite values sort to the *ends* (they are what trimming
exists to remove): each non-finite entry is mapped to +/-inf by sign-of-NaN
irrelevance — we place all of them at the top end, so a column with more
than ``b`` non-finite entries is visibly poisoned (NaN output) rather than
silently wrong, matching the NaN-faithfulness convention of the other
coordinate-wise rules (gars/common.py).
"""

import jax.numpy as jnp

from . import GAR, register
from .common import nonfinite_to_inf, use_pallas_coordinate_tier


def trimmed_mean_columns(block, nb_rows, nb_trim):
    """Per-column mean of the middle ``nb_rows - 2*nb_trim`` sorted values.

    On TPU, large blocks dispatch to the Pallas rank-selection kernel
    (same selected multiset per column; see
    ``common.use_pallas_coordinate_tier``)."""
    keep = nb_rows - 2 * nb_trim
    if block.shape[0] == nb_rows and use_pallas_coordinate_tier(block):
        from ..ops import pallas_kernels as pk

        return pk.coordinate_trimmed_mean(block, nb_trim, keep)
    clean = nonfinite_to_inf(block)
    ordered = jnp.sort(clean, axis=0)[nb_trim:nb_trim + keep]
    # Columns whose kept band still contains inf had > nb_trim poisoned
    # entries: surface NaN (GAR bound void), never a silently-huge mean.
    out = jnp.mean(ordered, axis=0)
    return jnp.where(jnp.isfinite(out), out, jnp.nan)


class TrimmedMeanGAR(GAR):
    coordinate_wise = True
    ARG_DEFAULTS = {"trim": -1}  # -1: trim f from each end

    def __init__(self, nb_workers, nb_byz_workers, args=None):
        super().__init__(nb_workers, nb_byz_workers, args)
        trim = int(self.args["trim"])
        self.nb_trim = self.nb_byz_workers if trim < 0 else trim
        if self.nb_workers - 2 * self.nb_trim < 1:
            from ..utils import UserException

            raise UserException(
                "trimmed-mean needs n - 2*trim >= 1 (got n=%d, trim=%d)"
                % (self.nb_workers, self.nb_trim)
            )

    def aggregate_block(self, block, dist2=None):
        return trimmed_mean_columns(block, self.nb_workers, self.nb_trim)

    def leaf_kernel(self, leaf):
        from ..ops import pallas_kernels as pk

        return pk.coordinate_trimmed_mean_leaf(
            leaf, trim=self.nb_trim, keep=self.nb_workers - 2 * self.nb_trim)


register("trimmed-mean", TrimmedMeanGAR)
