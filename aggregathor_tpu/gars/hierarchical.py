"""Hierarchical (tree-reduction) meta-GAR — the large-n fast path.

Motivated by efficient meta-aggregation (arXiv:2405.14759) and
tree-structured reduction (CodedReduce, arXiv:1902.01981): the flagship
rules (Krum, Bulyan) are O(n²·d) on the stacked (n, d) matrix, which is the
cost wall that keeps n small.  ``hier`` composes two registered rules into a
two-level tree::

    hier:g=16,inner=median,outer=krum

    groups   = reshape the n workers into n/g contiguous groups of g
    summary  = inner(group)   per group   — one cheap O(g·d) pass, vmapped
    output   = outer(summaries)           — the expensive rule over n/g rows

so the n²·d term shrinks to (n/g)²·d plus an O(n·d) group pass.  With g
grown ~n/const the outer matrix stays constant-sized and total work is
linear in n — sublinear in n² (``gars/scaling.run_sweep`` measures exactly
this claim).

**Byzantine bookkeeping.**  Groups are a *partition*: f Byzantine workers
can corrupt at most f group summaries (each worker sits in exactly one
group), so the outer rule runs over ``n/g`` rows with the SAME declared
``f`` — its (n/g, f) feasibility is validated here at parse time, exactly
like :class:`~aggregathor_tpu.gars.bucketing.BucketingGAR` validates its
inner rule.  The inner rule is best-effort damage control *within* a group
(a group with a Byzantine minority may still emit an honest-cloud summary);
it is instantiated with ``inner_f = min(f, g - 1)`` by default
(``inner_f=K`` overrides) and its own feasibility check also runs at parse
time.  The f-breakdown property is carried by the OUTER level: even if
every contaminated group's summary is fully adversarial, at most f of the
n/g outer rows are Byzantine — the bound the outer rule is sized for.

**TPU mapping.**  The inner pass is the (n/g, g, d_block) reshape vmapped
over groups — pure jnp tier: the vmapped-Pallas suspension in
``gars/common.py`` (``_is_batched_tracer``) detects the batching trace
centrally, so no Pallas kernel is reached under the group vmap until its
silicon proof lands.  Inner distance matrices (when the inner rule needs
them) are per-group (g, g) centered Grams completed with one psum across
dimension blocks under ``uses_axis``; the outer distances are one
(n/g, n/g) centered Gram, same discipline as ``bucketing.py``.

**NaN rows (lossy link).**  A dead worker's NaN row is absorbed at the
first level that cleanly excludes it: a NaN-tolerant inner drops it from
the group summary; a non-tolerant inner (e.g. ``average``) lets it poison
the summary, and a NaN-tolerant outer then excludes that group row — so
``nan_row_tolerant`` holds whenever either level's rule declares it.

**Nesting.**  ``hier`` composes with ``bucketing`` in both directions
(``bucketing:inner=hier(g=8,outer=krum)`` or ``hier:outer=bucketing(...)``)
— nested specs use the parenthesized form so their commas stay attached
(gars/__init__.py ``parse_spec``).  Randomized nested rules re-draw every
step: per-group inner keys derive from fold_in(key, 1) + the group index,
the outer key from fold_in(key, 2) — all disjoint, all replicated.

**Participation.**  Worker i's weight factorizes through the tree:
``outer_participation[group(i)] * inner_participation_within_group(i)``
(uniform 1/g when the inner rule defines none).  Each group's inner
weights sum to 1 and the outer weights sum to 1, so the scattered (n,)
vector sums to 1 — the convention the suspicion diagnostics rely on.
"""

import jax
import jax.numpy as jnp

from . import GAR, instantiate, register
from .common import centered_gram_sq_distances


class HierarchicalGAR(GAR):
    coordinate_wise = False
    needs_distances = False  # distances (if any) are per level, computed here
    uses_axis = True
    uses_key = True
    #: optional ``secure.masking.GroupMasking`` (requires ``inner=average``,
    #: validated by ``secure.masking.enable_masking``): group summaries are
    #: computed in the exact masked integer domain
    masking = None
    ARG_DEFAULTS = {"g": 4, "inner": "median", "outer": "krum", "inner_f": -1}

    def __init__(self, nb_workers, nb_byz_workers, args=None):
        super().__init__(nb_workers, nb_byz_workers, args)
        from ..utils import UserException

        self.g = int(self.args["g"])
        if self.g < 1 or self.nb_workers % self.g != 0:
            raise UserException(
                "hier needs a group size g >= 1 dividing n (got n=%d, g=%r)"
                % (self.nb_workers, self.args["g"])
            )
        self.nb_groups = self.nb_workers // self.g
        # f workers corrupt at most f groups (a partition): the outer rule
        # sees n/g rows with the same declared f — its (n/g, f) feasibility
        # check runs HERE, at parse time (the composition is rejected before
        # any training step if the tree cannot honor the budget).
        self.outer = instantiate(str(self.args["outer"]), self.nb_groups, self.nb_byz_workers)
        # The inner rule is within-group best effort; a group may hold up to
        # min(f, g) Byzantine members, clamped to what any rule can admit.
        inner_f = int(self.args["inner_f"])
        if inner_f < 0:
            inner_f = min(self.nb_byz_workers, self.g - 1)
        if inner_f > self.g:
            raise UserException(
                "hier inner_f=%d exceeds the group size g=%d" % (inner_f, self.g)
            )
        self.inner_f = inner_f
        self.inner = instantiate(str(self.args["inner"]), self.g, inner_f)
        # A NaN row is excluded by whichever level first absorbs it: the
        # inner drops it from the summary, or it poisons the summary and the
        # outer drops that group row.
        self.nan_row_tolerant = self.inner.nan_row_tolerant or self.outer.nan_row_tolerant

    # ------------------------------------------------------------------ #

    def _grouped(self, block):
        return block.reshape(self.nb_groups, self.g, block.shape[-1])

    def _inner_call(self, grouped, axis_name, key, with_participation):
        """vmapped inner pass: (n/g, g, d_block) -> (n/g, d_block) summaries
        (+ per-group (n/g, g) participation when requested)."""
        if self.masking is not None:
            # Masked group means (secure/masking.py): inner=average computed
            # in the exact mod-2^64 masked domain — rows one-time-padded
            # within their group, a dropped row NaNs its group summary and
            # the NaN-tolerant outer absorbs it.  Participation within a
            # group is uniform 1/g, exactly like plain average's.
            from ..secure.masking import masked_group_mean

            summaries = masked_group_mean(
                grouped, key, self.masking, axis_name=axis_name
            )
            return summaries, None
        inner = self.inner
        dist2 = None
        if inner.needs_distances:
            partial = jax.vmap(centered_gram_sq_distances)(grouped.astype(jnp.float32))
            if axis_name is not None:
                partial = jax.lax.psum(partial, axis_name)
            dist2 = jnp.maximum(partial, 0.0)
        keys = None
        if key is not None:
            base = jax.random.fold_in(key, 1)
            keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                jnp.arange(self.nb_groups)
            )

        def one(rows, d2, k):
            if with_participation:
                return inner.aggregate_block_and_participation(
                    rows, d2, axis_name=axis_name, key=k
                )
            return inner._call_aggregate(rows, d2, axis_name=axis_name, key=k), None

        in_axes = (0, 0 if dist2 is not None else None, 0 if keys is not None else None)
        return jax.vmap(one, in_axes=in_axes)(grouped, dist2, keys)

    def _outer_dist2(self, summaries, axis_name):
        if not self.outer.needs_distances:
            return None
        partial = centered_gram_sq_distances(summaries.astype(jnp.float32))
        if axis_name is not None:
            partial = jax.lax.psum(partial, axis_name)
        return jnp.maximum(partial, 0.0)

    def _outer_key(self, key):
        # disjoint from the per-group inner streams (fold_in(key, 1) + gidx)
        return None if key is None else jax.random.fold_in(key, 2)

    # ------------------------------------------------------------------ #

    def aggregate_block(self, block, dist2=None, axis_name=None, key=None):
        summaries, _ = self._inner_call(self._grouped(block), axis_name, key, False)
        return self.outer._call_aggregate(
            summaries, self._outer_dist2(summaries, axis_name),
            axis_name=axis_name, key=self._outer_key(key),
        )

    def aggregate_block_and_participation(self, block, dist2=None, axis_name=None, key=None):
        summaries, inner_part = self._inner_call(self._grouped(block), axis_name, key, True)
        agg, outer_part = self.outer.aggregate_block_and_participation(
            summaries, self._outer_dist2(summaries, axis_name),
            axis_name=axis_name, key=self._outer_key(key),
        )
        if outer_part is None:
            return agg, None
        if inner_part is None:
            # coordinate-wise inner rules select per coordinate, not per
            # worker: within a group the weight is uniform
            inner_part = jnp.full((self.nb_groups, self.g), 1.0 / self.g, jnp.float32)
        participation = (outer_part[:, None] * inner_part).reshape(self.nb_workers)
        return agg, participation


register("hier", HierarchicalGAR)
