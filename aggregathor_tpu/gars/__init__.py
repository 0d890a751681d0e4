"""Gradient Aggregation Rules (GARs) — the heart of the framework.

A GAR reduces the ``(n, d)`` matrix of per-worker flattened gradients to one
``(d,)`` aggregated gradient while tolerating up to ``f`` Byzantine rows
(reference: aggregators/__init__.py:40-60).  The reference ships three
implementation tiers per rule (numpy/py_func, pure-TF, C++ custom op); here
the tiers are:

- **jnp** (this package): jit-compiled XLA, the default on-device tier —
  replaces both the pure-TF tier and the C++ CPU/GPU custom ops;
- **oracle** (``gars/oracle.py``): plain numpy, reference-faithful semantics,
  the cross-check used by the property tests (SURVEY.md §4);
- **pallas** (``ops/pallas_kernels.py``): hand-written TPU kernels — the
  O(n²·d) pairwise distances (the row count alone picks one of two) and the
  coordinate-wise selections; ``gars/common.py`` ``kernel_tier`` /
  ``leaf_tier`` say where they run, from the platform and the shape;
- **native** (``ops/native``): C++ host library via ctypes, parity with the
  reference's ``aggregators/deprecated_native`` tier.

TPU-first design note: every distance-based rule is factored into
``selection_weights(dist2) -> W`` (tiny, O(n²) work, replicated) and a
``W @ block`` combine (MXU matmul, works on *dimension-sharded* column blocks
of the gradient matrix).  The distributed engine in ``parallel/`` exploits
this: the (n, d) matrix never materializes on one device — blocks stay
sharded, only the (n, n) distance matrix is psum-reduced.
"""

from ..utils import ClassRegister, import_directory

gars = ClassRegister("GAR")

#: reserved fold_in tag both engines use to derive the per-step GAR key from
#: the step key — far above any per-worker stream index, so the randomized
#: meta-rules' permutations never collide with the attack/lossy streams
GAR_KEY_TAG = 0x6AC0BEA7


def register(name, cls):
    return gars.register(name, cls)


def itemize():
    return gars.itemize()


def _split_args(text):
    """Split ``k=v,k=v`` on top-level commas only — a parenthesized value
    (a nested rule spec like ``hier(g=4,outer=krum)``) keeps its commas."""
    parts, depth, cur = [], 0, []
    for ch in text:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return [p for p in (p.strip() for p in parts) if p]


def parse_spec(spec):
    """Parse an inline GAR spec into ``(name, [key:value, ...])``.

    Three forms (all equivalent)::

        krum
        hier:g=16,inner=median,outer=krum
        hier(g=16,inner=median,outer=krum)

    Nested composite rules spell their sub-arguments in the parenthesized
    form so the commas stay attached to the inner spec::

        bucketing:s=2,inner=hier(g=8,outer=krum)

    The returned args use the ``key:value`` convention ``parse_keyval``
    expects.  A plain registered name passes through untouched.
    """
    from ..utils import UserException

    spec = str(spec).strip()
    ci, pi = spec.find(":"), spec.find("(")
    if pi != -1 and spec.endswith(")") and (ci == -1 or pi < ci):
        name, _, body = spec.partition("(")
        body = body[:-1]
    elif ci != -1:
        name, _, body = spec.partition(":")
    else:
        return spec, []
    name = name.strip()
    args = []
    for item in _split_args(body):
        if "=" not in item:
            raise UserException(
                "GAR spec argument %r wants key=value (in spec %r)" % (item, spec)
            )
        key, _, value = item.partition("=")
        args.append("%s:%s" % (key.strip(), value.strip()))
    return name, args


def instantiate(name, nb_workers, nb_byz_workers, args=None):
    """Build the GAR registered under ``name`` (reference: aggregators/__init__.py:66-70).

    ``args`` is a list of ``key:value`` strings, the same sub-argument
    convention every other registry uses (attacks, optimizers, experiments).
    ``name`` may also be an inline spec (``hier:g=16,outer=krum`` — see
    :func:`parse_spec`); spec args and explicit ``args`` concatenate, with
    duplicate keys rejected by ``parse_keyval``.
    """
    name, spec_args = parse_spec(name)
    return gars.get(name)(nb_workers, nb_byz_workers, spec_args + list(args or []))


class GAR:
    """Base Gradient Aggregation Rule.

    Subclasses implement ``aggregate_block``; ``aggregate`` is the dense
    convenience entry that computes the distance matrix when needed.

    Attributes:
      coordinate_wise: True if the rule treats coordinates independently, so a
        column block can be aggregated with no cross-block information.
      needs_distances: True if ``aggregate_block`` requires the global (n, n)
        pairwise squared-distance matrix (Krum/Bulyan family).
    """

    coordinate_wise = False
    needs_distances = False
    #: True if ``aggregate_block`` accepts ``axis_name=`` for cross-block
    #: reductions (iterative rules needing global row norms: the engine
    #: passes the worker mesh axis so blockwise results match the dense tier
    #: exactly, at one O(n) psum per internal iteration)
    uses_axis = False
    #: True if ``aggregate_block`` accepts ``key=`` (a replicated per-step
    #: PRNG key) — randomized meta-rules (bucketing) re-draw their
    #: permutation every step; the key is identical on every device and
    #: block, so the randomness never breaks replication
    uses_key = False
    #: True if an all-NaN row is cleanly EXCLUDED from the aggregate (never
    #: selected / weight 0) rather than poisoning it — the property the
    #: lossy link's NaN infill and the reputation quarantine rely on
    nan_row_tolerant = False
    #: typed key:value argument defaults accepted by this rule (strict: an
    #: unknown key raises instead of being silently ignored)
    ARG_DEFAULTS = {}

    def __init__(self, nb_workers, nb_byz_workers, args=None):
        from ..utils import parse_keyval

        self.nb_workers = int(nb_workers)
        self.nb_byz_workers = int(nb_byz_workers)
        self.args = parse_keyval(args, self.ARG_DEFAULTS, strict=True)
        self.check()

    def check(self):
        """Validate the (n, f) relation; raise UserException when unsatisfiable."""
        from ..utils import UserException

        if self.nb_workers < 1:
            raise UserException("GAR %r needs at least 1 worker" % type(self).__name__)
        if self.nb_byz_workers < 0:
            raise UserException("Negative declared Byzantine count")
        # Universal feasibility floor (graftcheck GC002): NO rule can
        # tolerate a Byzantine majority of everyone — f >= n leaves zero
        # honest rows to aggregate, and every declared-f budget downstream
        # (NaN infill, bounded-wait timeouts, forgery rejection, guardian
        # f+K re-sizing) silently overdraws.  Rejected here, at parse time,
        # for every rule — per-rule checks only tighten this further.
        if self.nb_byz_workers >= self.nb_workers:
            raise UserException(
                "GAR %r cannot declare f=%d >= n=%d: at least one worker "
                "must be honest for any aggregate to mean anything"
                % (type(self).__name__, self.nb_byz_workers, self.nb_workers)
            )

    def aggregate(self, grads, key=None):
        """Dense tier: reduce the full (n, d) matrix to (d,)."""
        from .common import pairwise_sq_distances

        dist2 = pairwise_sq_distances(grads) if self.needs_distances else None
        return self._call_aggregate(grads, dist2, axis_name=None, key=key)

    def _drop_memos(self):
        """Drop ``memo_by_identity`` entries created during this pass: they
        hold (tracer-arg, tracer-result) tuples that must not outlive the
        outer call (gars/common.py memo docstring)."""
        for name in [a for a in vars(self) if a.startswith("_memo_")]:
            delattr(self, name)

    def _call_aggregate(self, block, dist2, axis_name=None, key=None, keep_memo=False):
        """Invoke ``aggregate_block`` with exactly the keywords this rule
        declares (``uses_axis``/``uses_key``) — the single dispatch point the
        engines use, so plain rules keep their two-argument signature.

        Memo entries are dropped on exit (they hold tracers, see
        ``_drop_memos``) unless ``keep_memo`` — the one caller that needs
        the memo to survive is ``aggregate_block_and_participation``, whose
        participation read reuses the selection graph and which drops the
        memo itself afterwards."""
        kwargs = {}
        if self.uses_axis:
            kwargs["axis_name"] = axis_name
        if self.uses_key:
            kwargs["key"] = key
        try:
            return self.aggregate_block(block, dist2, **kwargs)
        finally:
            if not keep_memo:
                self._drop_memos()

    def aggregate_block(self, block, dist2=None):
        """Blockwise tier: reduce an (n, d_block) column block to (d_block,).

        ``dist2`` is the *global* (n, n) squared-distance matrix (already
        reduced across blocks) when ``needs_distances`` is set.
        """
        raise NotImplementedError

    def worker_participation(self, dist2):
        """Optional (n,) diagnostic: how much weight each worker's gradient
        carried in the aggregate (sums to 1).  Selection-based rules override
        this — a worker the rule consistently excludes is a suspect, the
        observable the Byzantine-ML literature uses to *detect* attackers
        rather than only absorb them.  None = not defined for this rule
        (coordinate-wise rules select per coordinate, not per worker)."""
        return None

    def aggregate_block_and_participation(self, block, dist2=None, axis_name=None, key=None):
        """Aggregate a block AND return the (n,) participation (or None).

        One entry point so iterative rules (geometric-median) can expose the
        weights their own iteration already computes — in one pass, with no
        state stashed on the instance between calls (a stashed jnp value
        would be a tracer leaking across trace boundaries)."""
        try:
            agg = self._call_aggregate(
                block, dist2, axis_name=axis_name, key=key, keep_memo=True
            )
            return agg, self.worker_participation(dist2)
        finally:
            self._drop_memos()

    #: The rule's kernel over one gradient leaf AS IT LIES, a method ``(n, ...,
    #: A, B) -> (..., A, B)`` float32 of the rules that have one (the rank rules:
    #: ops/pallas_kernels' leaf entries), else None.
    leaf_kernel = None

    def aggregate_leaf(self, leaf):
        """In-place tier of a COORDINATE-WISE rule with no distances, axis or
        key: the (n, ...) stack of the n workers' copies of one gradient leaf
        to the aggregated (...) leaf, float32.  Such a rule is elementwise
        across the workers whatever the shape, so the engine's in-place path
        (parallel/in_place.py) never lays the leaves out as (n, d) rows: a
        leaf goes to ``leaf_kernel`` as it lies where ``common.leaf_tier`` says
        so, and else, flattened alone, through ``aggregate_block``."""
        from .common import leaf_tier

        if leaf_tier(self, leaf) == "kernel":
            return self.leaf_kernel(leaf)
        block = leaf.reshape(leaf.shape[0], -1).astype("float32")
        return self._call_aggregate(block, None).reshape(leaf.shape[1:])


# Self-registering rule modules (reference: aggregators/__init__.py:76-85)
import_directory(__name__, __path__, skip=("oracle",))
