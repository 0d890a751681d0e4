"""GAR contract checker: every registered rule proves its declared contract.

A GAR's class attributes are load-bearing declarations, not documentation:
``nan_row_tolerant`` licenses the lossy link, the bounded-wait timeout path
and the quarantine to inject NaN rows *inside the declared-f budget*;
``worker_participation`` feeds reputation and forensics; parse-time
feasibility is what the guardian's escalation ladder relies on when it
re-sizes ``f``; dtype preservation is the exchange-compression contract.
A rule registered with a false declaration breaks subsystems that never
import it directly — so registration itself must be checkable.

This checker is semantic, not AST: it discovers every registered spec
through ``gars/__init__.py`` (``itemize``/``parse_spec``), instantiates
each at a small feasible ``(n, f)`` found by probing, and verifies under
``jax.eval_shape`` plus tiny concrete probes (n <= 16, d = 8, CPU-friendly):

- **GC001 nan-poison** — with ``nan_row_tolerant`` declared, ``f`` all-NaN
  rows must leave the aggregate finite (the budget the whole straggler /
  lossy / quarantine stack spends).
- **GC002 infeasibility accepted** — ``f >= n`` must be rejected at parse
  time with a ``UserException`` for EVERY rule (you cannot tolerate a
  Byzantine majority of everyone), and the rejection must be a parse
  error, not a crash deep in aggregation.
- **GC003 participation** — when ``worker_participation`` is defined it
  must be an (n,) vector summing to 1 (the scatter the forensics ledger
  and reputation EMA consume).
- **GC004 dtype/shape drift** — float32 ``(n, d)`` in, float32 ``(d,)``
  out, proven abstractly by ``jax.eval_shape`` (no compile, no FLOPs).
- **GC005 int8-wire survival** — the compressed-exchange contract
  (parallel/compress.py, ``--exchange int8``): finite rows squeezed
  through the int8 wire round-trip (quantization moves every value and
  zeroes small coordinates exactly) must still aggregate finite.  A rule
  that silently breaks under the quantized wire is a GC finding, not a
  surprise at the first compressed run.
- **GC000 probe crash** — any probe raising something other than the
  contract's expected exception is itself a finding: a rule the checker
  cannot exercise is a rule the next PR can silently break.

Composite specs (``hier:``/``bucketing:`` nestings) go through the same
probes — the sweep in ``tests/test_analysis.py`` asserts coverage of 100%
of the registry against ``itemize()``, not a hand-kept list.
"""

import functools

from .core import Finding

CHECKER = "gar-contract"

#: small feasible-(n, f) candidates, probed in order (bulyan needs
#: n >= 4f + 3, hier needs divisible groups, bucketing reduced inner ...)
CANDIDATES = ((8, 1), (8, 2), (12, 2), (16, 2), (11, 3), (16, 3), (9, 1),
              (6, 1), (16, 1), (32, 4))

#: probe width: big enough for coordinate medians to be meaningful, small
#: enough that 30+ rules x 4 probes stay inside the tier-1 test budget
PROBE_D = 8

#: composite nestings swept IN ADDITION to every registered name — the
#: meta-rule compositions the engines accept anywhere a GAR name is
COMPOSITE_SPECS = (
    "hier:g=2,inner=median,outer=krum",
    "bucketing:s=2,inner=krum",
    "bucketing:s=2,inner=hier(g=2,inner=median,outer=average-nan)",
    "hier:g=4,inner=bucketing(s=2,inner=median),outer=average-nan",
    # the aggregation tree (topology/spec.py) in BOTH nesting directions:
    # composites inside a tree level, and a tree as another meta-rule's
    # outer — the registry accepts it anywhere a GAR name is
    "tree:g=2x2,rules=median>median>average-nan",
    "tree:g=4,rules=bucketing(s=2,inner=median)>krum",
    "hier:g=2,inner=median,outer=tree(g=2,rules=median>average-nan)",
)


def default_specs():
    """Every registered GAR name (auto-discovered — a rule cannot register
    without entering this sweep) plus the composite nestings."""
    from .. import gars

    return tuple(gars.itemize()) + COMPOSITE_SPECS


def _finding(code, spec, symbol, message):
    return Finding(
        checker=CHECKER, code=code, path="gars/%s" % spec.split(":", 1)[0],
        line=0, scope=spec, symbol=symbol, message=message,
    )


def _instantiate(spec, n, f):
    from .. import gars

    return gars.instantiate(spec, n, f)


def _feasible(spec):
    """(gar, n, f) at the first feasible candidate; (None, None, reason)
    when none is.  A non-UserException from a rule's constructor is a
    CRASH, not an infeasibility — it must surface as a GC000 finding, not
    kill the whole checker run (the module-docstring contract)."""
    from ..utils import UserException

    crash = None
    for n, f in CANDIDATES:
        try:
            return _instantiate(spec, n, f), n, f
        except UserException:
            continue
        except Exception as exc:
            crash = "(n=%d, f=%d) crashed: %s: %s" % (n, f, type(exc).__name__, exc)
    return None, None, crash


def check_spec(spec):
    """All contract probes for one spec; returns a list of findings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..gars.common import pairwise_sq_distances
    from ..utils import UserException

    findings = []
    gar, n, f = _feasible(spec)
    if gar is None:
        detail = f  # _feasible's third slot carries the crash reason if any
        return [_finding(
            "GC000", spec, "feasibility",
            detail or "no feasible (n, f) among %r: the contract cannot be "
            "exercised" % (CANDIDATES,),
        )]

    base_key = jax.random.PRNGKey(0)
    # one derived key per probe (fresh fold_in data each — the hygiene the
    # prng checker enforces on this file like any other)
    shape_key, clean_key, nan_key, part_key, int8_key = (
        jax.random.fold_in(base_key, tag) for tag in range(5)
    )
    rng = np.random.default_rng(0x6A2)
    grads = rng.normal(size=(n, PROBE_D)).astype(np.float32)

    # GC004: dtype/shape under eval_shape — abstract, no compile
    try:
        out = jax.eval_shape(
            lambda g, k: gar.aggregate(g, key=k),
            jax.ShapeDtypeStruct((n, PROBE_D), jnp.float32),
            jax.ShapeDtypeStruct(np.shape(shape_key), np.asarray(shape_key).dtype),
        )
        if tuple(out.shape) != (PROBE_D,):
            findings.append(_finding(
                "GC004", spec, "shape",
                "aggregate of (%d, %d) returned shape %r, wants (%d,)"
                % (n, PROBE_D, tuple(out.shape), PROBE_D),
            ))
        if out.dtype != jnp.float32:
            findings.append(_finding(
                "GC004", spec, "dtype",
                "float32 input aggregated to %s: the wire-dtype "
                "round-trip in the engines relies on dtype preservation"
                % out.dtype,
            ))
    except Exception as exc:
        findings.append(_finding(
            "GC000", spec, "eval_shape",
            "eval_shape probe crashed: %s: %s" % (type(exc).__name__, exc),
        ))

    # concrete clean aggregate: finite
    try:
        clean = np.asarray(gar.aggregate(jnp.asarray(grads), key=clean_key))
        if not np.all(np.isfinite(clean)):
            findings.append(_finding(
                "GC001", spec, "clean-finite",
                "aggregate of finite gradients is not finite at (n=%d, f=%d)"
                % (n, f),
            ))
    except Exception as exc:
        findings.append(_finding(
            "GC000", spec, "aggregate",
            "concrete aggregate probe crashed: %s: %s"
            % (type(exc).__name__, exc),
        ))
        return findings  # later probes would only repeat the crash

    # GC001: declared NaN tolerance actually absorbs f NaN rows
    if gar.nan_row_tolerant and f >= 1:
        poisoned = grads.copy()
        poisoned[:f] = np.nan
        try:
            out = np.asarray(gar.aggregate(jnp.asarray(poisoned), key=nan_key))
            if not np.all(np.isfinite(out)):
                findings.append(_finding(
                    "GC001", spec, "nan-rows",
                    "declares nan_row_tolerant but %d NaN row(s) within "
                    "f=%d poison the aggregate — the lossy/straggler/"
                    "quarantine NaN budget is a lie for this rule" % (f, f),
                ))
        except Exception as exc:
            findings.append(_finding(
                "GC000", spec, "nan-probe",
                "NaN-tolerance probe crashed: %s: %s"
                % (type(exc).__name__, exc),
            ))

    # GC005: int8-wire survival — quantized finite rows aggregate finite
    # (the probe the compressed exchange relies on; run_compress_smoke.sh
    # exercises it through the real CLI).  One coordinate per row is
    # amplified 1000x before the round-trip: the per-row scale then
    # quantizes every small coordinate to an EXACT zero — real gradient
    # rows have heavy coordinates, and that zeroing is precisely the
    # structure a fragile rule breaks on.
    try:
        from ..parallel.compress import Int8Codec

        spiky = grads.copy()
        spiky[:, 0] *= 1000.0
        quantized = Int8Codec().roundtrip_rows(jnp.asarray(spiky))
        out = np.asarray(gar.aggregate(quantized, key=int8_key))
        if not np.all(np.isfinite(out)):
            findings.append(_finding(
                "GC005", spec, "int8-wire",
                "aggregate of int8-roundtripped finite gradients is not "
                "finite at (n=%d, f=%d) — the rule breaks under the "
                "compressed exchange (--exchange int8)" % (n, f),
            ))
    except Exception as exc:
        findings.append(_finding(
            "GC000", spec, "int8-probe",
            "int8-wire probe crashed: %s: %s" % (type(exc).__name__, exc),
        ))

    # GC003: participation scatter sums to 1
    try:
        dist2 = pairwise_sq_distances(jnp.asarray(grads)) if gar.needs_distances else None
        _, part = gar.aggregate_block_and_participation(
            jnp.asarray(grads), dist2, key=part_key
        )
        if part is not None:
            part = np.asarray(part)
            if part.shape != (n,):
                findings.append(_finding(
                    "GC003", spec, "participation-shape",
                    "worker_participation returned shape %r, wants (%d,)"
                    % (part.shape, n),
                ))
            elif not np.isclose(float(np.sum(part)), 1.0, atol=1e-3):
                findings.append(_finding(
                    "GC003", spec, "participation-sum",
                    "worker_participation sums to %.6f, wants 1 — the "
                    "reputation/forensics scatter double- or under-counts"
                    % float(np.sum(part)),
                ))
    except Exception as exc:
        findings.append(_finding(
            "GC000", spec, "participation",
            "participation probe crashed: %s: %s" % (type(exc).__name__, exc),
        ))

    # GC002: f >= n must be a parse-time UserException, never accepted and
    # never a crash from aggregation depths
    try:
        _instantiate(spec, 3, 3)
        findings.append(_finding(
            "GC002", spec, "infeasible-accepted",
            "(n=3, f=3) accepted at parse time: a rule cannot tolerate a "
            "Byzantine majority of everyone — feasibility must reject "
            "f >= n before a step ever runs",
        ))
    except UserException:
        pass  # the contract: loud, typed, at parse time
    except Exception as exc:
        findings.append(_finding(
            "GC002", spec, "infeasible-crash",
            "infeasible (n=3, f=3) crashed with %s instead of a parse-time "
            "UserException: %s" % (type(exc).__name__, exc),
        ))
    return findings


@functools.lru_cache(maxsize=4)
def _check_cached(specs):
    findings = []
    for spec in specs:
        findings.extend(check_spec(spec))
    return tuple(findings)


def check(modules=None, specs=None):
    """Checker entry point.  ``modules`` is accepted (and ignored) for
    signature parity with the AST checkers; results are cached per spec
    tuple — the CLI and the test sweep share one probe pass per process."""
    del modules
    return list(_check_cached(tuple(specs) if specs is not None else default_specs()))
