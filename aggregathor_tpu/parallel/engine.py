"""The robust SPMD training engine.

One jitted step function replaces the reference's entire per-step distributed
dance (worker gradient push over gRPC/MPI/UDP -> PS-side GAR -> variable
update, SURVEY.md §3.1).  Dataflow per step, for ``n`` logical workers over a
``W``-device ``worker`` mesh axis (k = n/W workers per device):

1.  **Isolated worker gradients** — the batch arrives worker-sharded; each
    device vmaps its k workers' forward/backward.  Gradients are flattened to
    (k, d) with the coherent pytree layout (core/flatten.py).
2.  **Local Byzantine attack / lossy link** — transforms that only read the
    worker's own slot run here, before any collective (honest threat model).
3.  **Reshard worker->dimension** — ``all_to_all`` turns the implicit (n, d)
    gradient matrix into per-device column blocks (n, blk).  This is the
    engine's key memory move: no device ever holds n gradients, per-device
    footprint stays O(d) (SURVEY.md §7 hard part (b)).  ``blk`` is
    ``_block_width(d)``: ``ceil(d / W)`` rounded up to the kernels' column
    tile (1,024 columns; 128 under that), the rows zero-padded once to
    ``W * blk``, so that every block starts on a lane boundary and the cut
    is a bitcast and not a relayout; on one device ``blk`` is ``d`` and
    nothing is padded or moved.
4.  **Omniscient attacks** — coalition attacks needing honest statistics
    (coordinate-wise mean/std) apply blockwise on the gathered rows.
5.  **Distances** — Krum/Bulyan need the (n, n) squared-distance matrix: each
    device computes its block's partial Gram contribution, one O(n²) ``psum``
    completes it (vs the reference's O(n²·d) PS-side loop, op_krum/cpu.cpp).
6.  **Blockwise GAR** — every rule reduces its column block locally
    (selection weights are identical on all devices by construction).
7.  **Gather + update** — ``all_gather`` restores the aggregated (d,) vector;
    the optax update applies identically on every device, keeping parameters
    replicated — the PS's "one canonical copy" without a PS (train_state.py).

Wire cost: one all_to_all (d floats out/in per device) + one O(n²) psum + one
all_gather (d floats) ≈ 2x a ring allreduce — the minimum for robust
aggregation, since the GAR provably needs per-worker gradients, not their sum
(SURVEY.md §2.6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import config
from ..core.flatten import FlatMap
from ..core.train_state import TrainState
from ..gars.common import centered_gram_sq_distances
from ..obs import trace
from ..obs.profiler import PHASE_PREFIX
from ..ops.pallas_kernels import LANE, MAX_BLOCK
from ..utils import UserException, info
from .mesh import model_axis, pipe_axis, worker_axis

#: the in-group (within one logical worker's submesh) mesh axes of the
#: leafwise-sharded mode — collectives over these complete replicated-leaf
#: gradients and per-bucket distances; both are size 1 in flat mode
_IN_GROUP_AXES = (pipe_axis, model_axis)

#: The step's phases, in the order a step runs them.  Each is a
#: ``jax.named_scope`` (``step.<phase>``) round its part of every step body
#: below: a scope writes ``op_name`` metadata and nothing else, so the
#: compiled program is the same program, and ``obs.profiler.phase_table``
#: reads from its text which instruction belongs to which phase — what cuts
#: a device trace of the step by phase (docs/observability.md).
PHASES = ("sample", "augment", "grad", "flatten", "perturb", "reshard", "gar",
          "gather", "apply", "epilogue")


_SCOPE_OF = {name: PHASE_PREFIX + name for name in PHASES}

#: Revision of where the scopes sit, carried in the name of every program with
#: phases that the engine jits (``jit_many_p1``).  JAX's persistent compilation
#: cache keys a program on its name and operations and leaves ``op_name``
#: metadata out of the key: under one name, a build that adds or moves a scope
#: is handed the program that an earlier build left in a shared cache directory
#: (an exported ``JAX_COMPILATION_CACHE_DIR``), with THAT build's scopes or none,
#: and ``phase_table`` would cut a trace by them.  Bump it whenever a
#: ``phase(...)`` is added, moved or renamed (tests/test_phases.py holds the
#: list of call sites against it).
PHASES_REVISION = 1


def _revised(fn):
    """``fn`` renamed to carry ``PHASES_REVISION``, for ``jax.jit``."""
    fn.__name__ = "%s_p%d" % (fn.__name__, PHASES_REVISION)
    return fn


def phase(name):
    """The named scope of one of ``PHASES`` (another name is a ``KeyError``)."""
    return jax.named_scope(_SCOPE_OF[name])


def _is_spec(x):
    return x is None or isinstance(x, P)


def _spec_axis_names(spec):
    names = set()
    for entry in spec or ():
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.update(entry)
        else:
            names.add(entry)
    return names


def _replication_axes(spec):
    """In-group mesh axes over which a leaf with this spec is replicated."""
    names = _spec_axis_names(spec)
    return tuple(a for a in _IN_GROUP_AXES if a not in names)


def validate_reputation_args(gar, reputation_decay, quarantine_threshold):
    """Shared validation of the reputation/quarantine knobs (both engines).

    Returns the normalized ``(decay, threshold)`` pair.  Quarantine is
    bounded by the rule's declared budget: at most ``f`` workers are masked
    per step (``quarantine_mask``), so a NaN-excluding rule sized for f
    Byzantine rows never sees more dead rows than it tolerates — which is
    why ``f >= 1`` is required to quarantine at all."""
    decay = None if reputation_decay is None else float(reputation_decay)
    threshold = float(quarantine_threshold)
    if decay is not None and not 0.0 < decay < 1.0:
        raise UserException("reputation_decay must lie in (0, 1), got %r" % reputation_decay)
    if threshold:
        if decay is None:
            raise UserException("quarantine_threshold needs reputation_decay set")
        if not 0.0 < threshold < 1.0:
            raise UserException(
                "quarantine_threshold must lie in (0, 1), got %r" % quarantine_threshold
            )
        if gar.nb_byz_workers < 1:
            raise UserException(
                "Quarantine masks up to f workers per step; declare "
                "--nb-decl-byz-workers >= 1 to use it"
            )
        if not gar.nan_row_tolerant:
            from ..gars import gars as _registry

            tolerant = sorted(
                name for name in _registry.itemize()
                if getattr(_registry.get(name), "nan_row_tolerant", False)
            )
            # ``bucketing``/``hier`` set nan_row_tolerant per-INSTANCE (they
            # inherit their child rules' tolerance), so the class-attribute
            # scan above cannot list them — name them explicitly.
            raise UserException(
                "Quarantine masks rows to NaN, which %s does not cleanly "
                "exclude (pick a NaN-excluding rule: %s; or bucketing/hier "
                "with NaN-tolerant child rules)"
                % (type(gar).__name__, ", ".join(tolerant))
            )
    return decay, threshold


def validate_chaos_args(chaos, attack, lossy_link, nb_workers, nb_real_byz):
    """Shared validation of a ChaosSchedule against the engine's own
    configuration (both engines).  Returns ``chaos`` unchanged."""
    if chaos is None:
        return None
    if attack is not None or lossy_link is not None:
        raise UserException(
            "--chaos subsumes the static --attack/--UDP knobs: encode them as "
            "schedule regimes instead (e.g. '0:attack=empire' / '0:drop=0.3')"
        )
    if chaos.nb_workers != nb_workers:
        raise UserException(
            "ChaosSchedule was built for n=%d workers but the engine has %d"
            % (chaos.nb_workers, nb_workers)
        )
    if chaos.has_attacks or getattr(chaos, "has_forgery", False):
        if nb_real_byz == 0:
            raise UserException(
                "The chaos schedule declares attack/forge/tamper regimes; they "
                "need --nb-real-byz-workers > 0 to have anyone to run them"
            )
        if chaos.nb_real_byz != nb_real_byz:
            # the schedule sized its attacks (e.g. little's z formula) for a
            # different coalition than the engine will gate
            raise UserException(
                "ChaosSchedule was built for %d real Byzantine workers but "
                "the engine declares %d" % (chaos.nb_real_byz, nb_real_byz)
            )
    return chaos


def quarantine_mask(reputation, threshold, nb_byz):
    """(n,) bool: below-threshold AND among the ``nb_byz`` lowest
    reputations — the cap keeps the masked count within the NaN budget the
    rule's (n, f) sizing tolerates (an unbounded mask could exceed it when
    the rank signal rotates across honest stragglers)."""
    from ..gars.common import smallest_k_mask

    return (reputation < threshold) & smallest_k_mask(reputation, nb_byz)


def _partial_pairwise_sq_distances(block):
    """Per-block contribution to the (n, n) squared-distance matrix.

    Direct difference form on the (n, d_block) block would cost O(n²·d_block)
    memory, so the shared centered-Gram helper is used; psum across blocks
    then yields the same convention as the dense tier (NaN anywhere -> NaN
    entry; per-block median centering is a valid translation per block).

    On TPU, large blocks dispatch to the Pallas streaming distance kernel
    (ops/pallas_kernels.py): the Gram form's robust centering pass is a
    per-column median — the same order-statistic cost the Pallas tier
    removes from the coordinate rules (measured r4: krum dist+score at
    d=8.4M, 9.5 ms Pallas vs 398 ms jnp) — while the streamed difference
    form needs no centering because it never cancels.
    """
    block = block.astype(jnp.float32)
    from ..gars.common import use_pallas_coordinate_tier

    if use_pallas_coordinate_tier(block):
        from ..ops import pallas_kernels as pk

        return pk.pairwise_sq_distances(block)
    return centered_gram_sq_distances(block)


class RobustEngine:
    """The ONE sharding-polymorphic robust engine (docs/engine.md).

    Two gradient dataflows behind one constructor, selected by ``sharding``:

    - ``"flat"`` (default on a trivial in-group mesh): one logical worker =
      one vmapped slot on the ``worker`` axis, gradients flattened to (k, d)
      rows, all_to_all reshard to dimension-sharded column blocks, blockwise
      GAR — the module-docstring dataflow.  Granularities ``vector``/``leaf``.
    - ``"sharded"``: one logical worker = a (pipe x model) submesh running a
      pipelined/tensor-parallel replica; robust aggregation runs per
      parameter bucket directly on the *sharded* gradients, the (n, d)
      matrix never materialized.  Granularities ``layer``/``leaf``/``global``.

    Everything that is not the gradient dataflow — knob validation, the
    chaos schedule, reputation/quarantine, worker momentum, the CLEVER
    carry, authenticated submission, the health probe, the flight recorder,
    and the whole step epilogue (``_finalize_step``) — exists ONCE and is
    shared by both bodies.  The two perturbation/submission pipelines stay
    separate on purpose: their PRNG stream layouts differ (flat folds per
    worker over the flattened row; sharded folds per (worker, leaf)), and
    bit-compatibility with existing runs pins both.
    """

    def __init__(self, mesh, gar, nb_workers=None, nb_real_byz=0, attack=None, lossy_link=None,
                 exchange=None, worker_momentum=None, batch_transform=None,
                 worker_metrics=False, reputation_decay=None, quarantine_threshold=0.0,
                 granularity=None, chaos=None,
                 health_probe=True, secure=False, flight=None,
                 l1_regularize=None, l2_regularize=None, sharding=None):
        self.mesh = mesh
        self.gar = gar
        # Mode resolution: explicit ``sharding`` wins; otherwise a mesh with
        # nontrivial in-group (pipe/model) axes means the leafwise-sharded
        # dataflow (a flat engine cannot use those devices at all).
        if sharding is None:
            sharding = (
                "sharded"
                if mesh.shape[pipe_axis] * mesh.shape[model_axis] > 1 else "flat"
            )
        if sharding not in ("flat", "sharded"):
            raise UserException(
                "sharding must be 'flat' or 'sharded' (got %r)" % (sharding,)
            )
        self.sharded = sharding == "sharded"
        if granularity is None:
            granularity = "layer" if self.sharded else "vector"
        if self.sharded:
            if granularity not in ("layer", "leaf", "global"):
                raise UserException(
                    "sharded granularity must be layer, leaf or global (got %r)"
                    % (granularity,)
                )
            if batch_transform is not None:
                raise UserException(
                    "batch_transform is a flat-engine feature (the sharded "
                    "batches flow through the pipeline stages)"
                )
        else:
            if granularity not in ("vector", "leaf"):
                raise UserException(
                    "granularity must be vector or leaf (got %r); layer/global "
                    "need the sharded mode (sharding='sharded')" % (granularity,)
                )
            if l1_regularize or l2_regularize:
                raise UserException(
                    "the flat engine takes l1/l2 inside loss_fn (the per-worker "
                    "loss is global there); l1_regularize/l2_regularize are the "
                    "sharded engine's analytic equivalent"
                )
        if nb_workers is None:
            nb_workers = mesh.shape[worker_axis]
        self.nb_workers = int(nb_workers)
        self.nb_real_byz = int(nb_real_byz)
        self.attack = attack
        self.lossy_link = lossy_link
        # Time-varying fault regimes (chaos/schedule.py): the schedule's
        # regime index is computed from the TRACED step counter each step, so
        # attack/loss/straggler knobs switch inside the one compiled program.
        # Chaos SUBSUMES the static whole-run knobs — mixing both would give
        # two transport simulations with colliding PRNG streams.
        self.chaos = validate_chaos_args(chaos, attack, lossy_link, self.nb_workers, self.nb_real_byz)
        # Device-side augmentation: ``batch_transform(worker_batch, key) ->
        # worker_batch`` runs INSIDE the jitted step, per worker, train-only
        # (eval paths never apply it).  Keys are a function of (run seed,
        # step, global worker index) so worker w's augmentation stream is
        # independent of nb_workers/device placement — the same discipline
        # as the host tier (models/preprocessing.py).
        self.batch_transform = batch_transform
        # Opt-in per-worker suspicion diagnostics (worker_sq_dist / worker_
        # participation metrics); off by default — the extra O(n·d) pass is
        # a measurable HBM tax at scale.
        self.worker_metrics = bool(worker_metrics)
        # In-step health probe (guardian/probe.py): finite-loss flag, update
        # norm, EMA loss-spike score, per-worker NaN-row flags, nested under
        # metrics["probe"].  On by default — it reuses values the step
        # already computes plus one O(k·d) isfinite pass and an O(n) gather,
        # and adds no dispatches or compiles (tests/test_guardian.py).
        self.health_probe = bool(health_probe)
        # Reputation-gated quarantine: an EMA of a per-step rank signal
        # (1 if the worker's RAW gradient is among the n-f closest to the
        # applied aggregate, else 0); workers whose reputation falls below
        # the threshold have their row masked NaN for that round — the
        # engine treats them exactly like fully-lossy workers, so the rule
        # must absorb NaN rows.  The signal is measured on the raw
        # (pre-quarantine) submissions, so an honest worker whose gradients
        # re-approach the aggregate recovers and is re-admitted.
        self.reputation_decay, self.quarantine_threshold = validate_reputation_args(
            gar, reputation_decay, quarantine_threshold
        )
        # Flat granularity:leaf applies the rule PER PARAMETER LEAF (per-
        # layer selection — the sharded mode's semantics on a plain worker
        # mesh, including n vmapped workers on one chip).  Memory shifts
        # from the dimension-sharded O(d) blocks to one (n, d_leaf) gather
        # at a time, and distance work is replicated per device instead of
        # sharded — the price of letting every layer pick its own honest
        # set.  Sharded granularities were validated above.
        self.granularity = granularity
        if self.sharded:
            if granularity == "global" and (gar.uses_axis or gar.uses_key) and not gar.needs_distances:
                # The global path concatenates DISTANCES across leaves;
                # iterative rules would need their per-iteration row norms
                # accumulated across every leaf instead, which the per-leaf
                # loop cannot do — refuse rather than silently degrade to
                # per-leaf semantics.
                raise UserException(
                    "granularity:global is not supported for %s (whole-vector "
                    "norms across leaves are not implemented); use "
                    "granularity:layer" % type(gar).__name__
                )
            if gar.nb_workers != self.nb_workers:
                raise UserException(
                    "GAR was built for n=%d but the mesh worker axis is %d"
                    % (gar.nb_workers, self.nb_workers)
                )
        # l1/l2 regularization (reference: graph.py:125-139).  The flat
        # engine wraps the per-worker loss; under the sharded shard_map the
        # loss is a LOCAL PARTIAL, so a parameter-norm term in the loss
        # would be counted once per replicating device.  The sharded body
        # instead applies the reg gradient ANALYTICALLY (l1*sign(p) +
        # 2*l2*p, elementwise on each shard) to the psum-completed
        # gradients — exact, shard-local, no double counting — and adds the
        # correctly replication-scaled norm to the reported loss.
        self.l1_regularize = float(l1_regularize) if l1_regularize else None
        self.l2_regularize = float(l2_regularize) if l2_regularize else None
        # Captured by the sharded init_state for put_state (checkpoint
        # restore re-sharding).
        self._state_shardings = None
        # History-aware robustness (Karimireddy et al. 2021): with
        # worker_momentum = beta in (0, 1), every worker sends its momentum
        # m_i <- beta*m_i + (1-beta)*g_i instead of the raw gradient, so the
        # GAR aggregates slow-moving honest statistics that a fresh-noise
        # Byzantine strategy cannot track.  Carried worker-sharded.
        self.worker_momentum = None if worker_momentum is None else float(worker_momentum)
        if self.worker_momentum is not None and not 0.0 < self.worker_momentum < 1.0:
            raise UserException("worker_momentum must lie in (0, 1), got %r" % worker_momentum)
        # Wire precision: the all_to_all + all_gather carry ~2d floats per
        # device per step (the dominant wire cost, module docstring); bf16
        # halves it.  Gradients are quantized ONCE before the reshard and all
        # GAR math runs in f32 on the upcast values, so every device still
        # sees bit-identical inputs (replicated-update determinism holds).
        # ``exchange`` (parallel/compress.py, docs/engine.md "The wire")
        # accepts a spec string (int8[:ef] / topk:... / bf16 / f32) or a
        # WireCodec.  bf16 sets the wire dtype, f32 and None leave it None
        # (no quantization path compiled in); int8/topk engage the codec in
        # the submission pipeline — encoded after the worker-local attacks,
        # decoded at the aggregation boundary so every GAR sees float32
        # rows.  Feasibility (masked fixed-point path, sharded mode, topk
        # budget) refuses HERE, which is also the guardian escalation
        # rebuild path — a ladder rung that re-builds the stack
        # re-validates the codec.
        from .compress import parse_exchange_spec

        self.exchange_dtype, self.codec = parse_exchange_spec(exchange)
        if self.codec is not None:
            if self.sharded:
                raise UserException(
                    "--exchange %s needs the flat engine: the sharded "
                    "dataflow's per-(worker, leaf) submissions would need "
                    "per-leaf codec/error-feedback state, a different "
                    "protocol (bf16/f32 wire dtypes work everywhere)"
                    % self.codec.spec()
                )
            self.codec.validate_for(gar=gar)
        #: the per-worker error-feedback residual rides TrainState.ef
        #: (worker-sharded, serialized — core/train_state.py)
        self.carries_ef = self.codec is not None and self.codec.uses_ef
        # Logical workers are decoupled from worker-axis slots in BOTH
        # modes: k = n/W workers are vmapped per slot (flat: per device;
        # sharded: per (pipe x model) submesh).  ``nb_mesh_workers`` is the
        # historical sharded-mode name for the same axis size.
        self.nb_devices = self.nb_mesh_workers = mesh.shape[worker_axis]
        if self.nb_workers % self.nb_devices != 0:
            raise UserException(
                "nb_workers (%d) must be a multiple of the worker mesh axis (%d)"
                % (self.nb_workers, self.nb_devices)
            )
        self.workers_per_device = self.nb_workers // self.nb_devices
        if self.nb_real_byz > self.nb_workers:
            raise UserException("More real Byzantine workers than workers")
        if attack is not None and self.nb_real_byz == 0:
            raise UserException("An attack needs --nb-real-byz-workers > 0 to have anyone to run it")
        # CLEVER stale infill needs the previously-received gradients carried
        # across steps (mpi_rendezvous_mgr.patch:833-835); stale-mode chaos
        # stragglers reuse the exact same carry (chaos/stragglers.py).
        self.carries_gradients = (lossy_link is not None and lossy_link.clever) or (
            self.chaos is not None and self.chaos.needs_carry
        )
        # Authenticated submission (secure/submit.py): every worker's
        # post-transport row is reduced to a tiny checksum INSIDE the one
        # compiled step (zero added dispatches/recompiles — the compile
        # count is identical with secure on or off, asserted by
        # tests/test_secure.py); rows whose tags cannot verify (chaos
        # forge/tamper) are masked NaN before stacking, and the digests +
        # verdicts ride metrics["secure"] to the host where the real HMAC
        # sign/verify runs one dispatch behind (cli/runner.py).
        self.secure = bool(secure)
        # Flight recorder (obs/flight.py): per-step telemetry lanes written
        # in-scan into a ring carried as a TrainState side buffer, fetched
        # by the host only at summary cadence.  Same compiled program shape
        # discipline as the probe: the ring rides the one executable, so
        # the compile count equals the recorder-off run (tests/
        # test_flight.py asserts).
        self.flight = flight
        if flight is not None:
            flight.validate_for(
                nb_workers=self.nb_workers, probe=self.health_probe,
                worker_metrics=self.worker_metrics,
                chaos=self.chaos is not None, secure=self.secure,
            )
        # jitted slice-concat executables for assemble_batches, per slice count
        self._assemble_cache = {}
        # Which of the flat step's two dataflows this engine runs, decided
        # here, once, from the arguments above (parallel/in_place.py): None
        # where nothing needs the workers' gradients as (n, d) rows — the step
        # then reduces each gradient leaf in place — else what does.
        from .in_place import rows_reason

        self._rows_reason = rows_reason(self)

    # ------------------------------------------------------------------ #

    def _worker_gradients(self, params, batch_shard, loss_fn):
        """vmap the local k workers' loss/grad; returns ((k,) losses, (k, d)
        grads, flatmap, counters).  A loss marked ``has_aux`` returns ``(loss,
        counters)``, a dict of scalars the model counts as it runs
        (models/sdar.py); ``counters`` is then that dict with (k,) leaves,
        else None."""
        has_aux = getattr(loss_fn, "has_aux", False)

        def one(worker_batch):
            return jax.value_and_grad(loss_fn, has_aux=has_aux)(params, worker_batch)

        with phase("grad"):
            losses, grads = jax.vmap(one)(batch_shard)
        losses, counters = losses if has_aux else (losses, None)
        k = self.workers_per_device
        leaves = jax.tree_util.tree_leaves(grads)
        with phase("flatten"):
            gvecs = jnp.concatenate(
                [leaf.reshape(k, -1).astype(jnp.float32) for leaf in leaves], axis=1)
        flatmap = FlatMap(jax.tree_util.tree_map(lambda g: g[0], grads))
        return losses, gvecs, flatmap, counters

    def _perturb_local(self, gvecs, key, carry=None, ridx=None, ef=None):
        """Apply local attack + wire codec + lossy link + chaos regime +
        the submission-forgery pipeline to each local worker's own slot.

        Returns (perturbed (k, d), new_carry, secure_info, new_ef) —
        ``new_carry`` is the post-transport gradients, i.e. what "the PS
        received" this step: exactly the stale value a lost packet keeps
        under CLEVER infill, and the value a stale-mode straggler keeps
        re-submitting (a worker late k steps in a row re-sends the same
        gradient k times).  ``secure_info`` (None unless ``secure``)
        carries the per-local-worker submitted/received digests and the
        forge/reject verdicts — what the host-side authenticator signs and
        verifies one dispatch behind (secure/submit.py).  ``ef`` is the
        local (k, d) error-feedback shard when the codec carries it;
        ``new_ef`` the updated residuals (None otherwise).
        """
        from ..secure.submit import FORGE_SCALE, row_digest, tamper_row

        k = self.workers_per_device
        didx = jax.lax.axis_index(worker_axis)
        chaos_forgery = self.chaos is not None and self.chaos.has_forgery
        out = []
        carry_rows = []  # post-transport, PRE-forgery (see carry note below)
        ef_rows = [] if ef is not None else None
        sec = {"digest_sent": [], "digest_recv": [], "forged": [], "rejected": []}
        for j in range(k):
            gidx = didx * k + j
            g = gvecs[j]
            wkey = jax.random.fold_in(key, gidx)
            previous = carry[j] if carry is not None else None
            if self.attack is not None and not self.attack.omniscient:
                forged = self.attack.apply_local(g, jax.random.fold_in(wkey, 1))
                g = jnp.where(gidx < self.nb_real_byz, forged, g)
            if self.chaos is not None and self.chaos.has_local_attacks:
                forged = self.chaos.apply_local_attacks(ridx, g, jax.random.fold_in(wkey, 1))
                g = jnp.where(gidx < self.nb_real_byz, forged, g)
            if self.codec is not None:
                # THE WIRE (parallel/compress.py): the row is encoded here
                # — after the worker-local attacks (an attacker forges what
                # it transmits; its forgery crosses the same lossy wire)
                # and BEFORE the transport faults below, so packet-loss NaN
                # masking lands on the DECODED image (a dropped packet of
                # int8 payload is still a NaN coordinate run —
                # parallel/lossy.py).  From here on, ``g`` is the wire
                # image: what the aggregator's decoder emits.
                if ef is not None:
                    g, new_ef_row = self.codec.ef_roundtrip(g, ef[j])
                    ef_rows.append(new_ef_row)
                else:
                    g = self.codec.roundtrip(g)
            if self.lossy_link is not None:
                g = self.lossy_link.apply(g, jax.random.fold_in(wkey, 2), gidx, previous=previous)
            if self.chaos is not None:
                if self.chaos.has_drop:
                    # chaos loss storms hit EVERY worker (link sized n); the
                    # rate is the regime's traced scalar — no recompilation
                    g = self.chaos.link.apply(
                        g, jax.random.fold_in(wkey, 2), gidx,
                        drop_rate=self.chaos.drop_rate(ridx),
                    )
                if self.chaos.has_stragglers:
                    late = self.chaos.stragglers.is_late(
                        wkey, gidx, self.chaos.straggler_rate(ridx)
                    )
                    g = self.chaos.stragglers.apply(
                        g, late, self.chaos.straggler_stale(ridx), previous=previous
                    )
            # The carry captures the row HERE — post-transport, PRE-forgery
            # (the sharded engine's convention): a stale straggler re-sends
            # the worker's own last submission, not the impostor's noise or
            # the aggregator's NaN rejection (a rejected step must not leak
            # extra NaN rows into later steps' f accounting).
            carry_rows.append(g)
            # Submission forgery pipeline (docs/security.md).  Order matters:
            # an impersonator REPLACES the submission (and will sign it with
            # a key it does not have), the sender-side digest covers what was
            # submitted, tampering corrupts bits AFTER signing, the receiver
            # digests what arrived — and under ``secure`` a row whose tag
            # cannot verify is rejected to NaN before stacking (absorbed by
            # the GARs within the same f budget as a lossy row).  Fold tags
            # 5/6 keep the forge/tamper streams disjoint from attack (1),
            # lossy (2), augment (3) and sampling (4).
            is_forge = is_tamper = None
            if chaos_forgery:
                fkey = jax.random.fold_in(wkey, 5)
                is_forge = (gidx < self.nb_real_byz) & jax.random.bernoulli(
                    fkey, self.chaos.forge_rate(ridx)
                )
                impostor = jax.random.normal(
                    jax.random.fold_in(fkey, 1), g.shape, g.dtype
                ) * jnp.asarray(FORGE_SCALE, g.dtype)
                g = jnp.where(is_forge, impostor, g)
            sent_digest = None
            if self.secure:
                sent_digest = row_digest(g)
                sec["digest_sent"].append(sent_digest)
            if chaos_forgery:
                tkey = jax.random.fold_in(wkey, 6)
                is_tamper = (gidx < self.nb_real_byz) & jax.random.bernoulli(
                    tkey, self.chaos.tamper_rate(ridx)
                )
                g = jnp.where(is_tamper, tamper_row(g, jax.random.fold_in(tkey, 1)), g)
            if self.secure:
                # without in-transit transforms the received bytes ARE the
                # submitted bytes — reuse the checksum instead of paying a
                # second O(d) pass (half the digest tax of the common case)
                sec["digest_recv"].append(
                    row_digest(g) if chaos_forgery else sent_digest
                )
                forged_flag = is_forge if is_forge is not None else jnp.bool_(False)
                rejected = forged_flag
                if is_tamper is not None:
                    rejected = rejected | is_tamper
                sec["forged"].append(forged_flag)
                sec["rejected"].append(rejected)
                g = jnp.where(rejected, jnp.nan, g)
            out.append(g)
        stacked = jnp.stack(out, axis=0)
        carry = jnp.stack(carry_rows, axis=0) if self.carries_gradients else None
        secure_info = None
        if self.secure:
            secure_info = {
                key_: jnp.stack(values) for key_, values in sec.items()
            }
        new_ef = jnp.stack(ef_rows, axis=0) if ef_rows is not None else None
        return stacked, carry, secure_info, new_ef

    def _block_width(self, d):
        """Columns of one device's block of the (n, d) gradient matrix.

        On one device the block is the rows themselves.  Over ``W`` devices
        it is the smallest multiple of the kernels' column tile
        (``ops/pallas_kernels``: ``MAX_BLOCK``, or ``LANE`` where the block
        is narrower than that) holding ``ceil(d / W)`` columns: every block
        then starts on a lane boundary of the rows' (8, 128) tiles, so the
        cut moves whole tiles (a bitcast at k = 8, where a block cut off
        the lane boundary is relaid element by element), and the kernels
        find the width they would pad to."""
        W = self.nb_devices
        blk = -(-d // W)
        if W > 1:
            blk += (-blk) % (MAX_BLOCK if blk >= MAX_BLOCK else LANE)
        return blk

    def _reshard_to_blocks(self, gvecs, d):
        """(k, d) worker-sharded -> (n, d_block) dimension-sharded column
        block: device w gets columns ``[w * blk, (w + 1) * blk)`` of every
        row, ``blk = _block_width(d)``, the columns past ``d`` zero (they
        add nothing to a distance and aggregate to zero under every rule;
        the gather cuts them off)."""
        W = self.nb_devices
        if self.exchange_dtype is not None:
            gvecs = gvecs.astype(self.exchange_dtype)
        if W == 1:
            return gvecs
        padded = jnp.pad(gvecs, ((0, 0), (0, W * self._block_width(d) - d)))
        return jax.lax.all_to_all(padded, worker_axis, split_axis=1, concat_axis=0, tiled=True)

    def _gather_blocks(self, agg_block, d):
        """(d_block,) aggregated column block of every device -> the (d,)
        vector: block w holds columns ``[w * blk, (w + 1) * blk)``, so the
        blocks in device order are the padded row."""
        if self.nb_devices == 1:
            return agg_block[:d]
        return jax.lax.all_gather(agg_block, worker_axis, axis=0).reshape(-1)[:d]

    def _prepare_rows(self, rows, attack_key, reputation, ridx=None):
        """The ORDER-SENSITIVE shared front of both aggregation paths:
        omniscient attack -> requantize forged rows -> quarantine mask.

        Returns ``(rows, raw_rows)``: what the rule consumes and the
        post-attack PRE-quarantine rows the reputation signal measures.
        The quarantine mask applies AFTER the omniscient attack so the
        reputation signal sees what attackers actually submitted (masking
        earlier would measure the attacker's honest gradient and never
        suspect it); forged rows are squeezed through the exchange dtype
        because they crossed the same wire as honest ones."""
        forged = False
        if self.attack is not None and self.attack.omniscient:
            byz_mask = jnp.arange(self.nb_workers) < self.nb_real_byz
            rows = self.attack.apply_matrix(rows, byz_mask, attack_key)
            forged = True
        if self.chaos is not None and self.chaos.has_omniscient_attacks:
            byz_mask = jnp.arange(self.nb_workers) < self.nb_real_byz
            rows = self.chaos.apply_omniscient_attacks(ridx, rows, byz_mask, attack_key)
            forged = True
        if forged:
            # forged rows crossed the same quantized wire as honest ones —
            # the one helper owning the precision-loss semantics
            from .compress import wire_roundtrip

            rows = wire_roundtrip(rows, dtype=self.exchange_dtype, codec=self.codec)
        raw_rows = rows
        if self.quarantine_threshold:
            qmask = quarantine_mask(
                reputation, self.quarantine_threshold, self.gar.nb_byz_workers
            )
            rows = jnp.where(qmask[:, None], jnp.nan, rows)
        return rows, raw_rows

    def _aggregate_block(self, block, key, reputation=None, ridx=None):
        """Omniscient attack, quarantine gate, distances (psum), blockwise GAR.

        Returns ``(agg_block, participation, block, raw_block)`` — the (n,)
        worker participation (or None; computed only under
        ``worker_metrics``), the post-quarantine ``block`` the rule actually
        consumed, and the post-attack PRE-quarantine ``raw_block`` the
        reputation signal measures."""
        block, raw_block = self._prepare_rows(block, key, reputation, ridx=ridx)
        dist2 = None
        if self.gar.needs_distances:
            partial = _partial_pairwise_sq_distances(block)
            dist2 = jax.lax.psum(partial, worker_axis) if self.nb_devices > 1 else partial
            dist2 = jnp.maximum(dist2, 0.0)
        axis = worker_axis if self.nb_devices > 1 else None
        # Replicated per-step key for randomized meta-rules (bucketing's
        # permutation); the reserved tag keeps it disjoint from the
        # per-worker attack/lossy streams.
        from ..gars import GAR_KEY_TAG

        gar_key = jax.random.fold_in(key, GAR_KEY_TAG)
        if self.worker_metrics:
            agg, participation = self.gar.aggregate_block_and_participation(
                block, dist2, axis_name=axis, key=gar_key
            )
            return agg, participation, block, raw_block
        agg = self.gar._call_aggregate(block, dist2, axis_name=axis, key=gar_key)
        return agg, None, block, raw_block

    def _aggregate_per_leaf(self, gvecs, flatmap, key, reputation, ridx=None):
        """granularity:leaf — gather and reduce each leaf's (n, d_leaf) rows
        independently (per-layer selection), BUCKETED by leaf size.

        Same-sized leaves are stacked into one (L, n, d_leaf) tensor and
        reduced by a single vmapped rule call behind a single all_gather —
        so a ResNet-50 (~160 leaves, ~dozens of distinct shapes) traces
        O(#distinct sizes) collectives and selection graphs instead of
        O(#leaves) (the compile-time/step-latency blowup VERDICT r2 flagged;
        same stacking trick as the sharded dataflow's layer axis,
        ``_make_sharded_body``).  Each leaf's PRNG keys fold in its ORIGINAL
        leaf index, so a leaf's selection does not depend on which leaves
        share its size; tests/test_engine.py holds one step to the numpy
        oracle applied leaf by leaf.

        Returns ``(agg, participation, wdist, rep_dist)``: the concatenated
        (d,) aggregate (identical on every device), the mean per-leaf
        participation (or None), and the full per-worker squared distances
        to the aggregate over the post-quarantine and raw rows respectively
        (None unless the corresponding feature is on).  No psums needed:
        every device sees complete rows."""
        from ..gars import GAR_KEY_TAG
        from ..gars.common import pairwise_sq_distances

        W = self.nb_devices
        base_key = jax.random.fold_in(key, GAR_KEY_TAG)
        participation_sum = jnp.zeros((self.nb_workers,), jnp.float32)
        participation_count = 0
        wdist = jnp.zeros((self.nb_workers,), jnp.float32) if self.worker_metrics else None
        rep_dist = (
            jnp.zeros((self.nb_workers,), jnp.float32)
            if self.reputation_decay is not None else None
        )

        buckets = {}  # size -> list of (leaf_index, offset), flattening order
        for i, (_, offset, size, _, _) in enumerate(flatmap.slices):
            buckets.setdefault(size, []).append((i, offset))

        concat_parts = []  # per-bucket (L * size,) aggregates
        perm = np.empty((flatmap.size,), np.int32)  # output slot -> concat slot
        pos = 0
        for size, entries in buckets.items():
            idxs = jnp.asarray([i for i, _ in entries], jnp.int32)
            local = jnp.stack(
                [gvecs[:, off:off + size] for _, off in entries], axis=0
            )  # (L, k, size) — static slices, one tensor on the wire
            if self.exchange_dtype is not None:
                local = local.astype(self.exchange_dtype)  # wire precision
            if W > 1:
                gathered = jax.lax.all_gather(local, worker_axis)  # (W, L, k, size)
                rows = gathered.transpose(1, 0, 2, 3).reshape(
                    len(entries), self.nb_workers, size
                )
            else:
                rows = local
            rows = rows.astype(jnp.float32)

            def per_leaf(leaf_rows, leaf_index):
                prep_key = jax.random.fold_in(key, 20_000 + leaf_index)
                leaf_rows, raw_rows = self._prepare_rows(leaf_rows, prep_key, reputation, ridx=ridx)
                dist2 = (
                    jnp.maximum(pairwise_sq_distances(leaf_rows), 0.0)
                    if self.gar.needs_distances else None
                )
                leaf_key = jax.random.fold_in(base_key, leaf_index)
                if self.worker_metrics:
                    agg_leaf, part = self.gar.aggregate_block_and_participation(
                        leaf_rows, dist2, axis_name=None, key=leaf_key
                    )
                else:
                    agg_leaf = self.gar._call_aggregate(
                        leaf_rows, dist2, axis_name=None, key=leaf_key
                    )
                    part = None
                return agg_leaf.astype(jnp.float32), part, leaf_rows, raw_rows

            # (vmapped rule calls: the Pallas auto-tier detects the
            # batching trace centrally and stays on jnp — gars/common.py
            # _is_batched_tracer)
            aggs, parts, prep_rows, raw_rows = jax.vmap(per_leaf)(rows, idxs)
            if parts is not None:
                participation_sum = participation_sum + jnp.sum(parts, axis=0)
                participation_count += len(entries)
            if wdist is not None:
                diff = prep_rows - aggs[:, None, :]
                wdist = wdist + jnp.sum(diff * diff, axis=(0, 2))
            if rep_dist is not None:
                rdiff = raw_rows - aggs[:, None, :]
                rep_dist = rep_dist + jnp.sum(rdiff * rdiff, axis=(0, 2))
            concat_parts.append(aggs.reshape(-1))
            for j, (_, off) in enumerate(entries):
                perm[off:off + size] = np.arange(
                    pos + j * size, pos + (j + 1) * size, dtype=np.int32
                )
            pos += len(entries) * size

        if not concat_parts:
            return jnp.zeros((0,), jnp.float32), None, wdist, rep_dist
        agg = jnp.concatenate(concat_parts)[perm]  # back to flattening order
        participation = (
            participation_sum / participation_count if participation_count else None
        )
        return agg, participation, wdist, rep_dist

    # ------------------------------------------------------------------ #
    # the step epilogue — ONE implementation for both dataflows

    def _finalize_step(self, state, *, params, opt_state, new_carry,
                       new_momentum, new_momentum_steps, total_loss,
                       update_norm, worker_nan, rep_dist, wdist,
                       participation, secure_metrics, ridx, new_ef=None):
        """Everything after the optimizer update, shared by the flat and the
        sharded step bodies (and the bounded-wait aggregator): reputation
        EMA, health probe, the metrics dict, and the flight-recorder write.
        Callers pass values that are already replicated/psum-completed for
        their dataflow; this method adds no collectives."""
        new_reputation = state.reputation
        if self.reputation_decay is not None:
            # Rank signal on the RAW submissions (post-ALL-attacks,
            # pre-quarantine): 1 if among the n-f closest to the applied
            # aggregate AND finite — NaN-infilled lossy rows read +inf
            # -> signal 0 (the finiteness gate stops +inf index-ties
            # from boosting low-index dead workers).
            from ..gars.common import nonfinite_to_inf, smallest_k_mask

            signal = smallest_k_mask(
                nonfinite_to_inf(rep_dist),
                self.nb_workers - self.gar.nb_byz_workers,
            ).astype(jnp.float32) * jnp.isfinite(rep_dist).astype(jnp.float32)
            beta = self.reputation_decay
            new_reputation = beta * state.reputation + (1.0 - beta) * signal
        new_loss_ema = state.loss_ema
        probe_fields = None
        if self.health_probe:
            from ..guardian import probe as health

            probe_fields = health.probe_metrics(
                total_loss, update_norm,
                health.spike_score(total_loss, state.loss_ema), worker_nan,
            )
            new_loss_ema = health.update_loss_ema(state.loss_ema, total_loss)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state,
            carry=new_carry, momentum=new_momentum,
            momentum_steps=new_momentum_steps,
            reputation=new_reputation, loss_ema=new_loss_ema,
            ef=new_ef if self.carries_ef else state.ef,
        )
        metrics = {
            "total_loss": total_loss,
            "grad_norm": update_norm,
        }
        if probe_fields is not None:
            from ..guardian import probe as health

            metrics[health.PROBE_KEY] = probe_fields
        if secure_metrics is not None:
            metrics["secure"] = secure_metrics
        if ridx is not None:
            # replicated scalar (a pure function of the replicated step)
            # — the observability layer's regime column
            metrics["chaos_regime"] = ridx
        if self.worker_metrics:
            # Suspicion diagnostics: squared distance of each worker's
            # gradient to the aggregate (universal), plus the rule's own
            # per-worker participation weight when it selects by worker.
            metrics["worker_sq_dist"] = wdist
            if participation is not None:
                metrics["worker_participation"] = participation
            if self.reputation_decay is not None:
                metrics["worker_reputation"] = new_reputation
                if self.quarantine_threshold:
                    metrics["nb_quarantined"] = jnp.sum(
                        quarantine_mask(
                            state.reputation, self.quarantine_threshold,
                            self.gar.nb_byz_workers,
                        ).astype(jnp.int32)
                    )
        if self.flight is not None:
            # In-scan flight-recorder write (obs/flight.py): each lane
            # stores the exact traced value the metrics dict carries,
            # so ring rows are bit-identical to per-step metrics by
            # construction.
            new_state = new_state.replace(
                flight=self.flight.record(state.flight, state.step, metrics)
            )
        return new_state, metrics

    # ------------------------------------------------------------------ #
    # the flat dataflow

    def _state_spec(self):
        """PartitionSpec prefix tree for TrainState: everything replicated
        except the worker-sharded side buffers (CLEVER carry, momentum)."""
        return TrainState(
            step=P(),
            params=P(),
            opt_state=P(),
            rng=P(),
            carry=P(worker_axis) if self.carries_gradients else None,
            momentum=P(worker_axis) if self.worker_momentum is not None else None,
            momentum_steps=P() if self.worker_momentum is not None else None,
            reputation=P() if self.reputation_decay is not None else None,
            loss_ema=P() if self.health_probe else None,
            flight=P() if self.flight is not None else None,
            ef=P(worker_axis) if self.carries_ef else None,
        )

    def _flat_out_shardings(self):
        """Explicit jit out_shardings for the flat builders: pin the output
        state to the ``_state_spec`` layout.  Without this the compiler
        canonicalizes size-1 mesh axes to replicated specs, so a run with
        a worker-sharded side buffer (momentum, CLEVER carry, the codec's
        error-feedback residual) would see a differently-committed state
        on its SECOND dispatch and retrace once — the same fix the sharded
        builders ship (see ``_sharded_build_step``)."""
        state_shardings = jax.tree.map(
            lambda spec: None if spec is None else NamedSharding(self.mesh, spec),
            self._state_spec(), is_leaf=_is_spec,
        )
        return (state_shardings, NamedSharding(self.mesh, P()))

    @property
    def gradient_path(self):
        """``"in place"`` where the flat step reduces the gradient leaves where
        they lie (parallel/in_place.py), ``"rows"`` where it lays them out as
        the (n, d) matrix of the module docstring; fixed when the engine was
        built."""
        return "in place" if self._rows_reason is None else "rows"

    def _make_flat_body(self, loss_fn, tx):
        """The per-step SPMD body shared by build_step and build_multi_step:
        the in-place path's (parallel/in_place.py) for an engine in which
        nothing needs the rows, else the rows path's, below.  Which one is
        logged once a build."""
        if self._rows_reason is None:
            from .in_place import make_body

            return make_body(self, loss_fn, tx)
        info("step lays gradients out as (n, d) rows: needed by %s" % self._rows_reason)
        W = self.nb_devices

        def body(state, batch):
            key = jax.random.fold_in(state.rng, state.step)
            # Active chaos regime for THIS step: a traced array index into
            # the schedule's compiled knob vectors, so regime switches land
            # at exactly their scheduled step with zero recompilation.
            ridx = self.chaos.regime_index(state.step) if self.chaos is not None else None
            if self.batch_transform is not None:
                k = self.workers_per_device
                didx = jax.lax.axis_index(worker_axis)

                def aug_one(worker_batch, j):
                    # fold tag 3: disjoint from the attack (1) / lossy (2)
                    # streams derived from the same (key, global worker) pair
                    wkey = jax.random.fold_in(jax.random.fold_in(key, didx * k + j), 3)
                    return self.batch_transform(worker_batch, wkey)

                with phase("augment"):
                    batch = jax.vmap(aug_one)(batch, jnp.arange(k))
            losses, gvecs, flatmap, counters = self._worker_gradients(
                state.params, batch, loss_fn)
            if self.codec is not None:
                # the codec budget is validated at the first trace, which
                # is also every guardian-escalation rebuild
                self.codec.validate_d(gvecs.shape[-1])
            new_momentum, new_momentum_steps = None, None
            with phase("perturb"):
                if self.worker_momentum is not None:
                    # Honest workers send momenta (computed BEFORE the attack:
                    # attackers forge what they transmit, not what honest peers
                    # remember).  Bias-corrected like Adam so early steps are not
                    # (1-beta)-scaled relative to plain gradients; the correction
                    # counts momentum updates, NOT the global step — the buffer
                    # re-zeroes on restore and its warmup must restart with it.
                    beta = self.worker_momentum
                    new_momentum = beta * state.momentum + (1.0 - beta) * gvecs
                    new_momentum_steps = state.momentum_steps + 1
                    gvecs = new_momentum / (1.0 - beta ** new_momentum_steps.astype(jnp.float32))
                gvecs, new_carry, secure_info, new_ef = self._perturb_local(
                    gvecs, key, carry=state.carry, ridx=ridx,
                    ef=state.ef if self.carries_ef else None,
                )
            d = gvecs.shape[-1]
            if self.granularity == "leaf":
                with phase("gar"):
                    agg, participation, wdist, rep_dist = self._aggregate_per_leaf(
                        gvecs, flatmap, key, state.reputation, ridx=ridx
                    )
            else:
                with phase("reshard"):
                    block = self._reshard_to_blocks(gvecs, d)
                    if self.exchange_dtype is not None:
                        block = block.astype(jnp.float32)  # GAR math always in f32
                with phase("gar"):
                    agg_block, participation, seen_block, raw_block = self._aggregate_block(
                        block, key, reputation=state.reputation, ridx=ridx
                    )
                with phase("gather"):
                    if self.exchange_dtype is not None:
                        agg_block = agg_block.astype(self.exchange_dtype)  # wire, leg 2
                    agg = self._gather_blocks(agg_block, d).astype(jnp.float32)
                wdist = rep_dist = None
                with phase("epilogue"):
                    if self.worker_metrics:
                        # distances over what the aggregator actually saw
                        # (post-attack, post-lossy, post-quarantine)
                        diff = seen_block - agg_block[None, :]
                        wdist = jnp.sum(diff * diff, axis=1)
                        if W > 1:
                            wdist = jax.lax.psum(wdist, worker_axis)
                    if self.reputation_decay is not None:
                        rdiff = raw_block - agg_block.astype(jnp.float32)[None, :]
                        rep_dist = jnp.sum(rdiff * rdiff, axis=1)
                        if W > 1:
                            rep_dist = jax.lax.psum(rep_dist, worker_axis)
            with phase("apply"):
                agg_tree = flatmap.inflate(agg)
                updates, opt_state = tx.update(agg_tree, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            with phase("epilogue"):
                total_loss = jax.lax.psum(jnp.sum(losses), worker_axis) if W > 1 else jnp.sum(losses)
                worker_nan = None
                if self.health_probe:
                    # Per-worker NaN-row flags measure the POST-TRANSPORT
                    # submissions (what the aggregation actually received:
                    # lossy NaN infill, dropped stragglers, inf attacks) —
                    # distinct from loss_finite, which measures model health.
                    local_bad = jnp.any(~jnp.isfinite(gvecs), axis=1)  # (k,)
                    if W > 1:
                        worker_nan = jax.lax.all_gather(local_bad, worker_axis).reshape(
                            self.nb_workers
                        )
                    else:
                        worker_nan = local_bad
                secure_metrics = None
                if secure_info is not None:
                    # Submission authentication material for the host-side
                    # sign/verify (secure/submit.py): per-worker digests of what
                    # was submitted vs received, plus the forge/reject verdicts.
                    # Gathered worker-major like the probe's NaN flags.
                    def gather_workers(local):
                        if W > 1:
                            gathered = jax.lax.all_gather(local, worker_axis)
                            return gathered.reshape((self.nb_workers,) + local.shape[1:])
                        return local

                    secure_metrics = {
                        name: gather_workers(value)
                        for name, value in secure_info.items()
                    }
                new_state, metrics = self._finalize_step(
                    state, params=params, opt_state=opt_state, new_carry=new_carry,
                    new_momentum=new_momentum, new_momentum_steps=new_momentum_steps,
                    total_loss=total_loss, update_norm=jnp.linalg.norm(agg),
                    worker_nan=worker_nan, rep_dist=rep_dist, wdist=wdist,
                    participation=participation, secure_metrics=secure_metrics,
                    ridx=ridx, new_ef=new_ef,
                )
                if counters is not None:
                    # the model's own counters, one value a worker, worker-major
                    metrics["model_counters"] = jax.tree.map(
                        lambda c: (jax.lax.all_gather(c, worker_axis).reshape(self.nb_workers)
                                   if W > 1 else c), counters)
                return new_state, metrics

        return body

    def _flat_build_step(self, loss_fn, tx):
        """Build the jitted robust training step.

        Args:
          loss_fn: (params, worker_batch) -> scalar loss.
          tx: optax GradientTransformation.
        Returns:
          step(state, batch) -> (state, metrics) with ``batch`` pytrees of
          leading dimension nb_workers (worker-major), sharded over the mesh.
        """
        body = self._make_flat_body(loss_fn, tx)
        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self._state_spec(), P(worker_axis)),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        # The span wrapper is HOST-side only (obs/trace.py): it never touches
        # the jitted callable, so the compile count is identical with tracing
        # on or off (tests/test_obs.py asserts), and attribute access
        # (``_cache_size``) falls through to the jit.
        return trace.traced(
            "train_step.dispatch",
            jax.jit(_revised(sharded), donate_argnums=(0,),
                    out_shardings=self._flat_out_shardings()),
            cat="train",
        )

    def _flat_build_multi_step(self, loss_fn, tx, repeat_steps=None):
        """Build a jitted K-step trainer: one dispatch runs a whole scan.

        Per-step host dispatch dominates wall time for small models (the
        reference pays this as a full PS round-trip per `sess.run`,
        runner.py:562-576); scanning K steps inside one executable removes
        it. Metrics come back per step (leading K).

        Two forms:
        - ``repeat_steps=None``: ``multi(state, batches)`` with every batch
          leaf leading (K, nb_workers, ...) — K distinct batches.
        - ``repeat_steps=K``: ``multi(state, batch)`` reuses one
          device-resident worker-major batch for K steps (no K-fold host
          transfer; what the throughput bench uses).
        """
        step_body = self._make_flat_body(loss_fn, tx)

        if repeat_steps is None:

            def many(state, batches):
                return jax.lax.scan(step_body, state, batches)

            batch_spec = P(None, worker_axis)
        else:

            def many(state, batch):
                return jax.lax.scan(
                    lambda s, _: step_body(s, batch), state, None, length=int(repeat_steps)
                )

            batch_spec = P(worker_axis)

        sharded = jax.shard_map(
            many,
            mesh=self.mesh,
            in_specs=(self._state_spec(), batch_spec),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return trace.traced(
            "train_multi_step.dispatch",
            jax.jit(_revised(sharded), donate_argnums=(0,),
                    out_shardings=self._flat_out_shardings()),
            cat="train",
        )

    def build_sampled_multi_step(self, loss_fn, tx, repeat_steps, batch_size):
        """K-step trainer drawing FRESH per-worker batches ON DEVICE each
        step from a device-resident dataset.

        Rationale: no step pays a host->device transfer.  The reference
        streams each worker's batches through a local queue-runner pipeline
        every step (graph.py:251-254 places each worker's input ops on that
        task's CPU; the pipeline itself is the experiment's
        DatasetDataProvider + tf.train.batch + prefetch_queue stack,
        experiments/cnnet.py:127-141); the TPU-native equivalent is to
        transfer the dataset ONCE (CIFAR-10 train is ~0.6 GB in f32 — a few
        percent of HBM) and gather each worker's sampled rows in-graph, so
        every step still trains on a fresh i.i.d.-with-replacement draw (the
        same stream semantics as ``WorkerBatchIterator``,
        datasets.py:318-325).

        Returns ``multi(state, data) -> (state, metrics)`` where ``data`` is
        the dataset pytree (e.g. ``{"image": x_train, "label": y_train}``),
        placed replicated via :meth:`replicate`.  Worker w's step-s draw is
        a pure function of ``(state.rng, s, w)`` — independent of the mesh
        layout, reproducible across restores, and disjoint (fold tag 4) from
        the attack (1) / lossy (2) / augment (3) streams derived from the
        same key.  Device-side augmentation (``batch_transform``) composes
        unchanged: it runs inside the step body on the sampled batch.
        """
        step_body = self._make_flat_body(loss_fn, tx)
        k = self.workers_per_device
        nb_steps = int(repeat_steps)
        batch_size = int(batch_size)

        def many(state, data):
            nb_examples = jax.tree_util.tree_leaves(data)[0].shape[0]

            def sampled_body(s, _):
                key = jax.random.fold_in(s.rng, s.step)
                didx = jax.lax.axis_index(worker_axis)

                def draw(j):
                    # fold tag 4: the data-sampling stream, disjoint from
                    # attack (1) / lossy (2) / augment (3)
                    wkey = jax.random.fold_in(
                        jax.random.fold_in(key, didx * k + j), 4
                    )
                    idx = jax.random.randint(wkey, (batch_size,), 0, nb_examples)
                    return jax.tree_util.tree_map(lambda a: a[idx], data)

                with phase("sample"):
                    batch = jax.vmap(draw)(jnp.arange(k))
                return step_body(s, batch)

            return jax.lax.scan(sampled_body, state, None, length=nb_steps)

        sharded = jax.shard_map(
            many,
            mesh=self.mesh,
            in_specs=(self._state_spec(), P()),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return trace.traced(
            "train_sampled_multi_step.dispatch",
            jax.jit(_revised(sharded), donate_argnums=(0,),
                    out_shardings=self._flat_out_shardings()),
            cat="train",
        )

    def _flat_build_gar_probe(self, d, seed=0):
        """Jitted GAR-only executable at the engine's exact (n, d) and
        sharding — the measurement instrument behind the runner's
        ``gar_seconds_total`` / ``gar.aggregate`` telemetry.

        Returns ``probe(step)``: one full aggregation (psum-completed
        distances + the rule's blockwise reduction — the same path the
        compiled train step runs in phase 5/6 of the module docstring) over
        a persistent synthetic device-resident row matrix.  Attacks, lossy
        links and quarantine are deliberately excluded: the probe times the
        RULE at the run's real (n, d), not the adversity simulation.  The
        caller times ``jax.block_until_ready(probe(step))``; ``step`` folds
        into the rule key so randomized meta-rules (bucketing/hier) redraw
        like they do in training."""
        from ..gars import GAR_KEY_TAG

        W = self.nb_devices
        blk = self._block_width(int(d))
        # Generate the synthetic rows ON DEVICE under jit with an explicit
        # output sharding: GSPMD shards the generation itself, so the host
        # never materializes the (n, d) matrix (n x the model footprint at
        # the large n the probe exists to measure).
        make_rows = jax.jit(
            lambda k: jax.random.normal(k, (self.nb_workers, W * blk), jnp.float32),
            out_shardings=jax.sharding.NamedSharding(self.mesh, P(None, worker_axis)),
        )
        rows = make_rows(jax.random.PRNGKey(seed))

        def body(block, key):
            dist2 = None
            if self.gar.needs_distances:
                partial = _partial_pairwise_sq_distances(block)
                dist2 = jax.lax.psum(partial, worker_axis) if W > 1 else partial
                dist2 = jnp.maximum(dist2, 0.0)
            axis = worker_axis if W > 1 else None
            gar_key = jax.random.fold_in(key, GAR_KEY_TAG)
            return self.gar._call_aggregate(block, dist2, axis_name=axis, key=gar_key)

        sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(None, worker_axis), P()),
            out_specs=P(worker_axis),
            check_vma=False,
        )
        fn = jax.jit(sharded)
        base = jax.random.PRNGKey(seed)

        def probe(step=0):
            return fn(rows, jax.random.fold_in(base, step))

        return probe

    def build_eval_sums(self, metric_fn):
        """Build the jitted evaluation step returning (sum, count) accumulators.

        Exact full-split metrics need sums accumulated across *all* eval
        batches before dividing (the reference evaluates the whole test set in
        one graph pass, experiments/mnist.py:136-148; here the host loop
        accumulates per-batch device sums instead).

        Args:
          metric_fn: (params, worker_batch) -> dict name -> (sum, count).
        Returns:
          eval_step(state, batch) -> dict name -> (sum, count) over the batch.
        """
        W = self.nb_devices

        def body(state, batch):
            sums = jax.vmap(lambda b: metric_fn(state.params, b))(batch)
            folded = jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), sums)
            if W > 1:
                folded = jax.lax.psum(folded, worker_axis)
            return folded

        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self._state_spec(), P(worker_axis)),
            out_specs=P(),
            check_vma=False,
        )
        return trace.traced("eval_step.dispatch", jax.jit(sharded), cat="eval")

    def _flat_build_eval(self, metric_fn):
        """Like ``build_eval_sums`` but divides, returning per-batch means."""
        eval_sums = self.build_eval_sums(metric_fn)

        def means(state, batch):
            folded = eval_sums(state, batch)
            return {name: total / jnp.maximum(count, 1) for name, (total, count) in folded.items()}

        return means

    # ------------------------------------------------------------------ #

    def shard_batch(self, batch):
        """Device_put a worker-major batch pytree with the worker sharding."""
        spec = jax.sharding.NamedSharding(self.mesh, P(worker_axis))
        return jax.device_put(batch, spec)

    def shard_batches(self, batches):
        """Device_put a (K, nb_workers, ...) batch stack for build_multi_step.

        The step axis is unsharded, so this also places a chunk SLICE
        ((k_i, nb_workers, ...) for any k_i) — the input pipeline
        (models/datasets.py ChunkPipeline) issues one such transfer per
        slice and re-joins them with :meth:`assemble_batches`."""
        spec = jax.sharding.NamedSharding(self.mesh, P(None, worker_axis))
        return jax.device_put(batches, spec)

    def assemble_batches(self, parts):
        """Concatenate step-axis chunk slices (each ``shard_batches``-placed)
        into the one (K, nb_workers, ...) device chunk ``build_multi_step``
        consumes.  Jitted (cached per slice count), so after the first chunk
        this is a single device-side executable whose output is a FRESH
        buffer — the input pipeline's host ping-pong buffers are safe to
        reuse once it has run, even if a backend aliased a ``device_put``."""
        fn = self._assemble_cache.get(len(parts))
        if fn is None:
            fn = jax.jit(lambda *xs: jax.tree_util.tree_map(
                lambda *leaves: jnp.concatenate(leaves, axis=0), *xs))
            self._assemble_cache[len(parts)] = fn
        return fn(*parts)

    def replicate(self, tree):
        """Device_put a pytree fully replicated over the mesh."""
        spec = jax.sharding.NamedSharding(self.mesh, P())
        return jax.device_put(tree, spec)

    def _worker_sharded(self, array_or_none, d=None):
        """Device_put (or create zeroed) a (nb_workers, d) worker-sharded buffer."""
        spec = jax.sharding.NamedSharding(self.mesh, P(worker_axis))
        if array_or_none is not None:
            return jax.device_put(array_or_none, spec)
        return jax.jit(lambda: jnp.zeros((self.nb_workers, d), jnp.float32), out_shardings=spec)()

    def _flat_put_state(self, state):
        """Device_put a TrainState with the engine's state sharding — fully
        replicated except the worker-sharded side buffers (restore path)."""
        carry, momentum, ef = state.carry, state.momentum, state.ef
        placed = self.replicate(state.replace(carry=None, momentum=None, ef=None))
        if carry is not None:
            carry = self._worker_sharded(carry)
        if momentum is not None:
            momentum = self._worker_sharded(momentum)
        if ef is not None:
            ef = self._worker_sharded(ef)
        return placed.replace(carry=carry, momentum=momentum, ef=ef)

    def _flat_init_state(self, params, tx, seed=0):
        """Create a replicated TrainState, plus zeroed worker-sharded side
        buffers when enabled: the CLEVER carry (packets lost before any
        gradient was received read as zero contributions, like the
        reference's freshly-allocated reassembly buffer) and the per-worker
        momentum."""
        state = self.replicate(TrainState.create(params, tx, rng=jax.random.PRNGKey(seed)))
        d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
        if self.carries_gradients:
            state = state.replace(carry=self._worker_sharded(None, d))
        if self.worker_momentum is not None:
            state = state.replace(
                momentum=self._worker_sharded(None, d),
                momentum_steps=self.replicate(jnp.zeros((), jnp.int32)),
            )
        if self.codec is not None:
            # the codec budget is validated as soon as d is known — which
            # includes every guardian-escalation rebuild
            self.codec.validate_d(d)
        if self.carries_ef:
            # fresh codec state: zero residuals (restore overwrites them —
            # the EF buffer is serialized, unlike carry/momentum)
            state = state.replace(ef=self._worker_sharded(None, d))
        if self.reputation_decay is not None:
            # everyone starts trusted; quarantine only after evidence accrues
            state = state.replace(
                reputation=self.replicate(jnp.ones((self.nb_workers,), jnp.float32))
            )
        if self.health_probe:
            from ..guardian.probe import EMA_UNSET

            state = state.replace(
                loss_ema=self.replicate(jnp.float32(EMA_UNSET))
            )
        if self.flight is not None:
            # empty ring, every slot tagged invalid (step -1)
            state = state.replace(
                flight=self.replicate(self.flight.init_buffers())
            )
        return state

    # ------------------------------------------------------------------ #
    # the leafwise-sharded dataflow (logical worker = (pipe x model) submesh)

    def _sharded_init_state(self, init_fn, specs, tx, seed=0):
        """Create the sharded TrainState.

        Args:
          init_fn: key -> global parameter pytree (e.g. transformer.init_params).
          specs:   matching pytree of PartitionSpecs (transformer.param_specs).
          tx:      optax GradientTransformation.
        """
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs, is_leaf=_is_spec)
        params = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(seed))
        rep = NamedSharding(self.mesh, P())
        # Optimizer state must come out with EXPLICIT NamedShardings: optax
        # buffers that mirror the params (adam's mu/nu, momentum's trace —
        # they share the params' treedef) take the params' layouts, every
        # other allocation (schedule counts etc.) replicates — the
        # spec-deriving build_step reads the layouts off these buffers, so
        # they are stated, not left to ambient-mesh propagation.
        opt_shapes = jax.eval_shape(tx.init, params)
        params_treedef = jax.tree_util.tree_structure(params)
        param_shardings = jax.tree.map(lambda p: p.sharding, params)

        def params_like(node):
            try:
                return jax.tree_util.tree_structure(node) == params_treedef
            except TypeError:
                return False

        if params_treedef.num_leaves == 1:
            # a single-leaf treedef would "match" every leaf, so identify
            # the params-mirroring buffers by shape/dtype identity instead
            only = jax.tree_util.tree_leaves(params)[0]
            opt_shardings = jax.tree.map(
                lambda s: only.sharding
                if (s.shape, s.dtype) == (only.shape, only.dtype) else rep,
                opt_shapes,
            )
        else:
            opt_shardings = jax.tree.map(
                lambda node: param_shardings if params_like(node) else rep,
                opt_shapes, is_leaf=params_like,
            )
        with jax.set_mesh(self.mesh):  # optax allocations need the mesh ambient
            opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(params)

        def per_worker_zeros():
            m_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, P(worker_axis, *tuple(s))),
                specs, is_leaf=_is_spec,
            )
            return jax.jit(
                lambda: jax.tree.map(
                    lambda p: jnp.zeros((self.nb_workers,) + p.shape, jnp.float32), params
                ),
                out_shardings=m_shardings,
            )()

        momentum = momentum_steps = carry = reputation = loss_ema = None
        flight = None
        if self.worker_momentum is not None:
            momentum = per_worker_zeros()
            momentum_steps = jax.device_put(jnp.zeros((), jnp.int32), rep)
        if self.carries_gradients:
            carry = per_worker_zeros()
        if self.reputation_decay is not None:
            reputation = jax.device_put(jnp.ones((self.nb_workers,), jnp.float32), rep)
        if self.health_probe:
            from ..guardian.probe import EMA_UNSET

            loss_ema = jax.device_put(jnp.float32(EMA_UNSET), rep)
        if self.flight is not None:
            # empty replicated ring, every slot tagged invalid (step -1)
            flight = jax.device_put(self.flight.init_buffers(), rep)
        state = TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            params=params,
            opt_state=opt_state,
            rng=jax.device_put(jax.random.PRNGKey(seed), rep),
            carry=carry,
            momentum=momentum,
            momentum_steps=momentum_steps,
            reputation=reputation,
            loss_ema=loss_ema,
            flight=flight,
        )
        # Remember the layout for put_state (checkpoint restore re-sharding).
        self._state_shardings = jax.tree.map(lambda a: a.sharding, state)
        return state

    def _sharded_put_state(self, state):
        """Re-shard a (possibly host-resident) state onto this mesh with the
        layout ``init_state`` established — the checkpoint-restore path
        (cli/runner.py) round-trips state through the host and needs the
        sharded placement back.  Leaves that are already live device arrays
        with the right sharding pass through unchanged."""
        if self._state_shardings is None:
            raise RuntimeError("put_state needs init_state to have run first")
        return jax.tree.map(jax.device_put, state, self._state_shardings)

    def _perturb(self, g, spec, key, widx, previous=None, ridx=None, late=None):
        """Worker-local attack + lossy link + chaos regime on this worker's
        own shard (the sharded twin of ``_perturb_local``'s head; kept
        separate because the PRNG stream is keyed per (worker, leaf) here).

        Returns (perturbed leaf, post-transport leaf) — the latter is what
        "the receiver saw", the stale value a lost packet keeps under CLEVER
        and a stale-mode straggler keeps re-submitting.  ``late`` is the
        worker's per-STEP lateness flag (drawn once in the body, shared by
        every leaf: a late worker misses the deadline for its whole
        gradient).
        """
        flat = g.reshape(-1)
        prev_flat = previous.reshape(-1) if previous is not None else None
        if self.attack is not None and not self.attack.omniscient:
            forged = self.attack.apply_local(flat, jax.random.fold_in(key, 1))
            flat = jnp.where(widx < self.nb_real_byz, forged, flat)
        if self.chaos is not None and self.chaos.has_local_attacks:
            forged = self.chaos.apply_local_attacks(ridx, flat, jax.random.fold_in(key, 1))
            flat = jnp.where(widx < self.nb_real_byz, forged, flat)
        if self.lossy_link is not None:
            flat = self.lossy_link.apply(flat, jax.random.fold_in(key, 2), widx, previous=prev_flat)
        if self.chaos is not None:
            if self.chaos.has_drop:
                flat = self.chaos.link.apply(
                    flat, jax.random.fold_in(key, 2), widx,
                    drop_rate=self.chaos.drop_rate(ridx),
                )
            if late is not None:
                flat = self.chaos.stragglers.apply(
                    flat, late, self.chaos.straggler_stale(ridx), previous=prev_flat
                )
        out = flat.reshape(g.shape)
        return out, out

    def _submission_pipeline(self, g_leaves, key, gidx, ridx):
        """The submission-forgery pipeline on sharded leaves (the tail of
        the flat ``_perturb_local``, re-expressed per leaf): chaos ``forge``
        replaces every leaf of a coalition worker with impostor noise,
        sender digests accumulate over all leaf shards, ``tamper`` flips a
        bit after signing, receiver digests follow, and under ``secure`` a
        rejected worker's every leaf reads NaN.

        Returns ``(g_leaves, secure_local)`` — ``secure_local`` (None unless
        ``secure``) holds the per-LOCAL-worker digests (lane sums over this
        device's shards; the body psum-completes them within the worker
        group) and the forge/reject verdicts.
        """
        from ..secure.submit import (
            DIGEST_LANES,
            FORGE_SCALE,
            row_digest,
            tamper_row,
        )

        chaos_forgery = self.chaos is not None and self.chaos.has_forgery
        if not (self.secure or chaos_forgery):
            return g_leaves, None
        k = self.workers_per_device
        out_leaves = [[] for _ in g_leaves]
        sent = jnp.zeros((k, DIGEST_LANES), jnp.uint32)
        recv = jnp.zeros((k, DIGEST_LANES), jnp.uint32)
        forged_flags, rejected_flags = [], []
        for j in range(k):
            widx = gidx * k + j
            # the 32_000+ offset namespace keeps these per-worker streams
            # disjoint from the per-(worker, leaf) perturbation parents and
            # the 30_000+ straggler draws (see the body's key discipline)
            wkey = jax.random.fold_in(key, 32_000 + widx)
            is_forge = is_tamper = None
            if chaos_forgery:
                fkey = jax.random.fold_in(wkey, 5)
                is_forge = (widx < self.nb_real_byz) & jax.random.bernoulli(
                    fkey, self.chaos.forge_rate(ridx)
                )
                tkey = jax.random.fold_in(wkey, 6)
                is_tamper = (widx < self.nb_real_byz) & jax.random.bernoulli(
                    tkey, self.chaos.tamper_rate(ridx)
                )
            forged_flag = is_forge if is_forge is not None else jnp.bool_(False)
            rejected = forged_flag
            if is_tamper is not None:
                rejected = rejected | is_tamper
            sent_j = jnp.zeros((DIGEST_LANES,), jnp.uint32)
            recv_j = jnp.zeros((DIGEST_LANES,), jnp.uint32)
            for i, g in enumerate(g_leaves):
                flat = g[j].reshape(-1).astype(jnp.float32)
                if is_forge is not None:
                    impostor = jax.random.normal(
                        jax.random.fold_in(jax.random.fold_in(fkey, 1), i),
                        flat.shape, flat.dtype,
                    ) * jnp.float32(FORGE_SCALE)
                    flat = jnp.where(is_forge, impostor, flat)
                leaf_digest = None
                if self.secure:
                    # per-leaf salt: leaves must not alias in the checksum
                    leaf_digest = row_digest(flat, salt=i * 0x9E3779B1)
                    sent_j = sent_j + leaf_digest
                if is_tamper is not None and i == 0:
                    # one bit flipped in transit (the first leaf's shard)
                    flat = jnp.where(
                        is_tamper, tamper_row(flat, jax.random.fold_in(tkey, 1)), flat
                    )
                if self.secure:
                    # no in-transit transform on this leaf -> received bytes
                    # are the submitted bytes, reuse the checksum
                    if chaos_forgery and i == 0:
                        leaf_digest = row_digest(flat, salt=i * 0x9E3779B1)
                    recv_j = recv_j + leaf_digest
                    flat = jnp.where(rejected, jnp.nan, flat)
                out_leaves[i].append(flat.reshape(g[j].shape).astype(g.dtype))
            sent = sent.at[j].set(sent_j)
            recv = recv.at[j].set(recv_j)
            forged_flags.append(forged_flag)
            rejected_flags.append(rejected)
        g_leaves = [jnp.stack(rows) for rows in out_leaves]
        if not self.secure:
            return g_leaves, None
        return g_leaves, {
            "digest_sent": sent,
            "digest_recv": recv,
            "forged": jnp.stack(forged_flags),
            "rejected": jnp.stack(rejected_flags),
        }

    def _leaf_buckets(self, g, spec):
        """Reshape a locally worker-stacked (k, ...) leaf to (k, n_buckets,
        d_bucket) rows-to-be."""
        k = g.shape[0]
        if self.granularity == "layer" and spec is not None and len(spec) >= 2 and spec[0] == pipe_axis:
            # Stage-stacked leaf (local stage dim 1, then the scanned layer
            # dim): one bucket per layer.
            return g.reshape(k, g.shape[1] * g.shape[2], -1)
        return g.reshape(k, 1, -1)

    def _gather_rows(self, buckets):
        """(k, Lb, d) local buckets -> (Lb, n, d) per-worker rows via one
        all_gather over the worker axis (worker-major: global worker index
        is group * k + local slot, the same layout the flat dataflow uses)."""
        if self.exchange_dtype is not None:
            buckets = buckets.astype(self.exchange_dtype)
        rows = jax.lax.all_gather(buckets, worker_axis)  # (W, k, Lb, d)
        if self.exchange_dtype is not None:
            rows = rows.astype(jnp.float32)
        rows = rows.reshape((self.nb_workers,) + rows.shape[2:])  # (n, Lb, d)
        return jnp.swapaxes(rows, 0, 1)

    def _apply_omniscient(self, rows, key, ridx=None):
        byz_mask = jnp.arange(self.nb_workers) < self.nb_real_byz
        forged = False
        if self.attack is not None and self.attack.omniscient:
            rows = jax.vmap(lambda m: self.attack.apply_matrix(m, byz_mask, key))(rows)
            forged = True
        if self.chaos is not None and self.chaos.has_omniscient_attacks:
            rows = jax.vmap(
                lambda m: self.chaos.apply_omniscient_attacks(ridx, m, byz_mask, key)
            )(rows)
            forged = True
        if forged:
            # forged rows crossed the same quantized wire as honest ones
            # (sharded mode refuses codecs, so this is the dtype twin —
            # elementwise, shape-agnostic over the bucket stack)
            from .compress import wire_roundtrip

            rows = wire_roundtrip(rows, dtype=self.exchange_dtype)
        return rows

    def _bucket_distances(self, rows, spec):
        """(Lb, n, n) squared distances for this leaf's buckets (exact)."""
        partial = jax.vmap(centered_gram_sq_distances)(rows.astype(jnp.float32))
        if model_axis in _spec_axis_names(spec):
            partial = jax.lax.psum(partial, model_axis)
        return jnp.maximum(partial, 0.0)

    def _replication_scale(self, spec):
        scale = 1.0
        for a in _replication_axes(spec):
            scale /= self.mesh.shape[a]
        return scale

    def _make_sharded_body(self, loss_fn, tx, state_specs):
        """The single-step shard_map body of the leafwise-sharded dataflow,
        shared by its ``build_step`` and ``build_multi_step`` forms."""
        param_specs = state_specs.params
        gar = self.gar
        k = self.workers_per_device

        def body(state, batch):
            key = jax.random.fold_in(state.rng, state.step)
            gidx = jax.lax.axis_index(worker_axis)  # worker-GROUP index
            # Active chaos regime + per-STEP worker lateness (one draw per
            # logical worker, shared by all its leaves).  The lateness key
            # lives in the 30_000+ offset namespace — fold_in(key, widx) is
            # the PARENT of every per-leaf stream (fold i, then tags 1/2),
            # so folding the straggler tag onto it directly would collide
            # with leaf index 5's stream (same convention as the 10_000+i /
            # 20_000+i offsets the engine uses elsewhere).
            ridx = None
            lates = [None] * k
            if self.chaos is not None:
                ridx = self.chaos.regime_index(state.step)
                if self.chaos.has_stragglers:
                    lates = [
                        self.chaos.stragglers.is_late(
                            jax.random.fold_in(key, 30_000 + gidx * k + j),
                            gidx * k + j,
                            self.chaos.straggler_rate(ridx),
                        )
                        for j in range(k)
                    ]
            with phase("grad"):
                if k == 1:
                    # one logical worker per submesh: the historical (and
                    # bit-proven) unvmapped path — keep it byte-for-byte
                    local = jax.tree.map(lambda x: x[0], batch)  # strip block dim
                    loss, grads = jax.value_and_grad(loss_fn)(state.params, local)
                    losses = loss[None]
                    grads = jax.tree.map(lambda g: g[None], grads)
                else:
                    # k logical workers per submesh (the large-n regime): vmap
                    # the per-worker loss/grad — every leaf leads with k
                    losses, grads = jax.vmap(
                        lambda b: jax.value_and_grad(loss_fn)(state.params, b)
                    )(batch)

                g_leaves, treedef = jax.tree_util.tree_flatten(grads)
                s_leaves = treedef.flatten_up_to(param_specs)

                # (2) complete replicated-leaf grads within the worker group
                g_leaves = [
                    jax.lax.psum(g, _replication_axes(s)) if _replication_axes(s) else g
                    for g, s in zip(g_leaves, s_leaves)
                ]
                # (2a) l1/l2 regularization, analytically on the completed grads
                # (see __init__): part of every worker's HONEST gradient, so it
                # lands before momentum and before the Byzantine perturbation —
                # the flat dataflow's in-loss placement, same math.
                l1, l2 = self.l1_regularize, self.l2_regularize
                if l1 or l2:
                    p_leaves = jax.tree_util.tree_leaves(state.params)
                    reg = jnp.float32(0.0)
                    for i, (p, s) in enumerate(zip(p_leaves, s_leaves)):
                        p32 = p.astype(jnp.float32)
                        delta = jnp.zeros_like(p32)
                        if l1:
                            delta = delta + l1 * jnp.sign(p32)
                            reg = reg + l1 * jnp.sum(jnp.abs(p32)) * self._replication_scale(s)
                        if l2:
                            delta = delta + 2.0 * l2 * p32
                            reg = reg + l2 * jnp.sum(p32 * p32) * self._replication_scale(s)
                        g_leaves[i] = g_leaves[i] + delta.astype(g_leaves[i].dtype)
                    # scaled per-leaf partials psum exactly like the data loss:
                    # the in-group psum in `metrics` then counts the norm once
                    # (every logical worker's loss carries the reg term, the flat
                    # dataflow's per-worker in-loss placement)
                    losses = losses + reg
            with phase("perturb"):
                # (2b) honest worker momentum (pre-attack, like the flat body):
                # send bias-corrected momenta, carry the uncorrected buffer
                new_momentum, new_momentum_steps = state.momentum, state.momentum_steps
                if self.worker_momentum is not None:
                    beta = self.worker_momentum
                    # momentum buffers are worker-sharded: local block (k, ...)
                    m_leaves, _ = jax.tree_util.tree_flatten(state.momentum)
                    new_momentum_steps = state.momentum_steps + 1
                    corr = 1.0 - beta ** new_momentum_steps.astype(jnp.float32)
                    m_new = [beta * m + (1.0 - beta) * g for m, g in zip(m_leaves, g_leaves)]
                    g_leaves = [m / corr for m in m_new]
                    new_momentum = jax.tree_util.tree_unflatten(treedef, m_new)
                # (3) per-worker perturbation of each logical worker's own shards
                # (skipped entirely when no adversity is configured — at k
                # workers per submesh the k-fold loop would otherwise pay trace
                # size for an identity transform)
                carry_leaves = None
                if self.carries_gradients:
                    carry_leaves = jax.tree_util.tree_leaves(state.carry)  # (k, ...)
                new_carry = state.carry
                if (self.attack is not None or self.lossy_link is not None
                        or self.chaos is not None):
                    post_leaves = []
                    for i, (g, s) in enumerate(zip(g_leaves, s_leaves)):
                        outs, posts = [], []
                        for j in range(k):
                            widx = gidx * k + j
                            out, post = self._perturb(
                                g[j], s,
                                jax.random.fold_in(jax.random.fold_in(key, widx), i),
                                widx,
                                previous=(
                                    carry_leaves[i][j]
                                    if carry_leaves is not None else None
                                ),
                                ridx=ridx, late=lates[j],
                            )
                            outs.append(out)
                            posts.append(post)
                        g_leaves[i] = jnp.stack(outs)
                        post_leaves.append(jnp.stack(posts))
                    if self.carries_gradients:
                        new_carry = jax.tree_util.tree_unflatten(treedef, post_leaves)

                # (3b) submission forgery + authentication digests (secure/):
                # impersonated/tampered submissions, sender/receiver checksums
                # over every leaf shard, reject-to-NaN under ``secure``
                g_leaves, secure_local = self._submission_pipeline(
                    g_leaves, key, gidx, ridx
                )

            # (4/5) per-bucket robust aggregation over the worker axis
            all_rows = []
            for i, (g, s) in enumerate(zip(g_leaves, s_leaves)):
                with phase("reshard"):
                    rows = self._gather_rows(self._leaf_buckets(g, s))
                with phase("gar"):
                    rows = self._apply_omniscient(
                        rows, jax.random.fold_in(key, 10_000 + i), ridx=ridx)
                all_rows.append(rows)

            with phase("gar"):
                # Quarantine BEFORE any distance computation (incl. the global
                # path below): masked rows must read +inf-distant to selection
                # rules, never finite-distant-but-NaN-valued.  raw rows are kept
                # for the reputation signal.
                raw_all_rows = all_rows
                if self.quarantine_threshold:
                    qmask = quarantine_mask(
                        state.reputation, self.quarantine_threshold, gar.nb_byz_workers
                    )
                    all_rows = [
                        jnp.where(qmask[None, :, None], jnp.nan, rows) for rows in all_rows
                    ]

                global_dist2 = None
                if self.granularity == "global" and gar.needs_distances:
                    acc = jnp.zeros((self.nb_workers, self.nb_workers), jnp.float32)
                    for rows, s in zip(all_rows, s_leaves):
                        partial = centered_gram_sq_distances(
                            rows.reshape(self.nb_workers, -1).astype(jnp.float32)
                        )
                        acc = acc + partial * self._replication_scale(s)
                    global_dist2 = jnp.maximum(jax.lax.psum(acc, _IN_GROUP_AXES), 0.0)

                agg_leaves = []
                # Suspicion accumulators (worker_metrics): whole-model per-worker
                # squared distance to the aggregate — per-leaf partials scaled by
                # the replication factor exactly like grad_norm's, psum-completed
                # below — and the mean per-bucket participation.  Participation
                # values are identical on every in-group device EXCEPT along the
                # pipe axis of stage-stacked leaves (distinct buckets), so each
                # contribution is scaled by 1/(replicating axes' size) and the
                # in-group psum then counts every distinct bucket exactly once.
                wdist = jnp.zeros((self.nb_workers,), jnp.float32)
                part_sum = jnp.zeros((self.nb_workers,), jnp.float32)
                part_count = 0.0  # global distinct-bucket count (static)
                rep_dist = jnp.zeros((self.nb_workers,), jnp.float32)
                # (vmapped rule calls below: the Pallas auto-tier detects the
                # batching trace centrally and stays on jnp — gars/common.py
                # _is_batched_tracer)
                for rows, raw_rows, g, s in zip(all_rows, raw_all_rows, g_leaves, s_leaves):
                    participation = None
                    if gar.needs_distances:
                        if global_dist2 is not None:
                            dist2 = jnp.broadcast_to(global_dist2, rows.shape[:1] + global_dist2.shape)
                        else:
                            dist2 = self._bucket_distances(rows, s)
                        if self.worker_metrics:
                            # One pass: the memoized selection graph serves both
                            # the aggregate and the participation (two separate
                            # vmaps would trace it twice per leaf).
                            agg, participation = jax.vmap(
                                gar.aggregate_block_and_participation
                            )(rows, dist2)
                        else:
                            agg = jax.vmap(gar.aggregate_block)(rows, dist2)
                    elif gar.uses_axis or gar.uses_key:
                        # Iterative rules' row norms complete over the model axis
                        # when this leaf's dimensions are sharded across it —
                        # exactly _bucket_distances' discipline — so every shard
                        # derives identical weights and the result matches dense.
                        # Randomized meta-rules get the replicated step key (one
                        # permutation per step, same on every device and leaf).
                        axis = model_axis if model_axis in _spec_axis_names(s) else None
                        from ..gars import GAR_KEY_TAG

                        gkey = jax.random.fold_in(key, GAR_KEY_TAG)
                        if self.worker_metrics:
                            agg, participation = jax.vmap(
                                lambda r, axis=axis: gar.aggregate_block_and_participation(
                                    r, None, axis_name=axis, key=gkey
                                )
                            )(rows)
                        else:
                            agg = jax.vmap(
                                lambda r, axis=axis: gar._call_aggregate(
                                    r, None, axis_name=axis, key=gkey)
                            )(rows)
                    else:
                        agg = jax.vmap(lambda r: gar.aggregate_block(r, None))(rows)
                    if self.reputation_decay is not None:
                        rdiff = raw_rows.astype(jnp.float32) - agg.astype(jnp.float32)[:, None, :]
                        rep_dist = rep_dist + jnp.sum(rdiff * rdiff, axis=(0, 2)) * self._replication_scale(s)
                    if self.worker_metrics:
                        diff = rows.astype(jnp.float32) - agg.astype(jnp.float32)[:, None, :]
                        wdist = wdist + jnp.sum(diff * diff, axis=(0, 2)) * self._replication_scale(s)
                        if participation is not None:
                            stacked = (
                                self.granularity == "layer" and s is not None
                                and len(s) >= 2 and s[0] == pipe_axis
                            )
                            rep = (model_axis,) + (() if stacked else (pipe_axis,))
                            pscale = 1.0
                            for a in rep:
                                pscale /= self.mesh.shape[a]
                            part_sum = part_sum + jnp.sum(participation, axis=0) * pscale
                            part_count += participation.shape[0] * (
                                self.mesh.shape[pipe_axis] if stacked else 1
                            )
                    # one aggregate per PARAMETER: strip the local worker
                    # stacking dim from the layout target
                    agg_leaves.append(agg.reshape(g.shape[1:]).astype(g.dtype))
                agg_tree = jax.tree_util.tree_unflatten(treedef, agg_leaves)

            with phase("apply"):
                # (6) local optax update — layouts already match the parameters
                updates, opt_state = tx.update(agg_tree, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)

            with phase("epilogue"):
                sq = jnp.float32(0.0)
                for agg, s in zip(agg_leaves, s_leaves):
                    sq = sq + jnp.sum(jnp.square(agg.astype(jnp.float32))) * self._replication_scale(s)
                grad_norm = jnp.sqrt(jax.lax.psum(sq, _IN_GROUP_AXES))

                # loss is a local partial: sum the local workers, then the worker
                # group's devices, then groups
                total_loss = jax.lax.psum(jnp.sum(losses), _IN_GROUP_AXES + (worker_axis,))
                worker_nan = None
                if self.health_probe:
                    # Per-worker NaN-row flags over the POST-TRANSPORT shards:
                    # count this worker's non-finite coordinates locally,
                    # complete over the worker group, flag, gather workers.
                    bad = jnp.zeros((k,), jnp.int32)
                    for g in g_leaves:
                        bad = bad + jnp.sum(
                            (~jnp.isfinite(g)).astype(jnp.int32),
                            axis=tuple(range(1, g.ndim)),
                        )
                    bad = jax.lax.psum(bad, _IN_GROUP_AXES)
                    worker_nan = jax.lax.all_gather(bad > 0, worker_axis).reshape(
                        self.nb_workers
                    )
                secure_metrics = None
                if secure_local is not None:
                    # complete each worker's lane sums over its in-group shards
                    # (uint32 psum wraps mod 2^32 — the checksum's own domain),
                    # then gather worker-major like the probe's NaN flags
                    def complete(local, summed):
                        value = (
                            jax.lax.psum(local, _IN_GROUP_AXES) if summed else local
                        )
                        gathered = jax.lax.all_gather(value, worker_axis)
                        return gathered.reshape((self.nb_workers,) + value.shape[1:])

                    secure_metrics = {
                        "digest_sent": complete(secure_local["digest_sent"], True),
                        "digest_recv": complete(secure_local["digest_recv"], True),
                        "forged": complete(secure_local["forged"], False),
                        "rejected": complete(secure_local["rejected"], False),
                    }
                return self._finalize_step(
                    state, params=params, opt_state=opt_state, new_carry=new_carry,
                    new_momentum=new_momentum, new_momentum_steps=new_momentum_steps,
                    total_loss=total_loss, update_norm=grad_norm,
                    worker_nan=worker_nan,
                    rep_dist=(
                        jax.lax.psum(rep_dist, _IN_GROUP_AXES)
                        if self.reputation_decay is not None else None
                    ),
                    wdist=(
                        jax.lax.psum(wdist, _IN_GROUP_AXES)
                        if self.worker_metrics else None
                    ),
                    participation=(
                        jax.lax.psum(part_sum, _IN_GROUP_AXES) / part_count
                        if part_count else None
                    ),
                    secure_metrics=secure_metrics, ridx=ridx,
                )

        return body

    def _sharded_build_step(self, loss_fn, tx, state):
        state_specs = jax.tree.map(lambda a: a.sharding.spec, state)
        body = self._make_sharded_body(loss_fn, tx, state_specs)
        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(state_specs, P(worker_axis)),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        # Host-side span wrapper only (obs/trace.py): the jit underneath is
        # untouched — zero added compiles, ``_cache_size`` falls through.
        # EXPLICIT out_shardings pin the output state to the init_state
        # layout: without them the compiler canonicalizes size-1 mesh axes
        # to replicated specs, so the SECOND step call would see differently
        # committed inputs and retrace (the zero-steady-state-recompile bar,
        # tests/test_gar_scaling.py).
        out_shardings = (
            jax.tree.map(lambda a: a.sharding, state),
            NamedSharding(self.mesh, P()),
        )
        return trace.traced(
            "train_step.dispatch",
            jax.jit(_revised(sharded), donate_argnums=(0,), out_shardings=out_shardings),
            cat="train",
        )

    def _sharded_build_multi_step(self, loss_fn, tx, state, repeat_steps=None):
        state_specs = jax.tree.map(lambda a: a.sharding.spec, state)
        body = self._make_sharded_body(loss_fn, tx, state_specs)

        if repeat_steps is None:

            def many(state, batches):
                return jax.lax.scan(body, state, batches)

            batch_spec = P(None, worker_axis)
        else:

            def many(state, batch):
                return jax.lax.scan(
                    lambda s, _: body(s, batch), state, None, length=int(repeat_steps)
                )

            batch_spec = P(worker_axis)

        sharded = jax.shard_map(
            many,
            mesh=self.mesh,
            in_specs=(state_specs, batch_spec),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        # Same out_shardings discipline as build_step: keep the output state
        # committed exactly like init_state's, or call 2 retraces.
        out_shardings = (
            jax.tree.map(lambda a: a.sharding, state),
            NamedSharding(self.mesh, P()),
        )
        return trace.traced(
            "train_multi_step.dispatch",
            jax.jit(_revised(sharded), donate_argnums=(0,), out_shardings=out_shardings),
            cat="train",
        )

    def _sharded_build_gar_probe(self, d, seed=0):
        """The sharded twin of the flat GAR probe (the measurement
        instrument behind ``gar_seconds_total`` / the ``gar.aggregate``
        span).

        The engine proper reduces per leaf/bucket; the probe measures ONE
        rule application over the whole-model (n, d) row matrix on a single
        replica — exact for ``granularity=global`` (one selection over the
        flattened vector) and an upper bound for layer/leaf granularity
        (the same arithmetic split across buckets).  Attacks/quarantine are
        excluded: the probe times the rule, not the adversity simulation."""
        from ..gars import GAR_KEY_TAG

        # Column-shard the synthetic rows over the worker axis (the flat
        # probe's layout): a replicated (n, d) matrix at whole-model d and
        # large n would cost n x the model footprint PER DEVICE — the
        # sharded mode's whole reason to exist is that that doesn't fit.
        # The body is plain jit, so GSPMD partitions the distance Gram and
        # the rule's columnwise work along d automatically.  d is padded to
        # the worker-axis multiple (sharding a dim requires divisibility;
        # model_dim is an arbitrary parameter count), and the rows are
        # generated ON DEVICE under jit with an explicit output sharding so
        # the host never materializes the (n, d) matrix.
        W = self.nb_mesh_workers
        blk = -(-int(d) // W)
        make_rows = jax.jit(
            lambda k: jax.random.normal(k, (self.nb_workers, W * blk), jnp.float32),
            out_shardings=NamedSharding(self.mesh, P(None, worker_axis)),
        )
        rows = make_rows(jax.random.PRNGKey(seed))
        gar = self.gar

        def body(rows, key):
            dist2 = None
            if gar.needs_distances:
                # jnp-tier Gram distances (same as _bucket_distances): the
                # common pairwise_sq_distances auto-dispatches to a Pallas
                # kernel on TPU, which GSPMD cannot partition over the
                # column-sharded rows
                dist2 = jnp.maximum(centered_gram_sq_distances(rows), 0.0)
            gar_key = jax.random.fold_in(key, GAR_KEY_TAG)
            return gar._call_aggregate(rows, dist2, axis_name=None, key=gar_key)

        fn = jax.jit(body)
        base = jax.random.PRNGKey(seed)

        def probe(step=0):
            return fn(rows, jax.random.fold_in(base, step))

        return probe

    def _sharded_build_eval(self, loss_fn, state):
        """Jitted eval: mean of the sharded loss over the worker axis.

        Built once from ``state``'s layout (like ``build_step``) so repeated
        cadenced evals hit the jit cache instead of recompiling.
        """
        specs = jax.tree.map(lambda a: a.sharding.spec, state)
        k = self.workers_per_device

        def body(state, batch):
            if k == 1:
                local = jax.tree.map(lambda x: x[0], batch)
                total = loss_fn(state.params, local)  # local partial
            else:
                total = jnp.sum(
                    jax.vmap(lambda b: loss_fn(state.params, b))(batch)
                )
            return jax.lax.psum(total, _IN_GROUP_AXES + (worker_axis,)) / self.nb_workers

        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(specs, P(worker_axis)),
            out_specs=P(),
            check_vma=False,
        )
        return trace.traced("eval_step.dispatch", jax.jit(sharded), cat="eval")

    # ------------------------------------------------------------------ #
    # the public, mode-polymorphic surface

    def init_state(self, *args, seed=0):
        """Create the TrainState for this engine's mode.

        - flat:    ``init_state(params, tx, seed=0)``
        - sharded: ``init_state(init_fn, specs, tx, seed=0)``
        """
        if self.sharded:
            if len(args) != 3:
                raise UserException(
                    "sharded init_state wants (init_fn, specs, tx); got %d "
                    "positional argument(s)" % len(args)
                )
            return self._sharded_init_state(*args, seed=seed)
        if len(args) != 2:
            raise UserException(
                "flat init_state wants (params, tx); got %d positional "
                "argument(s)" % len(args)
            )
        return self._flat_init_state(*args, seed=seed)

    def put_state(self, state):
        """Device_put a TrainState with this engine's state layout (the
        checkpoint-restore path)."""
        if self.sharded:
            return self._sharded_put_state(state)
        return self._flat_put_state(state)

    def build_step(self, loss_fn, tx, state=None):
        """Build the jitted robust training step.

        The sharded mode derives its in/out shardings from ``state`` (the
        TrainState from ``init_state``) and therefore requires it; the flat
        mode's layout is static and ``state`` is accepted and ignored, so
        callers can pass it uniformly."""
        if self.sharded:
            if state is None:
                raise UserException(
                    "the sharded build_step derives its shardings from the "
                    "TrainState; pass state=init_state(...)"
                )
            return self._sharded_build_step(loss_fn, tx, state)
        return self._flat_build_step(loss_fn, tx)

    def build_multi_step(self, loss_fn, tx, state=None, repeat_steps=None):
        """Build the jitted K-step scanned trainer (same ``state`` contract
        as :meth:`build_step`; ``repeat_steps`` reuses one resident batch)."""
        if self.sharded:
            if state is None:
                raise UserException(
                    "the sharded build_multi_step derives its shardings from "
                    "the TrainState; pass state=init_state(...)"
                )
            return self._sharded_build_multi_step(
                loss_fn, tx, state, repeat_steps=repeat_steps
            )
        return self._flat_build_multi_step(loss_fn, tx, repeat_steps=repeat_steps)

    def build_eval(self, fn, state=None):
        """flat: ``build_eval(metric_fn)`` -> per-batch means;
        sharded: ``build_eval(loss_fn, state)`` -> mean sharded loss."""
        if self.sharded:
            if state is None:
                raise UserException(
                    "the sharded build_eval derives its shardings from the "
                    "TrainState; pass state=init_state(...)"
                )
            return self._sharded_build_eval(fn, state)
        return self._flat_build_eval(fn)

    def build_gar_probe(self, d, seed=0):
        """Jitted GAR-only executable at the engine's exact (n, d) — see the
        mode-specific docstrings."""
        if self.sharded:
            return self._sharded_build_gar_probe(d, seed=seed)
        return self._flat_build_gar_probe(d, seed=seed)


    # ------------------------------------------------------------------ #
    # bounded-wait protocol hooks (parallel/bounded.py, docs/engine.md):
    # the fused SPMD step splits into per-worker submission executables the
    # host dispatches asynchronously, plus one aggregate+update executable
    # that absorbs workers missing the deadline as NaN rows — the chaos
    # straggler model as the ACTUAL protocol, not a simulation.

    def _check_bounded_wait_supported(self, allow_submesh=False):
        if self.sharded:
            in_group = self.mesh.shape[pipe_axis] * self.mesh.shape[model_axis]
            if in_group != 1 and not allow_submesh:
                raise UserException(
                    "build_group_grad needs trivial in-group axes "
                    "(--mesh W,1,1): a (pipe x model) submesh submission is "
                    "one collective program whose members cannot time out "
                    "independently — per-SUBMESH collective timeouts are "
                    "build_submesh_grad's protocol (docs/engine.md, "
                    "'v3: submesh deadlines')"
                )
            if self.granularity != "global":
                raise UserException(
                    "sharded bounded-wait aggregates the whole flattened "
                    "gradient; use granularity global (the sharded spelling "
                    "of the flat mode's vector)"
                )
            if self.worker_momentum is not None:
                raise UserException(
                    "sharded bounded-wait does not carry worker momentum: "
                    "the sharded TrainState.momentum is a per-leaf pytree, "
                    "not the flat (n, d) buffer the submission body indexes "
                    "— run the flat engine for momentum + bounded-wait"
                )
        elif self.granularity != "vector":
            raise UserException(
                "bounded-wait aggregates the whole flattened gradient "
                "(granularity vector); per-leaf selection is not supported"
            )
        if self.lossy_link is not None or self.chaos is not None:
            raise UserException(
                "bounded-wait replaces the simulated transport: drop --UDP/"
                "--chaos in-graph regimes (straggler regimes move to the "
                "host straggler model, parallel/bounded.py)"
            )

    def _bounded_submission_body(self, loss_fn):
        """The shared per-worker submission body of both bounded-wait
        builders: gradient -> worker momentum -> local attack -> wire
        encode -> digest, returning a dict with keys ``loss``, ``row``
        and (configured) ``momentum`` / ``ef`` / ``digest``.

        ``momentum`` / ``ef`` in the argument list are the WHOLE (n, d)
        buffers from ``TrainState`` (dynamically indexed by the traced
        worker index, so steady state never recompiles); the returned
        entries are the worker's updated (d,) rows, which the bounded
        aggregate writes back only for workers whose submission ARRIVED —
        a timed-out worker's momentum (and error-feedback residual) never
        updated, exactly as its gradient never shipped.  The submitted row
        is the bias-corrected momentum (Karimireddy et al. 2021),
        corrected by the GLOBAL update count: a straggler that missed
        rounds sends a slightly over-corrected momentum rather than
        forcing a per-worker count into the compiled signature.

        The wire: under a codec (parallel/compress.py) ``row`` is the
        ENCODED payload pytree — what actually crosses the host boundary,
        so the (n, d) f32 stack never does — and the digest covers the
        wire IMAGE (the exact f32 rows the aggregation-side decoder
        emits, a deterministic function of the encoded bytes: tampering
        the payload moves the image and therefore the digest).  On the
        dtype twin the digest keeps its historical convention (post-
        attack, pre-quantization — the fused ``_perturb_local``'s)."""
        from ..secure.submit import row_digest

        beta = self.worker_momentum

        def body(params, worker_batch, rng, step, widx, momentum,
                 momentum_steps, ef):
            key = jax.random.fold_in(rng, step)
            if self.batch_transform is not None:
                # fold tag 3: the augmentation stream (same as the fused body)
                wkey = jax.random.fold_in(jax.random.fold_in(key, widx), 3)
                with phase("augment"):
                    worker_batch = self.batch_transform(worker_batch, wkey)
            with phase("grad"):
                loss, grads = jax.value_and_grad(loss_fn)(params, worker_batch)
            leaves = jax.tree_util.tree_leaves(grads)
            with phase("flatten"):
                row = jnp.concatenate(
                    [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves]
                )
            out = {"loss": loss}
            with phase("perturb"):
                if beta is not None:
                    new_m = beta * momentum[widx] + (1.0 - beta) * row
                    out["momentum"] = new_m
                    correction = 1.0 - beta ** (
                        jnp.asarray(momentum_steps, jnp.float32) + 1.0
                    )
                    row = new_m / correction
                if self.attack is not None and not self.attack.omniscient:
                    wkey = jax.random.fold_in(key, widx)
                    forged = self.attack.apply_local(row, jax.random.fold_in(wkey, 1))
                    row = jnp.where(widx < self.nb_real_byz, forged, row)
                if self.codec is not None:
                    if ef is not None:
                        payload, image, new_ef = self.codec.ef_encode(row, ef[widx])
                        out["ef"] = new_ef
                    else:
                        payload = self.codec.encode(row)
                        image = self.codec.decode(payload, row.shape[-1])
                    if self.secure:
                        out["digest"] = row_digest(image)
                    out["row"] = payload
                    return out
                if self.secure:
                    out["digest"] = row_digest(row)
                if self.exchange_dtype is not None:
                    row = row.astype(self.exchange_dtype)
                out["row"] = row
                return out

        return body

    def build_worker_grad(self, loss_fn):
        """One jitted per-worker submission executable: ``grad_fn(params,
        worker_batch, rng, step, widx[, momentum, momentum_steps]) ->
        {loss, row[, momentum][, digest]}`` (the momentum operands appear
        iff ``worker_momentum`` is set; see ``_bounded_submission_body``).

        Compiled ONCE and dispatched n times per step (worker index and
        step are traced operands, so steady state never recompiles).  The
        row is what the worker "sends": flattened f32, worker momentum
        applied, local attack applied to coalition workers with the fused
        body's exact key discipline (fold worker, then tag 1), digest-
        summarized under ``secure``, wire-quantized when
        ``exchange_dtype`` is set — or the ENCODED codec payload when a
        wire codec is configured (``momentum`` and the error-feedback
        ``ef`` buffer append to the operand list in that order, each iff
        configured)."""
        self._check_bounded_wait_supported()
        body = self._bounded_submission_body(loss_fn)
        with_momentum = self.worker_momentum is not None
        with_ef = self.carries_ef

        def grad_fn(params, worker_batch, rng, step, widx, *extra):
            momentum = momentum_steps = ef = None
            i = 0
            if with_momentum:
                momentum, momentum_steps = extra[0], extra[1]
                i = 2
            if with_ef:
                ef = extra[i]
            return body(params, worker_batch, rng, step, widx, momentum,
                        momentum_steps, ef)

        return trace.traced(
            "worker_grad.dispatch", jax.jit(_revised(grad_fn)), cat="train"
        )

    def build_group_grad(self, loss_fn):
        """The sharded-mode submission executable: one jitted program per
        WORKER-AXIS SUBMESH, computing its k = n/W logical workers vmapped —
        ``group_fn(params, group_batch, rng, step, gidx[, momentum,
        momentum_steps]) -> {loss: (k,), row: (k, d)[, momentum: (k, d)]
        [, digest: (k, 4)]}``.

        The group index is a traced operand like the flat mode's worker
        index (one executable, dispatched W times per round, zero steady-
        state recompiles); global worker indices are ``gidx * k + j``, so
        attack coalitions and PRNG streams address workers exactly as the
        flat submission path does.  Requires trivial in-group axes (the
        submesh is a single device — ``_check_bounded_wait_supported``):
        the group's submission then completes independently of its peers,
        which is what a per-group deadline needs."""
        self._check_bounded_wait_supported()
        body = self._bounded_submission_body(loss_fn)
        k = self.workers_per_device

        def group_body(params, group_batch, rng, step, gidx, momentum,
                       momentum_steps):
            def one(j, worker_batch):
                # codec exchange is flat-engine-only (__init__), so the
                # group body never sees an ef operand
                return body(params, worker_batch, rng, step, gidx * k + j,
                            momentum, momentum_steps, None)

            return jax.vmap(one)(jnp.arange(k), group_batch)

        if self.worker_momentum is not None:
            def group_fn(params, group_batch, rng, step, gidx, momentum,
                         momentum_steps):
                return group_body(params, group_batch, rng, step, gidx,
                                  momentum, momentum_steps)
        else:
            def group_fn(params, group_batch, rng, step, gidx):
                return group_body(params, group_batch, rng, step, gidx,
                                  None, None)

        return trace.traced(
            "group_grad.dispatch", jax.jit(_revised(group_fn)), cat="train"
        )

    def build_submesh_grad(self, loss_fn):
        """The bounded-wait v3 submission executable for NONTRIVIAL
        (pipe x model) submeshes: one jitted program per WORKER-AXIS
        SUBMESH whose pipe/model collectives are INTERNAL to the program
        — ``submesh_fn(params, group_batch, rng, step, gidx) ->
        {loss: (k,), row: (k, d)[, digest: (k, 4)]}``.

        Where ``build_group_grad`` requires the submesh to be a single
        device, this builder embraces the collectives: the params stay
        committed to their (pipe, model) shardings, GSPMD partitions the
        per-worker gradient across the submesh's in-group devices, and
        the OUTPUTS are pinned replicated (``out_shardings``) so the
        host-side stack of W independent submissions commits one layout
        every round.  Each of the W dispatches is then one self-contained
        collective program: its in-group members finish or miss the
        deadline TOGETHER, so a submesh that misses the window forfeits
        its k = n/W logical rows as a unit into the same declared-f
        budget (parallel/bounded.py, ``submesh_timeout``).  The group
        index is a traced operand — one compiled signature, W dispatches
        per round, zero steady-state recompiles.  Momentum stays refused
        sharded and the codec exchange stays flat-engine-only, so the
        body never sees those operands."""
        self._check_bounded_wait_supported(allow_submesh=True)
        if not self.sharded:
            raise UserException(
                "build_submesh_grad is the sharded-mode submission builder "
                "(per-submesh collective programs); the flat engine "
                "dispatches build_worker_grad"
            )
        body = self._bounded_submission_body(loss_fn)
        k = self.workers_per_device

        def submesh_fn(params, group_batch, rng, step, gidx):
            def one(j, worker_batch):
                # momentum is refused sharded and the codec exchange is
                # flat-engine-only, so the body sees neither operand
                return body(params, worker_batch, rng, step, gidx * k + j,
                            None, None, None)

            return jax.vmap(one)(jnp.arange(k), group_batch)

        jitted = jax.jit(
            _revised(submesh_fn), out_shardings=NamedSharding(self.mesh, P())
        )
        return trace.traced("submesh_grad.dispatch", jitted, cat="train")

    def build_bounded_aggregate(self, tx, params_template, rows_form="wire",
                                stale_reweight=False):
        """The aggregator side of the bounded-wait protocol: ``agg(state,
        rows, losses, arrived, stale, extras) -> (state, metrics)``, jitted
        once (``params_template`` fixes the flatten/inflate layout).

        ``rows`` is the (n, ...) submission buffer in one of two forms
        (fixed at build time — one compiled signature per step):

        - ``rows_form="wire"``: what crossed the wire — (n, d) rows in
          the exchange dtype, or the stacked ENCODED payload pytree under
          a codec, decoded HERE so the GAR (and everything downstream)
          sees float32 rows;
        - ``rows_form="decoded"``: already-decoded float32 (n, d) rows —
          the incremental as-rows-land mode (parallel/bounded.py folds
          each submission into the buffer the instant it arrives, so the
          barrier only pays the aggregation).

        Fresh rows where ``arrived``, CLEVER carry rows where ``stale``
        (the host's stale infill, parallel/bounded.py), garbage elsewhere
        — masked to NaN in-graph AFTER decoding.  A row that is neither
        fresh nor stale is a NaN drop INSIDE the same declared-f budget
        as Byzantine rows, and a STALE row spends that budget too
        (timeouts + stale + attacks <= f for the rule's guarantee to hold
        — docs/engine.md, "f-accounting": the carry may hold a Byzantine
        worker's attack row).  Deadline verdicts land in
        ``metrics["straggler_timeout"]`` / ``metrics["stale_infill"]``;
        missed workers are excluded from the loss sum (the aggregator
        only averages what it received).  ``extras`` carries the
        configured optional operands: ``momentum`` / ``ef`` (the (n, d)
        updated rows, written back only where ``arrived`` — a timed-out
        worker's momentum and error-feedback residual never updated) and
        ``digests`` (the (n, 4) submission digests the host authenticator
        signs/verifies one dispatch behind, secure/submit.py).
        Omniscient attacks, quarantine, reputation, the health probe and
        the flight recorder ride the same shared code paths as the fused
        step (``_prepare_rows`` / ``_finalize_step``).

        ``stale_reweight=True`` is the v3 age-reweighted stale correction
        (the unbiased-estimator framing of arXiv:2505.23523): a stale
        carry row of age a is scaled by the traced coefficient
        c(a) = 1/(1 + a) — ``extras["stale_age"]`` carries the host's
        (n,) age vector — instead of re-entering at full weight.  The
        discount composes with the codec as two traced scalars (decode
        first, then reweight; parallel/compress.py), and it does NOT
        relax the f-accounting: a reweighted stale row still SPENDS the
        declared-f budget (the carry may hold a Byzantine worker's
        attack row — damping it is not dropping it)."""
        self._check_bounded_wait_supported(allow_submesh=True)
        if rows_form not in ("wire", "decoded"):
            raise UserException(
                "rows_form must be 'wire' or 'decoded' (got %r)" % (rows_form,)
            )
        from ..gars import GAR_KEY_TAG
        from ..gars.common import pairwise_sq_distances

        from .compress import wire_roundtrip

        # the flattening layout, for inflating the aggregate back to a tree
        flatmap = FlatMap(params_template)
        d = flatmap.size
        if self.codec is not None:
            self.codec.validate_d(d)

        def agg_fn(state, rows, losses, arrived, stale, extras):
            key = jax.random.fold_in(state.rng, state.step)
            with phase("reshard"):
                if rows_form == "wire" and self.codec is not None:
                    # decode at the aggregation boundary: every GAR sees f32
                    rows = self.codec.decode_rows(rows, d)
                else:
                    rows = rows.astype(jnp.float32)
                # deadline verdict first: a worker that neither arrived nor
                # carries a live stale row IS a NaN row — the exact convention
                # of a fully-lossy link, absorbed by the rule
                valid = arrived | stale
                rows = jnp.where(valid[:, None], rows, jnp.nan)
                if rows_form == "wire" and self.codec is None:
                    # the dtype twin's wire image (no-op on the f32 wire; the
                    # codec/decoded forms already ARE the wire image)
                    rows = wire_roundtrip(rows, dtype=self.exchange_dtype)
                reweight_coeff = None
                if stale_reweight:
                    # v3 age reweighting: damp each stale carry row by
                    # c(a) = 1/(1+a) — traced, so steady state never
                    # recompiles as ages tick.  Applied AFTER decode and the
                    # wire image (the coefficient scales what the rule sees,
                    # not what crossed the wire) and BEFORE _prepare_rows
                    # (reputation/quarantine judge the damped row, exactly
                    # what enters the aggregate).
                    ages = extras["stale_age"].astype(jnp.float32)
                    reweight_coeff = jnp.where(stale, 1.0 / (1.0 + ages), 1.0)
                    rows = rows * reweight_coeff[:, None]
            with phase("gar"):
                rows, raw_rows = self._prepare_rows(rows, key, state.reputation)
                dist2 = None
                if self.gar.needs_distances:
                    dist2 = jnp.maximum(pairwise_sq_distances(rows), 0.0)
                gar_key = jax.random.fold_in(key, GAR_KEY_TAG)
                participation = None
                if self.worker_metrics:
                    agg, participation = self.gar.aggregate_block_and_participation(
                        rows, dist2, axis_name=None, key=gar_key
                    )
                else:
                    agg = self.gar._call_aggregate(
                        rows, dist2, axis_name=None, key=gar_key
                    )
                agg = agg.astype(jnp.float32)
            with phase("apply"):
                agg_tree = flatmap.inflate(agg)
                updates, opt_state = tx.update(agg_tree, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            with phase("epilogue"):
                # the aggregator can only sum the losses it RECEIVED; a late
                # worker's loss never arrived (its row is the NaN infill)
                total_loss = jnp.sum(jnp.where(arrived, losses, 0.0))
                wdist = rep_dist = None
                if self.worker_metrics:
                    diff = rows - agg[None, :]
                    wdist = jnp.sum(diff * diff, axis=1)
                if self.reputation_decay is not None:
                    rdiff = raw_rows - agg[None, :]
                    rep_dist = jnp.sum(rdiff * rdiff, axis=1)
                worker_nan = None
                if self.health_probe:
                    worker_nan = jnp.any(~jnp.isfinite(rows), axis=1)
                new_momentum = new_momentum_steps = None
                if self.worker_momentum is not None:
                    # write back only the rows whose submission ARRIVED: a
                    # timed-out worker's momentum update never completed (its
                    # thread's result was discarded with the round).  Emitted
                    # replicated, like every other plain-jit output here; the
                    # host step re-places init_state's worker-sharded buffer
                    # ONCE so round 0's input layout matches every later
                    # round's (parallel/bounded.py — else both executables
                    # would recompile at round 1)
                    new_momentum = jnp.where(
                        arrived[:, None], extras["momentum"], state.momentum
                    )
                    new_momentum_steps = state.momentum_steps + 1
                new_ef = None
                if self.carries_ef:
                    # same convention as momentum: a timed-out worker's
                    # error-feedback residual never updated (its submission —
                    # and the quantization error it absorbed — never shipped)
                    new_ef = jnp.where(arrived[:, None], extras["ef"], state.ef)
                secure_metrics = None
                if self.secure:
                    # sent == received by construction on this path (no
                    # in-transit transform between the submission executable
                    # and the host's stack); the host authenticator still
                    # signs and verifies one dispatch behind, and a digest
                    # mismatch there would name a real corruption
                    nobody = jnp.zeros((self.nb_workers,), bool)
                    secure_metrics = {
                        "digest_sent": extras["digests"],
                        "digest_recv": extras["digests"],
                        "forged": nobody,
                        "rejected": nobody,
                    }
                new_state, metrics = self._finalize_step(
                    state, params=params, opt_state=opt_state, new_carry=None,
                    new_momentum=new_momentum,
                    new_momentum_steps=new_momentum_steps,
                    total_loss=total_loss, update_norm=jnp.linalg.norm(agg),
                    worker_nan=worker_nan, rep_dist=rep_dist, wdist=wdist,
                    participation=participation, secure_metrics=secure_metrics,
                    ridx=None, new_ef=new_ef,
                )
            # deadline evidence AFTER the epilogue: the flight recorder's
            # lane set predates the protocol; forensics/registry consume
            # these from the metrics dict on the host.  ``nb_timeouts`` is
            # the round's f-budget spend: NaN drops AND stale infills both
            # count (the guardian's over-budget escalation input).
            metrics["straggler_timeout"] = ~arrived
            metrics["stale_infill"] = stale
            metrics["nb_timeouts"] = jnp.sum((~arrived).astype(jnp.int32))
            metrics["nb_stale"] = jnp.sum(stale.astype(jnp.int32))
            if reweight_coeff is not None:
                metrics["stale_reweight_coeff"] = reweight_coeff
            return new_state, metrics

        jitted = jax.jit(_revised(agg_fn), donate_argnums=(0,))
        return trace.traced("bounded_aggregate.dispatch", jitted, cat="train")

    def build_incremental_fold(self, d):
        """The incremental-aggregation fold (parallel/bounded.py): write ONE
        worker's decoded submission into the aggregate-side (n, d) float32
        buffer the instant it lands, instead of stacking everything at the
        round barrier.  ``fold(buffer, wire_row, widx) -> buffer`` — the
        buffer is donated (an in-place row write), the worker index is a
        traced operand, and the decode runs here, overlapped with the
        submissions still outstanding — so the barrier-side aggregate
        consumes already-decoded rows (``rows_form="decoded"``).  Returns
        ``(fold, fresh)`` where ``fresh()`` allocates the round's zeroed
        buffer (content under never-written slots is irrelevant: the
        aggregate masks non-arrived, non-stale slots to NaN)."""
        self._check_bounded_wait_supported(allow_submesh=True)
        codec, dt = self.codec, self.exchange_dtype
        if codec is not None:
            codec.validate_d(d)
        n = self.nb_workers

        del dt  # the dtype twin's row arrives ALREADY in its wire dtype

        def fold(buffer, wire_row, widx):
            if codec is not None:
                row = codec.decode(wire_row, d)
            else:
                row = wire_row.astype(jnp.float32)
            return buffer.at[widx].set(row)

        # the fresh buffer commits REPLICATED like every fold output (the
        # submission payloads carry the mesh's replicated NamedSharding),
        # so the first fold of every round hits the same trace as the rest
        fresh = jax.jit(
            lambda: jnp.zeros((n, d), jnp.float32),
            out_shardings=NamedSharding(self.mesh, P()),
        )
        jitted = jax.jit(fold, donate_argnums=(0,))
        return trace.traced("bounded_fold.dispatch", jitted, cat="train"), fresh


# --------------------------------------------------------------------- #
# Start-up spans (obs/trace.py ``startup``; docs/observability.md "Reading a
# start-up") at the engine's boundaries: ``startup.engine`` (the constructor),
# ``startup.build_step`` (the three builders: closures only, ~0 — a guard),
# ``startup.put`` (``replicate``: the enqueue as the host sees it; the
# resident data set's at the top level, the state's inside the next; nothing
# in a step loop calls it) and ``startup.state_init`` (``init_state``, with
# the programs it loads leaf by leaf inside it).
#
# They are put on HERE, below every definition, and not as decorators or
# ``with`` blocks where the methods stand: a Mosaic kernel's serialized body
# carries the file and line of every frame that called it, so a line added
# above a step body would re-key the persistent compilation cache of every
# step program that holds a kernel (PERF.md §6, PR 37).

import functools  # noqa: E402


def _nbytes(tree):
    return int(sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(tree)))


def _put_span(replicate):
    @functools.wraps(replicate)
    def replicating(self, tree):
        with trace.startup("startup.put", bytes=_nbytes(tree)):
            return replicate(self, tree)

    return replicating


def _state_init_span(init_state):
    @functools.wraps(init_state)
    def initialising(self, *args, seed=0):
        with trace.startup("startup.state_init") as started:
            state = init_state(self, *args, seed=seed)
            started.note(leaves=len(jax.tree_util.tree_leaves(state)), bytes=_nbytes(state))
        return state

    return initialising


RobustEngine.__init__ = trace.startup("startup.engine")(RobustEngine.__init__)
for _builder in ("build_step", "build_multi_step", "build_sampled_multi_step"):
    setattr(RobustEngine, _builder,
            trace.startup("startup.build_step")(getattr(RobustEngine, _builder)))
RobustEngine.replicate = _put_span(RobustEngine.replicate)
RobustEngine.init_state = _state_init_span(RobustEngine.init_state)
