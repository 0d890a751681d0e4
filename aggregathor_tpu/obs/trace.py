"""Host-side span tracer emitting Chrome trace-event JSON.

The reference's only timing story is the end-of-run steps/s printout
(runner.py:504-598); a production run needs to see WHERE a step's wall time
went — dispatch vs blocking on the device vs host-side gaps — after the
fact, per step, without attaching a profiler.  This module is that story's
host half: lightweight spans written as Chrome trace events (the
``{"traceEvents": [...]}`` JSON Array Format), loadable in Perfetto /
``chrome://tracing`` next to a ``jax.profiler`` device trace.

Design constraints (the acceptance bar in ISSUE 4):

- **Zero compiles touched** — everything here is host-side Python; the
  jitted step programs are wrapped (``traced``), never modified, so the jit
  cache is byte-identical with tracing on or off (asserted by
  tests/test_obs.py).
- **Near-zero cost disabled** — tracing is OFF until :func:`install` is
  called; the disabled fast path of :class:`span` / :func:`instant` /
  :class:`TracedCallable` is a single global ``None`` check, beside the
  profiler annotation below.
- **On the profiler's clock** — every :class:`span` and every
  :class:`TracedCallable` dispatch also enters a
  ``jax.profiler.TraceAnnotation`` of the same name, installed or not: with
  no profiler session live that is one atomic test; with one live
  (``--xprof``, or anybody's ``jax.profiler.start_trace``) the program's
  spans sit in the ``.xplane.pb`` on the device trace's clock.
- **Bounded enabled cost** — events append to an in-memory list under a
  lock (one append per span, microseconds against millisecond steps) with a
  hard event cap; past it events are counted as dropped, never written.

Usage::

    from aggregathor_tpu.obs import trace
    trace.install("run.trace.json", run_id=run_id)
    with trace.span("dispatch", cat="train", step=12):
        ...
    @trace.span("checkpoint.save")
    def save(...): ...
    trace.save()            # or trace.uninstall(save=True)

Nesting is tracked per thread (a thread-local span stack): each event
carries its stack depth and parent name in ``args``, and Perfetto nests
same-thread "X" events by time containment.  All public entry points are
thread-safe — the serving stack records from handler threads while the
batcher thread records batches.

Beyond spans: ``Tracer.track`` allocates NAMED synthetic tracks (one
Perfetto lane per logical worker — the bounded-wait submission timelines,
docs/observability.md "Reading a round timeline"), ``complete_at`` lays
events onto them with explicit timestamps, and ``counter`` emits "C"
events Perfetto renders as numeric tracks (deadline window, arrivals,
bytes on wire per round).

Two tracers pointed at ONE path no longer clobber each other: a tiny
``<path>.claim`` sidecar carries the live writer's (writer_pid, run_id)
from install time, and a tracer installing onto a path owned by a LIVE
sibling writes to a pid-suffixed variant instead — while the trace file
itself is never touched before the first real save, so a dead writer's
completed output survives until this run actually has something to say.

One thing is ON from the first import, tracer or none: the **start-up
record** (:class:`startup`, :func:`startup_record`) — what the process did
between its start and its first step, named at the program's own boundaries.
See "the start-up record" below for why it may be.
"""

import functools
import json
import os
import threading
import time
import weakref

import jax

#: the process-wide installed tracer (None = tracing disabled)
_tracer = None

#: per-thread span stack for nesting (list of span names)
_local = threading.local()

#: hard cap on buffered events — a runaway loop degrades to a counted drop,
#: not an OOM (at ~150 B/event this caps the buffer around 150 MB)
MAX_EVENTS = 1_000_000

#: synthetic-track tids start here, far above any OS thread id width that
#: matters for display — named tracks (per-worker submission timelines,
#: counter tracks) must never collide with a real thread's tid
TRACK_TID_BASE = 1 << 48


def _claim_path(path):
    """The tiny sidecar holding a live tracer's (writer_pid, run_id)
    claim on ``path``.  A SIDECAR, not the trace file itself: the claim
    must exist from install time (or a second live tracer adopting the
    same path goes unnoticed for the whole run) without ever touching the
    trace file before its first real save (a metadata stub would destroy
    a dead writer's completed trace even if this run crashes unsaved)."""
    return path + ".claim"


def _write_claim(path, run_id):
    directory = os.path.dirname(path)
    if directory:
        os.makedirs(directory, exist_ok=True)
    tmp = _claim_path(path) + ".tmp"
    with open(tmp, "w") as fd:
        json.dump({"writer_pid": os.getpid(), "run_id": run_id}, fd)
    os.replace(tmp, _claim_path(path))


def _claimed_by_other(path, run_id):
    """Is ``path`` under a LIVE claim by another tracer?  True when its
    claim sidecar names a different (writer_pid, run_id) whose process is
    still alive (or is this very process — a sibling tracer).  A dead
    writer's claim is stale: overwriting its output at save time is the
    historical, expected behavior.  No sidecar = no claim."""
    try:
        with open(_claim_path(path)) as fd:
            other = json.load(fd)
    except Exception:
        return False
    pid, rid = other.get("writer_pid"), other.get("run_id")
    if pid is None:
        return False  # pre-claim-era trace: legacy file, no live writer
    try:
        pid = int(pid)
    except (TypeError, ValueError):
        return False
    if pid == os.getpid():
        # same process: ours only when the run_ids match AND identify a
        # writer (two default-None tracers are indistinguishable, so they
        # must not clobber each other — a second install in one process
        # never overwrites the first's output)
        return not (rid == run_id and rid is not None)
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False  # writer is gone: stale file
    except PermissionError:
        return True   # alive under another uid: very much a live claim
    except OSError:
        return False
    return True


def _unclaimed_path(path, run_id):
    """``path``, or a pid-suffixed variant when another LIVE tracer owns
    it — the fix for last-writer-wins clobbering when a train+serve pair
    (or two runner invocations) point at the same --trace-file."""
    if path is None or not _claimed_by_other(path, run_id):
        return path
    root, ext = os.path.splitext(path)
    candidate = "%s.%d%s" % (root, os.getpid(), ext)
    nb = 1
    while os.path.exists(candidate) and _claimed_by_other(candidate, run_id):
        candidate = "%s.%d-%d%s" % (root, os.getpid(), nb, ext)
        nb += 1
    from ..utils import warning

    warning(
        "Trace path %r is owned by another live tracer; writing to %r "
        "instead (pass distinct --trace-file paths to silence this)"
        % (path, candidate)
    )
    return candidate


def _stack():
    stack = getattr(_local, "spans", None)
    if stack is None:
        stack = _local.spans = []
    return stack


class Tracer:
    """Event buffer + clock for one trace file.  Use the module-level
    :func:`install` / :func:`save` / :func:`uninstall` in application code;
    construct directly only in tests."""

    def __init__(self, path, run_id=None, clock=None):
        # refuse to clobber a LIVE sibling's file: two tracers pointed at
        # one path (train+serve pair, two runner invocations) used to
        # silently overwrite each other through last-writer-wins os.replace
        self.path = _unclaimed_path(path, run_id)
        self.run_id = run_id
        self._clock = clock if clock is not None else time.perf_counter
        self._epoch = self._clock()
        self._lock = threading.Lock()
        self._events = []
        self._named_threads = set()
        self._tracks = {}
        self.dropped = 0
        self._pid = os.getpid()
        self._events.append({
            "ph": "M", "name": "process_name", "pid": self._pid, "tid": 0,
            "args": {"name": "aggregathor_tpu"},
        })
        if self.path is not None:
            # the claim sidecar marks this path owned by (writer_pid,
            # run_id) from THIS instant — what _claimed_by_other of a
            # later tracer reads before picking its own path; the trace
            # file itself is untouched until the first real save, so a
            # dead writer's completed trace survives a run that crashes
            # before saving anything
            _write_claim(self.path, run_id)

    # ------------------------------------------------------------------ #

    def now_us(self):
        """Microseconds since tracer epoch (the trace's ``ts`` clock)."""
        return (self._clock() - self._epoch) * 1e6

    def _append(self, event, tid):
        with self._lock:
            if tid not in self._named_threads:
                self._named_threads.add(tid)
                self._events.append({
                    "ph": "M", "name": "thread_name", "pid": self._pid,
                    "tid": tid, "args": {"name": threading.current_thread().name},
                })
            if len(self._events) >= MAX_EVENTS:
                self.dropped += 1
                return
            self._events.append(event)

    def complete(self, name, start_us, dur_us, cat="host", args=None):
        """One "X" (complete) event: a span of ``dur_us`` from ``start_us``."""
        self._append({
            "ph": "X", "name": name, "cat": cat, "pid": self._pid,
            "tid": threading.get_ident(), "ts": start_us,
            "dur": max(dur_us, 0.0), "args": args or {},
        }, threading.get_ident())

    def track(self, name):
        """A stable synthetic track (tid + thread_name metadata) for
        events that belong to a LOGICAL lane rather than a host thread —
        the per-worker submission timelines (parallel/bounded.py) render
        as one Perfetto track per worker regardless of which pool thread
        ran the submission.  Idempotent per name."""
        with self._lock:
            tid = self._tracks.get(name)
            if tid is None:
                tid = TRACK_TID_BASE + len(self._tracks)
                self._tracks[name] = tid
                self._named_threads.add(tid)
                self._events.append({
                    "ph": "M", "name": "thread_name", "pid": self._pid,
                    "tid": tid, "args": {"name": name},
                })
        return tid

    def complete_at(self, name, start_us, dur_us, tid, cat="host", args=None):
        """An "X" event on an EXPLICIT track with explicit timestamps —
        the retrospective form ``bounded-wait`` uses to lay a round's
        per-worker arrivals onto their tracks after the barrier closed."""
        self._append({
            "ph": "X", "name": name, "cat": cat, "pid": self._pid,
            "tid": int(tid), "ts": float(start_us),
            "dur": max(float(dur_us), 0.0), "args": args or {},
        }, int(tid))

    def counter(self, name, value, ts=None, cat="host", series="value"):
        """A "C" (counter) event — Perfetto renders each counter name as
        its own numeric track (the per-round deadline window, arrivals,
        stale rows, bytes on wire).  ``ts`` defaults to now."""
        self._append({
            "ph": "C", "name": name, "cat": cat, "pid": self._pid,
            "tid": 0, "ts": self.now_us() if ts is None else float(ts),
            "args": {series: float(value)},
        }, 0)

    def instant(self, name, cat="host", args=None):
        """One "i" (instant) event — discrete occurrences like a guardian
        rollback decision."""
        self._append({
            "ph": "i", "s": "t", "name": name, "cat": cat, "pid": self._pid,
            "tid": threading.get_ident(), "ts": self.now_us(),
            "args": args or {},
        }, threading.get_ident())

    def save(self):
        """Write the trace (atomic: tmp + rename).  Callable repeatedly —
        each call snapshots the events so far."""
        if self.path is None:
            return None
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "aggregathor_tpu.obs.trace",
                "run_id": self.run_id,
                "writer_pid": self._pid,
                "dropped_events": dropped,
            },
        }
        directory = os.path.dirname(self.path)
        if directory:
            os.makedirs(directory, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fd:
            json.dump(payload, fd)
        os.replace(tmp, self.path)
        return self.path

    @property
    def nb_events(self):
        with self._lock:
            return len(self._events)


# --------------------------------------------------------------------- #
# module-level lifecycle


def install(path, run_id=None, clock=None):
    """Enable tracing process-wide, writing to ``path`` on :func:`save`.
    Returns the :class:`Tracer`.  Installing over a live tracer replaces it
    (the old one is saved first).  What the start-up record already holds is
    replayed into the new tracer, so the file shows the process from its first
    start-up span and not from this call."""
    global _tracer
    if _tracer is not None:
        _tracer.save()
    _tracer = Tracer(path, run_id=run_id, clock=clock)
    _replay_startup(_tracer)
    return _tracer


def installed():
    """The active tracer, or None when tracing is disabled."""
    return _tracer


def save():
    """Flush the active tracer to its path (no-op when disabled)."""
    if _tracer is not None:
        return _tracer.save()
    return None


def uninstall(save=True):
    """Disable tracing; optionally flush first.  Returns the written path
    (or None)."""
    global _tracer
    tracer, _tracer = _tracer, None
    if tracer is not None and save:
        return tracer.save()
    return None


# --------------------------------------------------------------------- #
# spans


class span:
    """Context manager AND decorator for one named span.

    ``with span("dispatch", cat="train", step=3): ...`` times the block;
    ``@span("checkpoint.save")`` times every call of the decorated function.
    Either way the block also runs inside a ``jax.profiler.TraceAnnotation``
    of the span's name (one atomic test unless a profiler session is live);
    with the tracer disabled that and one global ``None`` check are the whole
    enter/exit path.  ``start()``/``stop()`` expose the manual form for spans whose
    lifetime does not nest lexically (the runner's host-gap span).
    """

    __slots__ = ("name", "cat", "args", "_t0", "_tracer", "_annotation")

    def __init__(self, name, cat="host", **args):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._tracer = None
        self._annotation = None

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        tracer = _tracer
        self._tracer = tracer
        if tracer is None:
            return self
        stack = _stack()
        if self.args is not None and stack:
            self.args = dict(self.args, parent=stack[-1], depth=len(stack))
        stack.append(self.name)
        self._t0 = tracer.now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        tracer = self._tracer
        if tracer is None:
            return False
        stack = _stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        args = self.args or {}
        if exc_type is not None:
            args = dict(args, error=exc_type.__name__)
        tracer.complete(self.name, self._t0, tracer.now_us() - self._t0,
                        cat=self.cat, args=args)
        return False

    # manual form (non-lexical lifetimes)
    start = __enter__

    def stop(self):
        self.__exit__(None, None, None)

    def __call__(self, fn):
        kind, name, cat, args = type(self), self.name, self.cat, self.args

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with kind(name, cat=cat, **args):
                return fn(*a, **kw)

        return wrapper


def instant(name, cat="host", **args):
    """Record an instant event (no-op when tracing is disabled)."""
    tracer = _tracer
    if tracer is not None:
        tracer.instant(name, cat=cat, args=args)


# --------------------------------------------------------------------- #
# the start-up record

#: most events the start-up record keeps; past it they are counted as dropped
#: (the largest set-up measured holds 15,509 at its first step: ResNet-50
#: under Bulyan, every distinct ``jnp`` call of the step a traced event)
STARTUP_MAX_EVENTS = 65536

_startup_lock = threading.Lock()
_startup_events = []
_startup_dropped = 0


def _startup_stack():
    """This thread's open start-up spans: their places in the record (None
    for one the full record dropped), innermost last."""
    stack = getattr(_local, "startup", None)
    if stack is None:
        stack = _local.startup = []
    return stack


def _startup_append(name, start_s, dur_s, args):
    """One event into the record under this thread's innermost open start-up
    span; returns the event, or None where the record is full."""
    global _startup_dropped
    stack = _startup_stack()
    event = {"name": name, "start_s": start_s, "dur_s": dur_s,
             "parent": stack[-1] if stack else None,
             "thread": threading.get_ident(), "args": dict(args)}
    with _startup_lock:
        if len(_startup_events) >= STARTUP_MAX_EVENTS:
            _startup_dropped += 1
            return None
        event["id"] = len(_startup_events)
        _startup_events.append(event)
    return event


class startup(span):
    """A :class:`span` of category ``startup`` that ALSO goes into the
    process's start-up record, tracer or none: ``with startup("startup.engine"):``
    or ``@startup("startup.mesh")``.

    The record is what a restart costs, named from inside: a process-wide
    list of ``{name, start_s, dur_s, parent, thread, args}`` on
    ``time.perf_counter``, ``parent`` the place in the record of the enclosing
    start-up span of the same thread (None at the top), ``dur_s`` None while
    the span is open.  It is on always, which the rest of this module is not,
    and may be: it is written at the boundaries a process passes once on its
    way to the first step (experiment, data, mesh, engine, state, the first
    call of each dispatcher) and by JAX's own stage events, which fire when a
    program is traced, lowered or loaded and never when one runs
    (``obs.profiler.listen_to_compiles``) — a dozen spans a process, and
    2,400 to 15,500 stage events by its first step in the benchmark's cells
    (19,800 by the end of a run; nearly all of them the nested traces of
    ``jnp`` calls) at a few microseconds each, against set-ups of 20 to 65 s
    in which no pair of runs could tell the record on from off (PERF.md §6,
    PR 37); none inside the step loop.  It is bounded (``STARTUP_MAX_EVENTS``,
    then a dropped count), so a process that rebuilds its engine for ever
    fills it and stops.  A tracer installed
    later (the runner installs after the backend is up) is handed what the
    record holds (:func:`install`); one installed already gets each span the
    usual way, once.

    ``note(bytes=...)`` adds arguments known only when the work is done."""

    __slots__ = ("_event",)

    def __init__(self, name, cat="startup", **args):
        super().__init__(name, cat=cat, **args)
        self._event = None

    def __enter__(self):
        self._event = _startup_append(self.name, time.perf_counter(), None, self.args)
        _startup_stack().append(None if self._event is None else self._event["id"])
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        _startup_stack().pop()
        if self._event is not None:
            self._event["dur_s"] = time.perf_counter() - self._event["start_s"]
        return False

    start = __enter__

    def note(self, **args):
        self.args = dict(self.args, **args)
        if self._event is not None:
            self._event["args"].update(args)


def startup_event(name, start_s, dur_s, **args):
    """A finished event for the start-up record from a clock reading made
    elsewhere (JAX's stage events, ``obs.profiler``): ``start_s`` on
    ``time.perf_counter``.  Its parent is this thread's open start-up span;
    events of this kind that nest in each other are sorted out by time when
    the record is read (:func:`startup_record`), since an inner one ends, and
    is reported, before the one round it.  An installed tracer gets it too."""
    _startup_append(name, start_s, dur_s, args)
    tracer = _tracer
    if tracer is not None and tracer._clock is time.perf_counter:
        tracer.complete_at(name, (start_s - tracer._epoch) * 1e6, dur_s * 1e6,
                           threading.get_ident(), cat="startup", args=args)


def _nest_by_time(events):
    """Re-parent the finished events that share a thread and a parent and
    contain one another in time: the innermost container becomes the parent.
    Spans entered and left on one thread never half-overlap, so one sweep in
    order of start does it; of two events over the very same interval the one
    reported later is the outer (it ended later in program order)."""
    groups = {}
    for event in events:
        if event["dur_s"] is not None:
            groups.setdefault((event["thread"], event["parent"]), []).append(event)
    for group in groups.values():
        group.sort(key=lambda e: (e["start_s"], -e["dur_s"], -e["id"]))
        open_ = []  # (end, id) of the events round the one at hand
        for event in group:
            end = event["start_s"] + event["dur_s"]
            while open_ and open_[-1][0] < end - 1e-6:  # JAX's clock ticks in 0.24 us
                open_.pop()
            if open_:
                event["parent"] = open_[-1][1]
            open_.append((end, event["id"]))


def startup_record():
    """A copy of the start-up record: ``{"events": [...], "dropped": n,
    "limit": STARTUP_MAX_EVENTS}``, an event's ``id`` its place in the list
    and ``parent`` the ``id`` of the event that encloses it — for a start-up
    span the one open on its thread when it was entered, for JAX's stage
    events also the stage event round them (a ``jit`` traced inside a traced
    function).  Self time is an event's ``dur_s`` less its children's."""
    with _startup_lock:
        events = [dict(event, args=dict(event["args"])) for event in _startup_events]
        dropped = _startup_dropped
    _nest_by_time(events)
    return {"events": events, "dropped": dropped, "limit": STARTUP_MAX_EVENTS}


def _replay_startup(tracer):
    """Hand a fresh tracer the finished events of the start-up record, on its
    own clock moved back to the first of them (a ``ts`` is never negative).  A
    tracer on a clock of its own (tests) shares no epoch with the record and
    gets nothing."""
    if tracer._clock is not time.perf_counter:
        return
    with _startup_lock:
        events = [event for event in _startup_events if event["dur_s"] is not None]
    if not events:
        return
    tracer._epoch = min(tracer._epoch, min(event["start_s"] for event in events))
    for event in events:
        tracer.complete_at(event["name"], (event["start_s"] - tracer._epoch) * 1e6,
                           event["dur_s"] * 1e6, event["thread"], cat="startup",
                           args=dict(event["args"], replayed=True))


def _abstract(leaf):
    """What lowering needs of one argument: shape, dtype and sharding."""
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=leaf.sharding,
                                    weak_type=leaf.weak_type)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def _abstract_signature(args, kwargs):
    """The abstract signature of a dispatch, or None for a call made under a
    trace (``jax.make_jaxpr`` of a dispatcher): that dispatches nothing."""
    if any(isinstance(leaf, jax.core.Tracer) for leaf in jax.tree.leaves((args, kwargs))):
        return None
    return jax.tree.map(_abstract, (args, kwargs))


class TracedCallable:
    """Wrap a callable (typically a jitted step function) so every call is
    a span — WITHOUT touching the callable itself: attribute access
    (``_cache_size``, ``lower``, ...) falls through to the wrapped function,
    so compile-count assertions and AOT APIs keep working, and the jit
    cache is untouched (tracing adds zero recompiles by construction).
    ``inner`` is the unwrapped callable.

    On its FIRST call only (an ``is None`` test on every later one) it
    remembers the abstract signature of its arguments, taken before the call
    since a step donates its state; :meth:`compiled_text` hands the compiled
    program's text to whoever wants to read it (``profiler.phase_table``).
    That first call is also a ``startup.first_call`` of the start-up record
    (:class:`startup`): the tracing, lowering and loading of the program
    happen inside it, and JAX's stage events land under it."""

    __slots__ = ("inner", "_name", "_cat", "_signature", "__weakref__")

    def __init__(self, name, fn, cat="dispatch"):
        object.__setattr__(self, "inner", fn)
        object.__setattr__(self, "_name", name)
        object.__setattr__(self, "_cat", cat)
        object.__setattr__(self, "_signature", None)

    def __call__(self, *args, **kwargs):
        if self._signature is None:
            signature = _abstract_signature(args, kwargs)
            if signature is not None:  # a call under a trace starts nothing
                object.__setattr__(self, "_signature", signature)
                with startup("startup.first_call", dispatcher=self._name,
                             program=getattr(self.inner, "__name__", None)):
                    with span(self._name, cat=self._cat):
                        return self.inner(*args, **kwargs)
        with span(self._name, cat=self._cat):
            return self.inner(*args, **kwargs)

    def compiled_text(self):
        """The text of the program this dispatcher runs, op_name metadata and
        all: lowered and compiled again from the first call's signature (the
        jit keeps its executable to itself), which leaves the jit's own cache
        as it was.  JAX keeps the lowering and the executable of a call it has
        made, so after the first dispatch this traces, compiles and loads
        nothing (0.1 to 0.9 s for the grid's step programs on a v5e)."""
        if self._signature is None:
            raise RuntimeError("%s has not been called yet: there is no program to read"
                               % self._name)
        args, kwargs = self._signature
        return self.inner.lower(*args, **kwargs).compile().as_text()

    def __getattr__(self, item):
        return getattr(self.inner, item)


#: every live callable ``traced()`` made (weakly held: an engine that is
#: rebuilt drops its old dispatchers with it)
_dispatchers = weakref.WeakSet()


def traced(name, fn, cat="dispatch"):
    """Shorthand: ``traced("train_step.dispatch", jax.jit(f))``."""
    made = TracedCallable(name, fn, cat=cat)
    _dispatchers.add(made)
    return made


def dispatchers():
    """The live callables ``traced()`` made, in no order."""
    return list(_dispatchers)


def validate_chrome_trace(payload):
    """Structural check that ``payload`` (a parsed trace file) is loadable
    Chrome trace JSON: ``traceEvents`` list, every event a dict with
    ``ph``/``name``/``pid``/``tid``, "X" events with numeric ``ts``/``dur``.
    Returns the event list; raises ``ValueError`` on violations.  Shared by
    tests and scripts/run_obs_smoke.sh so the smoke asserts the same schema
    the tests do."""
    if not isinstance(payload, dict) or not isinstance(payload.get("traceEvents"), list):
        raise ValueError("Chrome trace JSON wants a top-level traceEvents list")
    for event in payload["traceEvents"]:
        if not isinstance(event, dict):
            raise ValueError("trace event is not an object: %r" % (event,))
        for key in ("ph", "name", "pid", "tid"):
            if key not in event:
                raise ValueError("trace event missing %r: %r" % (key, event))
        if event["ph"] == "X":
            for key in ("ts", "dur"):
                if not isinstance(event.get(key), (int, float)):
                    raise ValueError("X event wants numeric %r: %r" % (key, event))
            if event["dur"] < 0:
                raise ValueError("X event with negative dur: %r" % (event,))
        elif event["ph"] == "i":
            if not isinstance(event.get("ts"), (int, float)):
                raise ValueError("i event wants numeric ts: %r" % (event,))
        elif event["ph"] == "C":
            if not isinstance(event.get("ts"), (int, float)):
                raise ValueError("C event wants numeric ts: %r" % (event,))
            args = event.get("args")
            if not isinstance(args, dict) or not args or not all(
                isinstance(v, (int, float)) for v in args.values()
            ):
                raise ValueError(
                    "C event wants a non-empty numeric args dict: %r" % (event,)
                )
    return payload["traceEvents"]
