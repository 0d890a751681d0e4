"""Device-side profiling: step-windowed traces, compile + memory telemetry.

Three instruments, all host-side plumbing around ``jax.profiler`` /
``jax.monitoring`` (the jitted programs are never touched — the PR-4
zero-recompile discipline):

- :class:`ProfilerWindow` — a programmatic ``jax.profiler`` capture over an
  explicit step window (``--xprof A:B`` on the runner): the device trace
  starts when the step counter reaches ``A`` and stops at ``B``, and every
  dispatch inside the window is wrapped in a
  ``jax.profiler.StepTraceAnnotation`` so the PR-4 host spans join the
  device timeline on the profiler's step axis.  Under ``--unroll`` the
  boundaries land on chunk boundaries (the window is never allowed to
  split a compiled scan).
- :class:`CompileWatch` — compile observability: wrapped executables are
  polled for jit-cache growth after every call (one host attribute read);
  a cache miss becomes a named ``compile_cache_misses_total{executable=}``
  counter increment plus a tagged ``compile_cache_miss`` summary event
  carrying WHICH executable retraced and the abstract shapes of the
  dispatch that triggered it — the first diagnostic anyone needs when
  steps/s falls off a cliff.  Beside it the process's ONE ``jax.monitoring``
  listener (:func:`listen_to_compiles`) hears every program traced, lowered,
  compiled or loaded from the persistent cache, wrapped or not, by name and
  with its start and end: each is an event of the start-up record
  (``trace.startup_record``), and :func:`install_compile_listener` points the
  ``compile_backend_*`` gauges at its totals.
- :func:`install_memory_gauges` — live/peak device memory bytes from
  ``Device.memory_stats()`` as scrape-time registry gauges (absent on
  backends that do not report, e.g. XLA:CPU).
"""

import collections
import contextlib
import functools
import re
import threading
import time

import jax

from ..utils import UserException, info
from . import trace

#: what every step phase's ``jax.named_scope`` starts with (parallel/engine.py
#: ``phase``): keeps a phase apart from a flax module's or JAX's own
#: ``jvp(...)`` / ``transpose(...)`` elements of an ``op_name`` path
PHASE_PREFIX = "step."


# --------------------------------------------------------------------- #
# the compiled program, cut by phase

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
#: what the compiler inserts so that a LATER instruction finds its operand in
#: the layout or memory space it wants: booked to what it feeds
_RELAYOUTS = ("copy", "copy-start", "copy-done", "while")
#: what takes no time on the device: looked through, never booked
_PLUMBING = ("get-tuple-element", "tuple", "bitcast", "parameter", "constant")
_ELEMENT = re.compile(r" get-tuple-element\(.*\), index=(\d+)")
_BODY = re.compile(r" while\(.*\bbody=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
#: what a model's own scopes inside its loss start with (models/sdar.py:
#: ``model.attention``, ``model.experts``, ...).  They nest under ``step.grad``
#: and ``step.augment`` and are a second cut of the same program: the table of
#: one prefix takes no notice of the other's scopes
MODEL_PREFIX = "model."


@functools.lru_cache(maxsize=None)
def _scope(prefix):
    return re.compile(r"(?:^|[/(])%s([A-Za-z_]+)(?=[/)]|$)" % re.escape(prefix))


def phase_of(op_name, prefix=PHASE_PREFIX):
    """The innermost scope of ``prefix`` on an ``op_name`` path, or None:
    ``jit(many)/while/body/step.grad/vmap(jvp(conv))/mul`` -> ``grad``."""
    found = _scope(prefix).findall(op_name or "")
    return found[-1] if found else None


def _computations(hlo_text):
    """{computation: [(instruction, is root, its line)]} of an HLO text."""
    computations, current = {}, None
    for line in hlo_text.splitlines():
        if current is None:
            header = _COMPUTATION.match(line)
            if header:
                current = computations.setdefault(header.group(1), [])
        elif line.startswith("}"):
            current = None
        else:
            found = _INSTRUCTION.match(line)
            if found:
                current.append((found.group(2), bool(found.group(1)), line))
    return computations


def phase_table(hlo_text, prefix=PHASE_PREFIX):
    """``({instruction name: phase or None}, notes)`` of a compiled program's
    text (``compiled.as_text()``, ``TracedCallable.compiled_text()``), by the
    scopes that start with ``prefix``: the step's phases, or with
    ``MODEL_PREFIX`` the parts a model names inside its loss.

    An instruction's phase is the innermost ``step.<phase>`` scope of its
    ``metadata={op_name="..."}`` (parallel/engine.py ``phase``); a profiler
    capture names each device event after its instruction, so the table cuts
    any ``--xprof`` capture of the program by phase.  Two kinds of entry rest
    on more than the instruction's own metadata, and ``notes`` lists both so
    that a reader can say how much of its split is soft:

    - ``notes["soft"]``: fusions whose fused computation holds instructions of
      more than one phase.  A fusion takes the phase of its own metadata, else
      of its fused computation's root, else of most of its fused instructions,
      and its time is booked whole to that one phase.
    - ``notes["inherited"]``: instructions the compiler made and gave no
      ``op_name`` (layout copies, the ``dynamic-update-slice`` chain a
      ``concatenate`` becomes, the loop a relayout becomes).  A copy or a
      loop, put there so that a later instruction finds its operand laid out
      as it wants, takes the phase of the nearest instruction it feeds, else
      of the nearest that feeds it; any other takes the phase of the nearest
      instruction that feeds it, else of the nearest it feeds; the inside of a
      bare loop takes the loop's.

    ``None`` is left for an instruction whose ``op_name`` names no phase (the
    scan's ``while`` itself, its counter) and for a bare one next to none.
    Raises ``ValueError`` when no instruction is under any phase: JAX's
    persistent compilation cache leaves metadata out of its key, so a program
    loaded from a cache directory that a build WITHOUT the scopes filled is
    that build's program, and carries none."""
    computations = _computations(hlo_text)

    def own_phase(line):
        found = _OP_NAME.search(line)
        return phase_of(found.group(1), prefix) if found else None

    table, soft, operands, bare, moved, plumbing = {}, [], {}, set(), set(), set()
    home, caller = {}, {}  # instruction -> its computation -> the instruction that calls it
    roots, loops, element = {}, {}, {}  # computation -> root; while -> body; get-tuple-element -> index
    for computation, instructions in computations.items():
        for name, is_root, line in instructions:
            home[name] = computation
            if is_root:
                roots[computation] = name
            index = _ELEMENT.search(line)
            if index:
                element[name] = int(index.group(1))
            body_of = _BODY.search(line)
            if body_of:
                loops[name] = body_of.group(1)
            for called_name in _CALLED.findall(line):
                caller[called_name] = name
            phase = own_phase(line)
            body = line.split(" = ", 1)[1]
            opcode = _OPCODE.search(body)
            called = _CALLS.search(line)
            if opcode and opcode.group(1) == "fusion" and called:
                fused = computations.get(called.group(1), [])
                inside = [own_phase(fused_line) for _n, _r, fused_line in fused]
                held = collections.Counter(p for p in inside if p is not None)
                if len(held) > 1:
                    soft.append(name)
                if phase is None:
                    at_root = [p for (_n, at, _l), p in zip(fused, inside) if at]
                    phase = at_root[0] if at_root and at_root[0] is not None else (
                        held.most_common(1)[0][0] if held else None)
            table[name] = phase
            if phase is None and not _OP_NAME.search(line):
                bare.add(name)  # the compiler's own: it may inherit
                if opcode and opcode.group(1) in _RELAYOUTS:
                    moved.add(name)
                elif opcode and opcode.group(1) in _PLUMBING:
                    plumbing.add(name)
            if opcode:
                # the operand list ends at the first ")" that a "," or the line's end follows
                listed = re.match(r"[^)]*(?:\)(?!,|$)[^)]*)*", body[opcode.end():]).group(0)
                operands[name] = _OPERAND.findall(listed)
    if not any(phase is not None for phase in table.values()):
        raise ValueError(
            "none of the program's %d instructions is under a %s<phase> scope: either the "
            "program is not a step the engine built, or it was loaded from a persistent "
            "compilation cache (JAX_COMPILATION_CACHE_DIR, <checkout>/.jax_cache) that a "
            "build without the scopes filled - the cache key leaves metadata out, so the "
            "cached program is that build's; clear the directory or point at a fresh one"
            % (len(table), prefix))

    users = collections.defaultdict(list)
    for name, feeds in operands.items():
        for operand in feeds:
            users[operand].append(name)
    # a loop hands on, untouched, what its body only passes through: those
    # elements' readers are not the loop's to be booked to
    for loop, body in loops.items():
        root = operands.get(roots.get(body), [])
        passed = {index for index, fed in enumerate(root)
                  if element.get(fed) == index and not operands.get(operands[fed][0])}
        users[loop] = [user for user in users[loop] if element.get(user) not in passed]

    def nearest(start, neighbours):
        """Breadth-first from ``start`` through the compiler's own
        instructions to the first instruction that has a phase."""
        seen, queue = {start}, collections.deque([start])
        while queue:
            for other in neighbours.get(queue.popleft(), ()):
                if other in seen:
                    continue
                if table.get(other) is not None:
                    return table[other]
                if other in bare:
                    seen.add(other)
                    queue.append(other)
        return None

    inherited = {}
    while True:
        found = {}
        # pieces of a decomposed operation first, by what feeds them; then the
        # relayouts, by what they feed (which may be such a piece)
        for names, first, second in ((bare - moved - plumbing, operands, users),
                                     (moved, users, operands)):
            resolved = {}
            for name in names:
                if table[name] is None:
                    near = nearest(name, first) or nearest(name, second)
                    if near is not None:
                        resolved[name] = near
            table.update(resolved)
            found.update(resolved)
        # what is still bare inside a called computation (a loop the compiler
        # made of a relayout, say) is its caller's
        for name in bare - plumbing:
            if table[name] is None and table.get(caller.get(home[name])) is not None:
                found[name] = table[name] = table[caller[home[name]]]
        if not found:
            break
        inherited.update(found)
    return table, {"soft": soft, "inherited": sorted(inherited)}


# --------------------------------------------------------------------- #
# step-windowed device traces


class ProfilerWindow:
    """One ``jax.profiler`` capture over steps ``[begin, end)``.

    ``spec`` is the CLI form ``"A:B"`` (ints, ``A < B``).  The runner calls
    :meth:`maybe_start` before each dispatch and :meth:`maybe_stop` after
    the step counter advances; :meth:`annotate` wraps the dispatch in a
    ``StepTraceAnnotation`` while the capture is live (and is a no-op
    ``nullcontext`` otherwise, so the inactive path costs one attribute
    read).  :meth:`close` stops a capture left open at shutdown."""

    def __init__(self, spec, trace_dir):
        try:
            begin, _, end = str(spec).partition(":")
            self.begin, self.end = int(begin), int(end)
        except ValueError:
            raise UserException("--xprof wants A:B step integers (got %r)" % (spec,))
        if not 0 <= self.begin < self.end:
            raise UserException(
                "--xprof wants 0 <= A < B (got %d:%d)" % (self.begin, self.end)
            )
        self.trace_dir = trace_dir
        self.active = False
        self.done = False

    def maybe_start(self, step):
        """Open the capture when ``step`` enters the window (idempotent;
        never reopens a finished window)."""
        if self.active or self.done or step < self.begin or step >= self.end:
            return False
        jax.profiler.start_trace(self.trace_dir)
        self.active = True
        info("Profiler window open at step %d -> %r (steps %d:%d)"
             % (step, self.trace_dir, self.begin, self.end))
        return True

    def maybe_stop(self, step):
        """Close the capture once ``step`` passed the window end."""
        if not self.active or step < self.end:
            return False
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        info("Profiler window closed at step %d (device trace in %r)"
             % (step, self.trace_dir))
        return True

    def annotate(self, step):
        """Context manager for one dispatch: a ``StepTraceAnnotation``
        inside the live window (joining host spans to the device timeline
        per step), a free ``nullcontext`` outside it."""
        if not self.active:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation("train", step_num=int(step))

    def close(self):
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
        elif not self.done:
            from ..utils import warning

            # e.g. the whole window fell inside one unrolled chunk, or
            # before the resume offset — an empty trace dir with no
            # diagnostic would read as a silent success
            warning(
                "--xprof window %d:%d never opened (steps advance in "
                "chunk strides and must LAND inside the window; widen it "
                "past the unroll, or move it past the resume step)"
                % (self.begin, self.end)
            )


# --------------------------------------------------------------------- #
# compile observability

#: JAX's three stage events (``dispatch.log_elapsed_time``: one time span per
#: program and stage, ``fun_name`` the program) under the start-up record's
#: names.  The last fires for a load from the persistent cache as for a compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    BACKEND_COMPILE_EVENT: "compile.load",
}
#: what the persistent cache says of the load under way on the thread
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: the one table of the process's compiles: whether the listener is
#: registered, ``time.perf_counter`` less ``time.time`` at that moment (JAX's
#: spans are on the latter, the start-up record on the former), and count and
#: seconds of the load stage, which outlive the bounded record (the gauges)
_compiles = {"registered": False, "offset": 0.0, "count": 0, "seconds": 0.0}
_compiles_lock = threading.Lock()
_loading = threading.local()  # the cache's word on this thread's load, until its span comes


def _monitor_listener(event, *times, fun_name=None, **_):
    """THE ``jax.monitoring`` listener of the process, registered under all
    three of its signatures: ``(event)`` for the cache's hit and miss,
    ``(event, seconds)`` for its retrieval time, ``(event, start, end)`` for a
    stage of a program.  A stage becomes an event of the start-up record
    (``trace.startup_event``) under its ``STAGES`` name with ``program`` =
    ``fun_name`` (``many_p1`` for the trace, ``jit(many_p1)`` for the other
    two); a load also says ``cache`` (hit, miss, or none where the cache was
    not asked or did not keep the program) and ``retrieval_s``, both reported
    inside its span on its thread."""
    if not times:
        if event in _CACHE_EVENTS:
            _loading.cache = _CACHE_EVENTS[event]
    elif len(times) == 1:
        if event == _RETRIEVAL_EVENT:
            _loading.retrieval_s = float(times[0])
    elif event in STAGES:
        stage, (start, end) = STAGES[event], times
        args = {"program": fun_name}
        if stage == "compile.load":
            args.update(cache=getattr(_loading, "cache", "none"),
                        retrieval_s=getattr(_loading, "retrieval_s", None))
            _loading.cache, _loading.retrieval_s = "none", None
            with _compiles_lock:
                _compiles["count"] += 1
                _compiles["seconds"] += end - start
        trace.startup_event(stage, start + _compiles["offset"], end - start, **args)


def listen_to_compiles():
    """Register the listener, once a process (``jax.monitoring`` takes no
    listener back): ``utils.compile_cache.place_compile_cache`` calls this
    before anything compiles, so every entry point and the benchmark's harness
    have it without asking."""
    with _compiles_lock:
        if _compiles["registered"]:
            return
        _compiles["registered"] = True
        _compiles["offset"] = time.perf_counter() - time.time()
    jax.monitoring.register_event_listener(_monitor_listener)
    jax.monitoring.register_event_duration_secs_listener(_monitor_listener)
    jax.monitoring.register_event_time_span_listener(_monitor_listener)


def install_compile_listener(registry):
    """Point the scrape-time gauges ``compile_backend_total`` /
    ``compile_backend_seconds_total`` at the listener's totals of the load
    stage: EVERY program this process compiled or loaded from the persistent
    cache, wrapped by a ``CompileWatch`` or not.  Repeated calls only re-point
    the gauges; the trace and lower stages, and each program by name, are in
    the start-up record (``trace.startup_record``)."""
    listen_to_compiles()
    registry.gauge(
        "compile_backend_total",
        "Backend compiles observed by jax.monitoring in this process",
    ).set_function(lambda: float(_compiles["count"]))
    registry.gauge(
        "compile_backend_seconds_total",
        "Wall time jax.monitoring attributes to backend compiles",
    ).set_function(lambda: _compiles["seconds"])


def startup_summary():
    """The start-up record in one line, for an operator: each top-level
    start-up span by name with its seconds, the three stages of the first
    dispatcher called (the step program) with the cache's word on its load,
    and how many programs were loaded in how many seconds altogether
    (docs/observability.md "Reading a start-up")."""
    record = trace.startup_record()
    events = [event for event in record["events"] if event["dur_s"] is not None]
    parts = {}
    for event in events:
        if event["parent"] is None and event["name"].startswith("startup."):
            parts[event["name"]] = parts.get(event["name"], 0.0) + event["dur_s"]
    said = ["%s %.2f s" % (name.split(".", 1)[1], seconds) for name, seconds in parts.items()]
    first = next((event for event in events if event["name"] == "startup.first_call"), None)
    if first is not None:
        program = first["args"].get("program")
        stages = {event["name"]: event for event in events
                  if event["parent"] == first["id"]
                  and event["args"].get("program") in (program, "jit(%s)" % program)}
        told = ["%s %.2f s" % (stage.split(".", 1)[1], stages[stage]["dur_s"])
                for stage in STAGES.values() if stage in stages]
        if "compile.load" in stages:
            told[-1] += " (cache %s)" % stages["compile.load"]["args"]["cache"]
        said.append("step program %s: %s" % (program, ", ".join(told) or "traced before"))
    loads = [event["dur_s"] for event in events if event["name"] == "compile.load"]
    said.append("%d program(s) loaded in %.2f s" % (len(loads), sum(loads)))
    if record["dropped"]:
        said.append("%d event(s) dropped" % record["dropped"])
    return "Start-up: " + "; ".join(said)


def describe_abstract(args, kwargs=(), limit=12):
    """Compact abstract-shape descriptors (``f32[8,16,784]``-style) for the
    leaves of a dispatch's arguments — what a compile-miss event records as
    the offending shapes.  Truncated to ``limit`` leaves (the full pytree
    of a train state is hundreds of leaves; the batch and the first few
    state leaves identify the retrace)."""
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    out = []
    for leaf in leaves[:limit]:
        dtype = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dtype is None or shape is None:
            out.append(type(leaf).__name__)
        else:
            out.append("%s[%s]" % (
                jax.dtypes.canonicalize_dtype(dtype).name,
                ",".join(str(d) for d in shape),
            ))
    if len(leaves) > limit:
        out.append("... +%d leaves" % (len(leaves) - limit))
    return out


class _WatchedCallable:
    """Attribute-fallthrough wrapper (the ``TracedCallable`` idiom): every
    call compares the wrapped executable's jit-cache size before/after and
    reports growth to the owning :class:`CompileWatch`.  The wrapped
    callable is never modified — zero added recompiles by construction."""

    __slots__ = ("inner", "_watch", "_name")

    def __init__(self, watch, name, fn):
        object.__setattr__(self, "inner", fn)
        object.__setattr__(self, "_watch", watch)
        object.__setattr__(self, "_name", name)

    def _cache_len(self):
        probe = getattr(self.inner, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_len()
        out = self.inner(*args, **kwargs)
        after = self._cache_len()
        if before is not None and after is not None and after > before:
            self._watch.note_miss(self._name, after, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self.inner, item)


class CompileWatch:
    """Names compile-cache misses of the executables it wraps.

    ``wrap(name, fn)`` returns the watched callable (idempotent per
    ``(name, fn)`` pair — re-wrapping after a guardian rebuild reuses the
    name).  On a miss the watch increments
    ``compile_cache_misses_total{executable=name}`` and, when a
    ``SummaryWriter`` is attached, emits a tagged ``compile_cache_miss``
    event carrying the executable name, the new cache size and the
    abstract shapes of the triggering dispatch — so "why did step 512
    stall" is answered by the summary stream, not a profiler session."""

    def __init__(self, registry, summaries=None, step_provider=None):
        self._counter = registry.counter(
            "compile_cache_misses_total",
            "Jit-cache growth observed per wrapped executable "
            "(the first compile of each executable counts once)",
            labelnames=("executable",),
        )
        self.summaries = summaries
        self.step_provider = step_provider
        self.misses = []  # [(name, cache_size, shapes)] — tests / postmortems

    def wrap(self, name, fn):
        if isinstance(fn, _WatchedCallable) and fn._watch is self:
            return fn
        return _WatchedCallable(self, str(name), fn)

    def note_miss(self, name, cache_size, args, kwargs):
        shapes = describe_abstract(args, kwargs)
        self.misses.append((name, int(cache_size), shapes))
        self._counter.labels(executable=name).inc()
        if int(cache_size) <= 1:
            # the FIRST compile of an executable is expected — it counts
            # (the smoke asserts a nonzero compile counter) but does not
            # alarm; the summary event is reserved for true RETRACES, the
            # "steps/s fell off a cliff" diagnostic
            return
        if self.summaries is not None:
            step = 0
            if self.step_provider is not None:
                try:
                    step = int(self.step_provider())
                except Exception:
                    step = 0
            self.summaries.event(step, "compile_cache_miss", {
                "executable": name,
                "cache_size": int(cache_size),
                "arg_shapes": shapes,
            })


# --------------------------------------------------------------------- #
# device memory gauges


def install_memory_gauges(registry, devices=None):
    """Scrape-time live/peak device-memory gauges from
    ``Device.memory_stats()``.

    Registered per device that actually reports stats (TPU/GPU; XLA:CPU
    returns None and registers nothing).  Returns the number of devices
    instrumented.  The callbacks re-read ``memory_stats()`` at every
    scrape — live views, no writer loop, like serve's queue gauges."""
    devices = jax.devices() if devices is None else devices
    instrumented = 0
    live = registry.gauge(
        "device_memory_live_bytes", "Bytes currently allocated on the device",
        labelnames=("device",),
    )
    peak = registry.gauge(
        "device_memory_peak_bytes", "Peak bytes ever allocated on the device",
        labelnames=("device",),
    )
    for index, device in enumerate(devices):
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue

        def read(dev, key, fallback=0.0):
            def value():
                try:
                    return float((dev.memory_stats() or {}).get(key, fallback))
                except Exception:
                    return fallback
            return value

        label = str(index)
        live.labels(device=label).set_function(read(device, "bytes_in_use"))
        peak.labels(device=label).set_function(read(device, "peak_bytes_in_use"))
        instrumented += 1
    return instrumented
