"""The traced step's device time, cut by the phases the program names.

The program brackets every part of its step in a ``step.<phase>`` named scope
(parallel/engine.py ``PHASES``) and reads from its own compiled text which
instruction belongs to which phase (``obs.profiler.phase_table``); the
recorded trace names each device event after its instruction.  So the cut needs
nothing of the trace that ``trace_reduce.load_xplane`` does not already keep:

    ctx["raw_trace"]  -> self time per instruction, inside the step program's
                         spans on device 0 (``trace_reduce._leaves_and_self_times``)
    the one ``obs.trace.dispatchers()`` entry whose program is
    ctx["trace"]["step_module"] -> {instruction: phase}

Self times telescope: an operation's self time is its duration less that of
the operations it directly holds, so the phases and the unattributed rest add
up to the duration of the TOP-LEVEL operations — the module span less the
hand-overs between them — whatever became of the leaves.  That sum is held to
the module span a step (``device_step_ms``) within ``SPAN_AGREE``; it is not
compared with the busy time, which is the leaves alone: how much of the spans
the leaves cover is ``trace_reduce.reduce``'s to judge, once.  A sum off the
span, or phases that cover under ``COVER_MIN`` of the whole, is a
``TraceContradiction``.

A program from before the scopes (the parent of PR 24) has no ``dispatchers``
and no ``phase_table``: there is then nothing to read, every reader returns
None and the harness leaves the metric out.
"""

import collections
import json
import time

from trace_reduce import TraceContradiction, _leaves_and_self_times, _union

#: Phases must cover this share of the operations' time.  A refactor that drops
#: a scope shows here first; a cache directory that a build without the scopes
#: filled gives a program with none (``phase_table`` raises, naming it).
COVER_MIN = 0.90
#: The operations' time per step and ``device_step_ms`` may differ by this
#: much.  In 49 traced runs of the seven cells (PRs 33 to 41, two among them
#: that lost 3 % of their leaves) and PR 42's 24 the sum reads 0.0001 to
#: 0.0121 % UNDER the span (the hand-overs between top-level operations; most
#: on four chips), 0.0403 % in one four-chip run that compiled, and 0.0005 %
#: on the recorded trace with 0 to 5 % of its events deleted: twelve-fold room
#: over the one, forty-fold over the rest.  What it refuses: another program's
#: operations between two dispatches, a top-level operation lost, a
#: ``device_step_ms`` of another trace.  (An event recorded twice nests in its
#: twin and moves nothing.)
SPAN_AGREE = 0.005


def instruction(op_name):
    """``fusion.3 f32[8,512]`` (trace_reduce.short_name) -> ``fusion.3``."""
    return op_name.split(" ", 1)[0]


def program_table(step_module, dispatchers, phase_table):
    """``(table, notes, seconds)`` of the one dispatcher among ``dispatchers``
    whose compiled program is the traced ``step_module``
    (``jit_many(9439790079306549169)`` in a trace, ``HloModule jit_many`` in
    the text); none, or two, is a contradiction."""
    wanted = step_module.split("(", 1)[0]
    found = []
    begin = time.perf_counter()
    for dispatcher in dispatchers:
        if "jit_%s" % getattr(dispatcher, "__name__", "") != wanted:
            continue
        try:
            text = dispatcher.compiled_text()
        except RuntimeError:  # built, never called: it has no program
            continue
        if text.split(",", 1)[0].split()[-1] == wanted:
            found.append(text)
    if len(found) != 1:
        raise TraceContradiction(
            "%d of the program's %d dispatchers run the traced step program %r: the phase "
            "table needs exactly one" % (len(found), len(dispatchers), step_module))
    table, notes = phase_table(found[0])
    return table, notes, time.perf_counter() - begin


def cut(raw_trace, step_module, steps_traced, device_step_ms, table, notes):
    """Self time per phase per step, in ms, on device 0 inside the spans of
    ``step_module``; raises ``TraceContradiction`` where the cut contradicts
    the reduction's ``device_step_ms`` or covers too little."""
    lines = raw_trace["devices"][min(raw_trace["devices"], key=int)]
    spans = _union([start, start + duration] for name, start, duration in lines["modules"]
                   if name == step_module)
    if not spans:
        raise TraceContradiction("device 0 ran no %s" % step_module)
    inside = [op for op in lines["ops"]
              if op[1] >= spans[0][0] and op[1] + op[2] <= spans[-1][1]]
    _leaves, self_time = _leaves_and_self_times(inside)
    soft, inherited = set(notes["soft"]), set(notes["inherited"])
    per_phase, marked = collections.Counter(), collections.Counter()
    for name, ns in self_time.items():
        per_phase[table.get(instruction(name)) or "unattributed"] += ns
        for mark, names in (("soft_fusions", soft), ("inherited", inherited)):
            if instruction(name) in names:
                marked[mark] += ns

    def to_ms(ns):
        return ns / steps_traced / 1e6

    total_ms = to_ms(sum(per_phase.values()))
    unattributed_ms = to_ms(per_phase.pop("unattributed", 0))
    phases = {name: to_ms(ns) for name, ns in per_phase.items()}
    cover = (total_ms - unattributed_ms) / total_ms if total_ms else 0.0
    if cover < COVER_MIN:
        raise TraceContradiction(
            "phases cover %.3f of the step's operations, under %.2f: a scope was dropped from "
            "the step body, or the table is another program's" % (cover, COVER_MIN))
    if abs(total_ms / device_step_ms - 1.0) > SPAN_AGREE:
        raise TraceContradiction(
            "phases and the rest add up to %.4f ms a step, device_step_ms is %.4f: they differ "
            "by more than %.1f %%: another program's operations lie between the dispatches, "
            "or a top-level operation was lost" % (total_ms, device_step_ms, 100 * SPAN_AGREE))
    return {"phases": phases, "unattributed_ms": unattributed_ms, "total_ms": total_ms,
            "cover": cover, "soft_fusion_ms": to_ms(marked["soft_fusions"]),
            "inherited_ms": to_ms(marked["inherited"])}


def phases(ctx):
    """The cut of this run's traced steps, computed once and kept in ``ctx``;
    None when the program has nothing to read it from."""
    if "phases" in ctx:
        return ctx["phases"]
    try:
        from aggregathor_tpu.obs.profiler import phase_table
        from aggregathor_tpu.obs.trace import dispatchers
    except ImportError:
        print("grid phases: the program names no phases (no obs.trace.dispatchers, no "
              "obs.profiler.phase_table): nothing to read", flush=True)
        ctx["phases"] = None
        return None
    reduced = ctx["trace"]
    table, notes, table_s = program_table(reduced["step_module"], dispatchers(), phase_table)
    found = cut(ctx["raw_trace"], reduced["step_module"], reduced["steps_traced"],
                reduced["device_step_ms"], table, notes)
    print("grid phases %s" % json.dumps(dict(found, table_s=table_s)), flush=True)
    ctx["phases"] = found
    return found


def per_step_ms(ctx, *names):
    """The summed ms a step of the phases ``names``, or None (see ``phases``)."""
    found = phases(ctx)
    return None if found is None else sum(found["phases"].get(name, 0.0) for name in names)
