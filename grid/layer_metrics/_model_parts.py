"""The traced step's device time cut by the parts the MODEL names inside its
loss: ``model.<part>`` named scopes (models/sdar.py: ``embed``, ``attention``,
``router``, ``experts``, ``head``, and ``noise`` inside the in-step noising),
read from the step program's compiled text by the same table that cuts it by
``step.<phase>`` (``obs.profiler.phase_table`` with ``MODEL_PREFIX``), and met
with the self time of each instruction on device 0 as phase_reduce.py meets
the phases.  Forward, the recomputed forward of the checkpointed layers and
the backward pass all carry the scope of the part they belong to.

The model's parts are a cut of ``step.grad`` and ``step.augment`` only, so
they cover far less than the step and no cover is demanded of them; what no
part names (the scan's own copies, the sampling, the rows, the rule, the
update) is ``unnamed``; the step's own phase table is printed beside them
(phase_reduce.py ``phases``: since PR 42 the six phase metrics list the three
language cells and have read it by then; in a cell that a later PR adds, and
that they do not list, nothing else prints it).  A program without
``MODEL_PREFIX`` (the parent of PR 31), or whose model names no part, gives
nothing to read: every reader returns None and the harness leaves the metric out.
"""

import collections
import json

from phase_reduce import instruction, phases, program_table
from trace_reduce import TraceContradiction, _leaves_and_self_times, _union


def parts(ctx):
    """{"parts": {part: ms a step}, "unnamed_ms": ...}, computed once and kept
    in ``ctx``; None when the program has nothing to read it from."""
    if "model_parts" in ctx:
        return ctx["model_parts"]
    ctx["model_parts"] = None
    try:
        from aggregathor_tpu.obs.profiler import MODEL_PREFIX, phase_table
        from aggregathor_tpu.obs.trace import dispatchers
    except ImportError:
        print("grid model parts: the program has no second table of scopes "
              "(obs.profiler.MODEL_PREFIX): nothing to read", flush=True)
        return None
    reduced, raw = ctx["trace"], ctx["raw_trace"]
    try:  # the step's own phases beside the model's parts (the "grid phases" line), for a
        phases(ctx)  # cell the phase metrics do not list: there nothing else prints them
    except TraceContradiction as contradiction:
        print("grid phases: %s" % contradiction, flush=True)
    try:
        table, _notes, _seconds = program_table(
            reduced["step_module"], dispatchers(), lambda text: phase_table(text, MODEL_PREFIX))
    except ValueError:  # no instruction under any model.<part> scope
        print("grid model parts: the step program names no model part", flush=True)
        return None
    lines = raw["devices"][min(raw["devices"], key=int)]
    spans = _union([start, start + duration] for name, start, duration in lines["modules"]
                   if name == reduced["step_module"])
    inside = [op for op in lines["ops"]
              if op[1] >= spans[0][0] and op[1] + op[2] <= spans[-1][1]]
    _leaves, self_time = _leaves_and_self_times(inside)
    per_part = collections.Counter()
    for name, ns in self_time.items():
        per_part[table.get(instruction(name)) or "unnamed"] += ns
    to_ms = lambda ns: ns / reduced["steps_traced"] / 1e6
    unnamed_ms = to_ms(per_part.pop("unnamed", 0))
    found = {"parts": {name: to_ms(ns) for name, ns in per_part.items()},
             "unnamed_ms": unnamed_ms}
    print("grid model parts %s" % json.dumps(found), flush=True)
    ctx["model_parts"] = found
    return found


def per_step_ms(ctx, *names):
    """The summed ms a step of the parts ``names``, or None (see ``parts``)."""
    found = parts(ctx)
    return None if found is None else sum(found["parts"].get(name, 0.0) for name in names)
