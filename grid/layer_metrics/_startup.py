"""Set-up by parts, as the PROGRAM records them: the start-up record of
``aggregathor_tpu.obs.trace`` (``startup_record``) holds a span for each
boundary the process passed on its way to the first step (``startup.experiment``,
``.data_host``, ``.mesh``, ``.engine``, ``.build_step``, ``.put``,
``.state_init``, ``.first_call``) and an event for each stage of each program
JAX traced, lowered and loaded (``compile.trace``, ``.lower``, ``.load``, with
``program`` and, on a load, the cache's ``hit`` / ``miss`` and its retrieval
time), each with the ``id`` of the event round it.  The harness's own laps
(``grid setup_parts``) time the same set-up from outside, in six laps; this
says what is inside the largest of them.

The record is cut at the end of the ``startup.first_call`` of the step
dispatcher — the one whose program is the traced ``ctx["trace"]["step_module"]``
(``jit_many_p1(...)`` there, ``many_p1`` here): what the harness does after that
(the phase readers lower the step again, the rule's probe, the reference) is not
set-up.  Self time is an event's seconds less its children's, never a sum of
durations: a ``jit`` traced inside a traced function is inside its seconds.

A program from before the record (the parent of PR 37) has no ``startup_record``:
there is then nothing to read, every reader returns None and the harness leaves
the metric out.
"""

import collections
import json

#: JAX's clock ticks in 0.24 us; an event that ends with the cut is inside it
EPSILON = 1e-6
STAGES = ("compile.trace", "compile.lower", "compile.load")


def reduce_record(record, step_module, programs):
    """The printed line's dict from a start-up ``record``, the traced step
    module's name and the ``__name__`` of every live dispatcher's program; or
    ``(None, why)``."""
    program = step_module.split("(", 1)[0]
    program = program[len("jit_"):] if program.startswith("jit_") else program
    if program not in programs:
        return None, "no live dispatcher runs the traced step program %r" % step_module
    events = [event for event in record["events"] if event["dur_s"] is not None]
    first_calls = [event for event in events if event["name"] == "startup.first_call"
                   and event["args"].get("program") == program]
    if not first_calls:
        return None, "the record holds no startup.first_call of the program %r" % program
    first = min(first_calls, key=lambda event: event["start_s"])
    cut = first["start_s"] + first["dur_s"] + EPSILON
    kept = {event["id"]: event for event in events if event["start_s"] + event["dur_s"] <= cut}
    children = collections.defaultdict(list)
    for event in kept.values():
        if event["parent"] in kept:
            children[event["parent"]].append(event)
    own = {ident: event["dur_s"] - sum(child["dur_s"] for child in children[ident])
           for ident, event in kept.items()}

    def descendants(event):
        found, queue = [], [event]
        while queue:
            for child in children[queue.pop()["id"]]:
                found.append(child)
                queue.append(child)
        return found

    def outermost(name):
        """Seconds of the events called ``name`` that lie in no other such."""
        inside = {child["id"] for event in kept.values() if event["name"] == name
                  for child in descendants(event)}
        return sum(event["dur_s"] for event in kept.values()
                   if event["name"] == name and event["id"] not in inside)

    # the step program's own three stages, inside its first call
    wanted = {"compile.trace": program, "compile.lower": "jit(%s)" % program,
              "compile.load": "jit(%s)" % program}
    stages = {}
    for event in sorted(descendants(first), key=lambda event: event["start_s"]):
        if wanted.get(event["name"]) == event["args"].get("program"):
            stages.setdefault(event["name"], event)
    of_step = {event["id"] for event in stages.values()}
    nested = collections.defaultdict(lambda: [0, 0.0])
    for stage in stages.values():
        for event in descendants(stage):
            of_step.add(event["id"])
            entry = nested[(stage["name"], event["name"], event["args"].get("program"))]
            entry[0] += 1
            entry[1] += own[event["id"]]
    load_args = stages["compile.load"]["args"] if "compile.load" in stages else {}
    step = {"first_call_s": first["dur_s"],
            "trace_s": stages["compile.trace"]["dur_s"] if "compile.trace" in stages else 0.0,
            "lower_s": stages["compile.lower"]["dur_s"] if "compile.lower" in stages else 0.0,
            "load_s": stages["compile.load"]["dur_s"] if "compile.load" in stages else 0.0,
            "trace_self_s": own[stages["compile.trace"]["id"]] if "compile.trace" in stages
            else 0.0,
            "cache": load_args.get("cache"), "retrieval_s": load_args.get("retrieval_s"),
            "nested": [{"in": stage, "event": name, "program": nested_program, "count": count,
                        "self_s": seconds}
                       for (stage, name, nested_program), (count, seconds) in sorted(
                           nested.items(), key=lambda item: -item[1][1])[:5]]}

    others = [event for event in kept.values()
              if event["name"] in STAGES and event["id"] not in of_step]
    top, order = {}, []
    for event in sorted(kept.values(), key=lambda event: event["start_s"]):
        if event["parent"] in kept:
            continue
        name = event["name"] if event["name"].startswith("startup.") else "compile.outside_spans"
        if name not in top:
            top[name] = {"name": name, "count": 0, "total_s": 0.0, "self_s": 0.0}
            order.append(name)
        top[name]["count"] += 1
        top[name]["total_s"] += event["dur_s"]
        top[name]["self_s"] += own[event["id"]]
    began = min(event["start_s"] for event in kept.values())
    return {
        "step_program": program, "dispatcher": first["args"].get("dispatcher"),
        "first_calls": len(first_calls), "cut_s": cut - EPSILON - began,
        "top": [top[name] for name in order], "named_s": sum(t["total_s"] for t in top.values()),
        "step": step,
        "other_programs": {
            "loaded": sum(event["name"] == "compile.load" for event in others),
            "events": len(others), "self_s": sum(own[event["id"]] for event in others)},
        "data_host_s": outermost("startup.data_host"),
        "state_init_s": outermost("startup.state_init"),
        "events": len(record["events"]), "after_cut": len(events) - len(kept),
        "dropped": record["dropped"], "limit": record.get("limit"),
    }, None


def startup(ctx):
    """The reduced record, computed and printed once and kept in ``ctx``; None
    when the program has nothing to read it from."""
    if "startup" in ctx:
        return ctx["startup"]
    ctx["startup"] = None
    try:
        from aggregathor_tpu.obs.trace import dispatchers, startup_record
    except ImportError:
        print("grid startup: the program keeps no start-up record "
              "(obs.trace.startup_record): nothing to read", flush=True)
        return None
    found, why = reduce_record(
        startup_record(), ctx["trace"]["step_module"],
        {getattr(dispatcher, "__name__", None) for dispatcher in dispatchers()})
    if found is None:
        print("grid startup: %s" % why, flush=True)
        return None
    print("grid startup %s" % json.dumps(found), flush=True)
    ctx["startup"] = found
    return found


def part(ctx, *path):
    """One number of the reduced record (``part(ctx, "step", "trace_s")``), or
    None (see ``startup``)."""
    found = startup(ctx)
    for key in path:
        if found is None:
            return None
        found = found[key]
    return found
