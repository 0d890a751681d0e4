"""From a profiler trace to the per-layer numbers, and the checks that the
reduction makes of itself.

A trace is reduced from a neutral form, so that the small recorded one beside
this file (grid/recorded/) and a fresh ``.xplane.pb`` go through the same code:

    {"devices": {"<id>": {"modules": [[name, start_ns, duration_ns], ...],
                          "ops":     [[name, start_ns, duration_ns], ...]}},
     "host": [[name, start_ns, duration_ns], ...]}

(an operation that is a collective carries its XLA opcode as a fourth element)

``modules`` are whole compiled programs on the device's line, ``ops`` the
operations inside them (a ``while`` holds its body's operations: the leaves are
the ones that hold nothing), ``host`` the harness's own annotations
(``dispatch``, ``wait_loss``, ``input``) on the same clock.

The traced window is two or three whole dispatches after warm-up, each ended by
a wait: a few seconds of device time, never the timed window.  It runs from the
first device event of the step program to the last.  Busy is the union of the
leaf operations on one device's line; the idle share is 1 - busy / window,
averaged over the devices.
"""

import collections
import glob
import os
import re

#: Leaf operations must cover at least this share of the step program's module
#: spans.  By hand, on two dispatches of each one-chip cell (my chip runs,
#: PR 23: 1.33 million operation events in config 2's two), they cover 0.9846,
#: 0.9877 and 0.9845 of them: what is missing is the sequencer's hand-over
#: between operations, and on four chips the loops' own time and the waits
#: inside them (0.966 to 0.972 there).  A trace whose device buffer overflowed
#: keeps the module spans and loses operations, and reads far below: trace
#: fewer dispatches.  This is the ONE judgement of the leaves' cover:
#: phase_reduce.py compares nothing with the leaves.
COVER_MIN = 0.90
#: Busy time per step against the module span per step, and the busy share the
#: timed window implies (device_step_ms x steps_per_s) against 1 - idle share,
#: may differ by this much.  By hand (same runs) the first pair differs by
#: 1.2 to 1.6 % and the second by 1.2 to 1.6 points: each traced dispatch ends
#: in a wait (2 to 37 ms) that the timed window does not make.  PR 22's 0.995
#: against 0.24 is the kind of reading this refuses.
AGREE = 0.10

HOST_NAMES = ("dispatch", "wait_loss", "input")
COLLECTIVE_PREFIXES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute")


class TraceContradiction(ValueError):
    """The reduction's numbers contradict each other: none may be printed."""


class DroppedEvents(TraceContradiction):
    """The capture lost too many operation events to be read: a shorter one may
    not (run.py ``capture`` takes it once more, with a dispatch fewer)."""


def short_name(name):
    """``%fusion.3 = f32[8,512]{1,0:T(8,128)} fusion(...)`` -> ``fusion.3 f32[8,512]``:
    an operation's event is named by its whole HLO line."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    return "%s %s" % (op.lstrip("%"), rest.split(" ", 1)[0].split("{", 1)[0][:48])


def opcode(name):
    """The XLA opcode of an operation's HLO line (``fusion``, ``all-to-all``), or
    None.  The instruction's own name will not do: the TPU compiler names a
    collective after the JAX primitive (``all_to_all.14``, ``psum.3``)."""
    found = re.search(r" ([a-z][a-z0-9-]*)\(", name.split(" = ", 1)[-1])
    return found.group(1) if found else None


def _op_entry(event):
    entry = [short_name(event.name), int(event.start_ns), int(event.duration_ns)]
    code = opcode(event.name)
    return entry + [code] if code and code.startswith(COLLECTIVE_PREFIXES) else entry


def load_xplane(trace_dir):
    """The neutral form of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise TraceContradiction("the profiler wrote no .xplane.pb under %s" % trace_dir)
    data = ProfileData.from_file(paths[-1])
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                kind = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if kind:
                    lines[kind] = [_op_entry(event) for event in line.events]
            trace["devices"][plane.name.rsplit(":", 1)[1]] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace["host"] += [[event.name, int(event.start_ns), int(event.duration_ns)]
                                  for event in line.events if event.name in HOST_NAMES]
    return trace


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _length(intervals):
    return sum(end - start for start, end in intervals)


def _clip(intervals, spans):
    """The parts of ``intervals`` (merged) that lie inside ``spans`` (merged)."""
    out, j = [], 0
    for start, end in intervals:
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < end:
            out.append([max(start, spans[k][0]), min(end, spans[k][1])])
            k += 1
    return out


def _leaves_and_self_times(ops):
    """Leaf operations as intervals, and self time by operation name (an
    operation's duration less that of the operations it directly holds)."""
    ordered = sorted(ops, key=lambda op: (op[1], -op[2]))
    self_time = collections.Counter()
    leaves, stack = [], []  # stack of [name, start, end, children's time, has child]
    def close(entry):
        self_time[entry[0]] += (entry[2] - entry[1]) - entry[3]
        if not entry[4]:
            leaves.append([entry[1], entry[2]])
    for name, start, duration in (op[:3] for op in ordered):
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += duration
            stack[-1][4] = True
        stack.append([name, start, start + duration, 0, False])
    while stack:
        close(stack.pop())
    return leaves, self_time


def step_module(trace):
    """The name of the module that took most device time: the step program."""
    totals = collections.Counter()
    for lines in trace["devices"].values():
        for name, _start, duration in lines["modules"]:
            totals[name] += duration
    if not totals:
        raise TraceContradiction("no program ran on a device inside the traced window")
    return totals.most_common(1)[0][0]


def module_mean_ms(trace, fragment):
    """Mean device duration, in ms, of the modules whose name holds ``fragment``
    (device 0), or None when there is none."""
    lines = trace["devices"][min(trace["devices"], key=int)]
    durations = [duration for name, _start, duration in lines["modules"] if fragment in name]
    return sum(durations) / len(durations) / 1e6 if durations else None


def reduce(trace, steps_traced, steps_per_s=None):
    """The trace's numbers; raises ``TraceContradiction`` where they disagree.

    ``steps_traced`` is the number of training steps the traced dispatches
    made, ``steps_per_s`` the timed window's rate (checked when given)."""
    module = step_module(trace)
    per_device = []
    for device in sorted(trace["devices"], key=int):
        lines = trace["devices"][device]
        spans = _union([start, start + duration] for name, start, duration in lines["modules"]
                       if name == module)
        if not spans:
            raise TraceContradiction("device %s ran no %s" % (device, module))
        window = [spans[0][0], spans[-1][1]]
        inside = [op for op in lines["ops"] if op[1] >= window[0] and op[1] + op[2] <= window[1]]
        leaves, self_time = _leaves_and_self_times(inside)
        busy = _union(leaves)
        per_device.append({
            "device": device, "window": window, "busy": busy, "self_time": self_time,
            "collectives": {op[0] for op in inside if len(op) > 3},
            "module_ns": _length(spans),
            "cover": _length(_clip(busy, spans)) / _length(spans),
        })
    first = per_device[0]
    window_s = sum(d["window"][1] - d["window"][0] for d in per_device) / len(per_device) / 1e9
    busy_s = sum(_length(d["busy"]) for d in per_device) / len(per_device) / 1e9
    device_step_ms = first["module_ns"] / steps_traced / 1e6
    busy_step_ms = _length(first["busy"]) / steps_traced / 1e6
    idle_share = 1.0 - busy_s / window_s
    cover = min(d["cover"] for d in per_device)
    if cover < COVER_MIN:
        raise DroppedEvents(
            "operations cover %.3f of the step program's spans, under %.2f: the device's "
            "event buffer dropped events; trace fewer dispatches" % (cover, COVER_MIN))
    if abs(busy_step_ms / device_step_ms - 1.0) > AGREE:
        raise TraceContradiction(
            "busy time per step %.4f ms and device_step_ms %.4f disagree by more than %.0f %%"
            % (busy_step_ms, device_step_ms, 100 * AGREE))
    if steps_per_s is not None:
        implied = device_step_ms * steps_per_s / 1e3
        if abs(implied - (1.0 - idle_share)) > AGREE:
            raise TraceContradiction(
                "device_step_ms x steps_per_s says the device is busy %.3f of the time, "
                "the idle share says %.3f" % (implied, 1.0 - idle_share))
    gaps = collections.Counter()
    host = sorted(trace["host"], key=lambda e: e[1])
    edges = [first["window"][0]] + [t for span in first["busy"] for t in span] + [first["window"][1]]
    for start, end in zip(edges[0::2], edges[1::2]):
        if end > start:
            overlap = collections.Counter()
            for name, h_start, h_duration in host:
                shared = min(end, h_start + h_duration) - max(start, h_start)
                if shared > 0:
                    overlap[name] += shared
            gaps[overlap.most_common(1)[0][0] if overlap else "unattributed"] += end - start
    collective_ns = sum(first["self_time"][name] for name in first["collectives"])
    return {
        "step_module": module,
        "steps_traced": steps_traced,
        "device_step_ms": device_step_ms,
        "busy_step_ms": busy_step_ms,
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": idle_share,
        "cover": cover,
        "collective_ms_per_step": collective_ns / steps_traced / 1e6,
        "breakdown": {
            "device_ops": [[name, ns / 1e9] for name, ns in first["self_time"].most_common(10)],
            "idle_gaps": [[name, ns / 1e9] for name, ns in gaps.most_common(10)],
        },
    }
