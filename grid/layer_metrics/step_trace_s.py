"""Seconds of Python tracing of the step program inside its first call
(``compile.trace`` of the step, every nested ``jit`` and kernel wrapper traced
inside it included), from the program's own start-up record (_startup.py)."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "step", "trace_s")
