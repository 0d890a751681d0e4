"""Seconds the step program took from jaxpr to an MLIR module inside its first
call (``compile.lower``; a Mosaic kernel is serialised here), from the
program's own start-up record (_startup.py)."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "step", "lower_s")
