"""Summed self seconds of every stage (trace, lower, load) of every program but
the step's, up to the end of the step's first call: the harness's seeded
initialiser, the leaf-by-leaf programs of ``init_state``, the optimizer's;
their count is in the printed line (_startup.py)."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "other_programs", "self_s")
