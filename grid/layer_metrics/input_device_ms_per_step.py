"""Device time a step spends making its batch: the per-worker draw from the
resident data set (phase ``sample``) and the in-step augmentation (``augment``),
from the traced step cut by phase (phase_reduce.py)."""

from phase_reduce import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "sample", "augment")
