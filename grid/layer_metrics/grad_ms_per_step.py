"""Device time a step spends on the workers' forward and backward passes
(phase ``grad``) and on laying their gradients out as (k, d) rows
(``flatten``), from the traced step cut by phase (phase_reduce.py)."""

from phase_reduce import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "grad", "flatten")
