"""Device time a step spends in the rule as the step runs it (phase ``gar``:
attack and quarantine masks, distances and their ``psum``, the rule and its
kernels), from the traced step cut by phase (phase_reduce.py).  ``gar_device_ms``
is the rule alone in a program of the harness's own."""

from phase_reduce import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "gar")
