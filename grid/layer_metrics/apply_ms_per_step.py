"""Device time a step spends outside gradients, exchange and rule: the
optimizer's update (phase ``apply``), the loss sum, norms, health probe and
state hand-over (``epilogue``), and worker momentum, attack and wire codec on
the rows (``perturb``), from the traced step cut by phase (phase_reduce.py)."""

from phase_reduce import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "apply", "epilogue", "perturb")
