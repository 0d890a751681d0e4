"""Device time of the collective operations (all-to-all, all-reduce, all-gather
by XLA operation name) on device 0, per step.  Nothing to read on one chip."""


def read(ctx):
    value = ctx["trace"]["collective_ms_per_step"]
    return value if value > 0 else None
