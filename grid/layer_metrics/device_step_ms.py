"""Device time of one step: the step program's module spans over the steps
traced (device 0)."""


def read(ctx):
    return ctx["trace"]["device_step_ms"]
