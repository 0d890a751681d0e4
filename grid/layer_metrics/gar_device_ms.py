"""Device time of the cell's rule alone, at the shape each chip aggregates
(n rows, ceil(d / chips) columns, float32), from the trace of the harness's own
jitted wrapper ``grid_gar_probe`` run after the window."""


def read(ctx):
    return ctx["gar_probe"]["device_ms"]
