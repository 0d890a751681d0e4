"""Seconds of ``engine.init_state`` (``startup.state_init``): the optimizer's
state, the side buffers and the puts, with the programs loaded inside it; from
the program's own start-up record (_startup.py)."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "state_init_s")
