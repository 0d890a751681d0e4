"""Seconds of the step program's ``compile.load`` inside its first call: the
cache key, the retrieval and deserialisation of the executable and its load
onto the chip(s) on a hit of the persistent cache, the compile on a miss; from
the program's own start-up record (_startup.py), whose printed line says which
it was and the retrieval's share."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "step", "load_s")
