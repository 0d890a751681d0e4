"""Seconds the training arrays took to materialise on the host
(``startup.data_host``: the loaders and stand-ins of models/datasets.py, the
token rows of models/sdar.py and models/laguna.py), from the program's own
start-up record (_startup.py)."""

from layer_metrics._startup import part


def read(ctx):
    return part(ctx, "data_host_s")
