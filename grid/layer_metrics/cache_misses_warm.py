"""Persistent-cache misses of the run up to the end of the window
(jax.monitoring).  Zero in every run of a checkout but its first; anything else
shows in ``setup_s``."""


def read(ctx):
    return float(ctx["counters"]["cache_misses"])
