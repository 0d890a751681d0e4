"""Device time a step spends turning worker rows into column blocks and back:
pad, transpose and ``all_to_all`` (phase ``reshard``), and the ``all_gather``
of the aggregated block with its cut to d (``gather``) - collectives and the
copies round them - from the traced step cut by phase (phase_reduce.py)."""

from phase_reduce import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "reshard", "gather")
