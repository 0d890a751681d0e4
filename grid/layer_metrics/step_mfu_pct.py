"""The whole step's share of the chips' bf16 peak while the step program runs:
the model's forward and backward FLOPs a step (``cell.flops_per_step``, what
``mfu_pct`` counts) over ``device_step_ms`` and the mesh's peak.  It stands
beside the kernels' rooflines: a kernel taken off the path leaves its roofline
silent, this one still bounds the gain.  ``mfu_pct`` is the same FLOPs over the
host's clock of the timed window, where dispatches queue back to back: the two
agree as ``steps_per_s`` and 1000 / ``device_step_ms`` do."""

from cell import flops_per_step


def read(ctx):
    spec = ctx["cell"]
    return (100.0 * flops_per_step(spec) / (ctx["trace"]["device_step_ms"] / 1e3)
            / (spec["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
