"""Device time a step spends in the model's attention (part ``attention``: the
norm before it, the four projections, per-head norms, RoPE, the masked scores
and values by query chunk, forward, recomputed and backward), from the traced
step cut by the model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "attention")
