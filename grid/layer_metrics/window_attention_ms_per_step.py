"""Device time a step spends in the model's sliding-window attention layers
(part ``attention_window`` of models/laguna.py: the norm before it, the four
projections, RoPE, scores and values by query chunk over the window's keys
alone, forward, recomputed and backward), from the traced step cut by the
model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "attention_window")
