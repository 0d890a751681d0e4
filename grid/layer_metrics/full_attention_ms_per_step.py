"""Device time a step spends in the model's full-attention layers (part
``attention_full`` of models/laguna.py: the norm before it, the four
projections, YaRN's partial RoPE, causal scores and values by query chunk over
every earlier key, forward, recomputed and backward), from the traced step cut
by the model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "attention_full")
