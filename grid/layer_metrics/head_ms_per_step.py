"""Device time a step spends in the model's output head (part ``head``: the
last norm, the vocabulary product at the noisy half, log-softmax and the
weighted loss, forward and backward), from the traced step cut by the model's
own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "head")
