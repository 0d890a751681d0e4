"""Device time a step spends in the model's expert layer (parts ``router``:
scores over all the experts, the largest few, their weights; and ``experts``:
each held expert's gather, gated products and weighted scatter-add, tile by
tile, forward, recomputed and backward), from the traced step cut by the
model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "router", "experts")
