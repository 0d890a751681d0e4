"""Device time a step spends choosing each query's keys (part ``select`` of
models/keye_vl2.py: the exact top-k of a query's causal index scores — a
stable sort and a threshold — and the int8 pairs the attention kernel is
handed, with the selection's counters), from the traced step cut by the
model's own parts (_model_parts.py).  What a select that is not a sort would
shorten."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "select")
