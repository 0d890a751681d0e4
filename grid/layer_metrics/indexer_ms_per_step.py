"""Device time a step spends in the learned indexer (part ``indexer`` of
models/keye_vl2.py: its three projections, the key's LayerNorm, rotary, and
the index scores I[t, s] of every causal pair, 16 heads of 64 under a ReLU and
a query's weights — forward and the checkpointed layers' recomputed forward;
no gradient reaches it), from the traced step cut by the model's own parts
(_model_parts.py).  What a fused indexer kernel would shorten."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "indexer")
