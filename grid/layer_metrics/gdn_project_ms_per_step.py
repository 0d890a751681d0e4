"""Device time a step spends in a Gated DeltaNet layer round its recurrence
(part ``gdn_project`` of models/qwen3_next.py: the layer's norm, the two fused
projections, the depthwise causal convolution and SiLU, the heads' L2 norm,
beta and g, the gated per-head norm and ``W_o`` — forward, recomputed forward
and backward), from the traced step cut by the model's own parts
(_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "gdn_project")
