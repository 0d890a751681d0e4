"""Device time a step spends in the chunked gated delta rule (part
``delta_rule`` of models/qwen3_next.py: the cumulative decay, the chunks'
triangular systems, the products of every chunk made at once and the scan that
carries one state a head across the chunks — forward, the checkpointed layers'
recomputed forward and backward; the core alone, without the projections, the
convolution and the gated norm round it), from the traced step cut by the
model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "delta_rule")
