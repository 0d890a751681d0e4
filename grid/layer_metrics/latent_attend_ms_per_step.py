"""Device time a step spends in latent attention's scores, softmax and values
(part ``mla_attend`` of models/deepseek_v3.py: the fused kernel of
ops/attention.py run at 256 lanes for 192-wide scores over 128-wide values,
its padding and the slice that drops it, or the chunked XLA form — forward,
recomputed and backward), from the traced step cut by the model's own parts
(_model_parts.py).  What a kernel that carries the two widths itself would
shorten."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mla_attend")
