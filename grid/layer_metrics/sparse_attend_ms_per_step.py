"""Device time a step spends in the attention over the selected keys (part
``sparse_attend`` of models/keye_vl2.py: ops/attention.py's fused kernel under
a mask that is data, forward, recomputed forward and backward, with XLA's
copies of q and the cotangent into its layout and of the gradients out of it;
the chunked XLA form off a TPU), from the traced step cut by the model's own
parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "sparse_attend")
