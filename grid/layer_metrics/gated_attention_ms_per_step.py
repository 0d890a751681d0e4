"""Device time a step spends in the gated full-attention layer (part
``attention_full`` of models/qwen3_next.py: the layer's norm, the query
projection with its gate, k and v, the per-head norms, rotary on a quarter of a
head, ops/attention.py's kernel under ``Causal()`` at heads of 256 lanes — or
XLA's chunked softmax where the kernel does not take the shape —, the sigmoid
gate and ``W_o``; forward, recomputed forward and backward), from the traced
step cut by the model's own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "attention_full")
