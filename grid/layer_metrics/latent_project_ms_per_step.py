"""Device time a step spends around latent attention's scores (part
``mla_project`` of models/deepseek_v3.py: the norm, W_q, W_kva, the latent's
norm, W_kvb, RoPE on the rotary parts, the concatenation of a head's key, W_o
— forward, recomputed and backward), from the traced step cut by the model's
own parts (_model_parts.py)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mla_project")
