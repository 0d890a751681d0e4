"""Device time a step spends in the model's feed-forward layers (parts
``dense_mlp``: the leading layer's gated unit; ``router``: sigmoid scores over
all the experts, the choice under the bias, the weights; ``experts``: every
held expert over every position under the router's weight; ``shared_expert``:
the shared experts' one unit — forward, recomputed and backward), from the
traced step cut by the model's own parts (_model_parts.py).  The same parts as
``ffn_ms_per_step``, under a name of this cell's own: an accepted metric's list
of cells is not this PR's to edit."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dense_mlp", "router", "experts", "shared_expert")
