"""Device time a step spends in the model's feed-forward layers (parts
``dense_mlp``: the leading layer's gated unit; ``router``: scores over all the
experts, the largest few (in ``models/deepseek_v3.py`` chosen under the bias),
their weights; ``experts``: every held expert over every position under the
router's weight; ``shared_expert``: the unit every position passes — forward,
recomputed and backward), from the traced step cut by the model's own parts
(_model_parts.py).  One reader for every model that names these four parts
(cells 6 and 7)."""

from layer_metrics._model_parts import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dense_mlp", "router", "experts", "shared_expert")
