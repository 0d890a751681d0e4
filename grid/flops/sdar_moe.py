"""Multiply-accumulates of one sequence through the forward pass of one chip's
share of SDAR trained by diffusion over blocks (grid/references/sdar_moe.py).

The model reads 2L positions ([x_t ; x_0]).  Counted: the four attention
projections at every position; scores and values over the (query, key) pairs
THE MASK ALLOWS, not 2L x 2L; the router at every position; the expected
visits to the held experts, experts_per_token x held / experts a position;
the output head at the noisy half only.  Norms, RoPE, softmax and the
embedding's gather are no multiply-accumulates.
"""


def allowed_pairs(length, block):
    """(query, key) pairs of the block-diffusion mask over 2L positions, with
    nb = L / block blocks: noisy->noisy the block diagonal, noisy->clean the
    blocks strictly earlier, clean->clean those not later, clean->noisy none."""
    nb = length // block
    per = block * block
    return per * nb + per * nb * (nb - 1) // 2 + per * nb * (nb + 1) // 2


def forward_macs(shape, vocabulary):
    length, d = shape["sequence_length"], shape["hidden_size"]
    heads, kv, dh = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    projections = 2 * d * heads * dh + 2 * d * kv * dh
    router = d * shape["num_experts"]
    visits = shape["num_experts_per_tok"] * len(shape["experts_held"]) / shape["num_experts"]
    experts = visits * 3 * d * shape["moe_intermediate_size"]
    attention = 2 * allowed_pairs(length, shape["block_length"]) * heads * dh
    layer = 2 * length * (projections + router + experts) + attention
    return int(shape["num_hidden_layers"] * layer + length * d * vocabulary)

