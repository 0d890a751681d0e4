"""Multiply-accumulates of one sequence through the forward pass of one chip's
share of Laguna trained on the next token (grid/references/laguna.py).

Counted, layer by layer from the shape's three lists: the four attention
projections at every position with THAT LAYER'S head count; scores and values
over the (query, key) pairs THE LAYER'S MASK ALLOWS (causal, and on a sliding
layer inside the window), not L x L; a dense layer's three matrices; in a
sparse layer the router, the shared expert and the expected visits to the held
experts, experts_per_token x held / experts a position; the output head.
Norms, RoPE, softmax and the embedding's gather are no multiply-accumulates.
"""


def allowed_pairs(length, window=None):
    """(query, key) pairs with j <= i and, inside a window, i - j < window."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def forward_macs(shape, vocabulary):
    length, d, dh = shape["sequence_length"], shape["hidden_size"], shape["head_dim"]
    kv = shape["num_key_value_heads"]
    visits = shape["num_experts_per_tok"] * len(shape["experts_held"]) / shape["num_experts"]
    total = length * d * vocabulary
    for kind, mlp, heads in zip(shape["layer_types"], shape["mlp_layer_types"],
                                shape["num_attention_heads_per_layer"]):
        per_position = 2 * d * heads * dh + 2 * d * kv * dh
        if mlp == "dense":
            per_position += 3 * d * shape["intermediate_size"]
        else:
            per_position += (d * shape["num_experts"]
                             + 3 * d * shape["shared_expert_intermediate_size"]
                             + visits * 3 * d * shape["moe_intermediate_size"])
        window = shape["sliding_window"] if kind == "sliding_attention" else None
        total += length * per_position + 2 * allowed_pairs(length, window) * heads * dh
    return int(total)
