"""Multiply-accumulates of one sequence through the forward pass of one chip's
share of Keye-VL-2.0's language layer trained on the next token
(grid/references/keye_vl2.py), and what the attention over the selected keys
alone has to do and to move.

Counted, layer by layer: the four attention projections and the indexer's
three at every position; the index scores over the CAUSAL (query, key) pairs,
indexer_num_heads x indexer_head_dim a pair (the indexer has to score every
key a query may read before it can choose); the main heads' scores and values
over the SELECTED pairs only, min(t + 1, topk) a query, not the causal
triangle and not L x L; the router; the expected visits to the held experts,
num_experts_per_tok x held / num_experts a position; the output head.  Norms,
RoPE, softmax, ReLU and its weighted sum, the sort and the embedding's gather
are no multiply-accumulates.
"""


def selected_pairs(length, topk):
    """(query, key) pairs a head reads in one sequence: query t reads
    min(t + 1, topk) keys."""
    full = min(length, topk)
    return full * (full + 1) // 2 + (length - full) * topk


def forward_macs(shape, vocabulary):
    length, d = shape["sequence_length"], shape["hidden_size"]
    heads, kv, dh = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    sa = shape["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    projections = 2 * d * heads * dh + 2 * d * kv * dh
    indexer = d * j * di + d * di + d * j
    router = d * shape["num_experts"]
    visits = shape["num_experts_per_tok"] * len(shape["experts_held"]) / shape["num_experts"]
    experts = visits * 3 * d * shape["moe_intermediate_size"]
    index_scores = length * (length + 1) // 2 * j * di
    attention = 2 * selected_pairs(length, sa["topk"]) * heads * dh
    layer = length * (projections + indexer + router + experts) + index_scores + attention
    return int(shape["num_hidden_layers"] * layer + length * d * vocabulary)


#: matrix products over a selected pair a head: the forward pass's scores and
#: values, and the backward pass's five (scores again, dP, dq, dk, dv)
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def selected_attention_flops(shape, workers):
    """Operations a step of the attention over the selected pairs alone, two a
    multiply-accumulate: every layer's forward, its recomputed forward (the
    layers are checkpointed) and its backward pass, every worker."""
    products = 2 * FORWARD_PRODUCTS + BACKWARD_PRODUCTS
    pairs = selected_pairs(shape["sequence_length"], shape["sa_config"]["topk"])
    return (2 * products * pairs * shape["num_attention_heads"] * shape["head_dim"]
            * shape["num_hidden_layers"] * workers)


def selected_attention_bytes(shape, workers):
    """Bytes a step that the same three passes have to move, float32: a
    forward reads q, k and v and writes the output; the backward pass reads
    those four and the output's cotangent and writes the three gradients.
    (The selection itself, a bit a pair at the least, is left out: a floor.)"""
    length, dh = shape["sequence_length"], shape["head_dim"]
    wide = length * shape["num_attention_heads"] * dh * 4
    narrow = length * shape["num_key_value_heads"] * dh * 4
    forward, backward = 2 * wide + 2 * narrow, 4 * wide + 4 * narrow
    return (2 * forward + backward) * shape["num_hidden_layers"] * workers
