"""Multiply-accumulates of one sequence through the forward pass of one chip's
share of DeepSeek-V3's layer trained on the next token
(grid/references/deepseek_v3.py).

Counted, layer by layer: the latent attention's projections at every position
with THE HEADS HELD (W_q, W_kva whole, W_kvb, W_o); scores over
qk_nope_head_dim + qk_rope_head_dim and values over v_head_dim, a head, over
the (query, key) pairs the causal mask allows, not L x L and not the lanes a
kernel pads to; a dense layer's three matrices; in a sparse layer the router,
the shared experts' unit and the expected visits to the held experts,
num_experts_per_tok x held / n_routed_experts a position; the output head.
Norms, RoPE, softmax, the router's bias and the embedding's gather are no
multiply-accumulates.
"""


def forward_macs(shape, vocabulary):
    length, d, heads = shape["sequence_length"], shape["hidden_size"], shape["num_attention_heads"]
    qk = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"]
    rank, dv = shape["kv_lora_rank"], shape["v_head_dim"]
    width = shape["moe_intermediate_size"]
    visits = shape["num_experts_per_tok"] * len(shape["experts_held"]) / shape["n_routed_experts"]
    projections = (d * heads * qk + d * (rank + shape["qk_rope_head_dim"])
                   + rank * heads * (shape["qk_nope_head_dim"] + dv) + heads * dv * d)
    pairs = length * (length + 1) // 2
    total = length * d * vocabulary
    for layer in range(shape["num_hidden_layers"]):
        if layer < shape["first_k_dense_replace"]:
            feed_forward = 3 * d * shape["intermediate_size"]
        else:
            feed_forward = (d * shape["n_routed_experts"]
                            + 3 * d * shape["n_shared_experts"] * width + visits * 3 * d * width)
        total += length * (projections + feed_forward) + pairs * heads * (qk + dv)
    return int(total)
