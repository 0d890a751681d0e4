"""Multiply-accumulates of one sequence through the forward pass of one chip's
share of Qwen3-Next trained on the next token (grid/references/qwen3_next.py),
and what the chunked gated delta rule alone has to do and to move.

Counted, layer by layer from ``full_attention_interval``.  A Gated DeltaNet
layer: the two fused projections and ``W_o`` at every position; the depthwise
convolution's taps, one multiply-accumulate a tap a channel a position; the
delta rule's products in the CHUNKED form (``delta_rule_chunk_macs``: what the
recurrence costs any program that does not walk it token by token).  A full
layer: its four projections (the query's twice as wide: the gate), scores and
values over the causal pairs, not L x L.  Every layer: the router, the shared
expert and its gate, the expected visits to the held experts,
num_experts_per_tok x held / num_experts a position.  Then the output head.
Norms, RoPE, softmax, the gates' sigmoids, softplus and exponentials, the
cumulative sums and the embedding's gather are no multiply-accumulates.

**The chunked delta rule's necessary work**, a chunk of C positions of one head
of widths Dk and Dv (``delta_rule_chunk_macs``), with P = C (C - 1) / 2 the
pairs strictly below a chunk's diagonal and P' = C (C + 1) / 2 those on or
below it:

    P Dk          diag(beta) K K^T below the diagonal: the system's matrix
    P (Dk + Dv)   its solution against the two right-hand sides (U and W) by
                  forward substitution
    P' Dk         Q K^T on and below the diagonal
    P' Dv         those scores times the chunk's writes
    3 C Dk Dv     against the carried state: what it predicts (W S), what it
                  answers (Q S), its update (K^T V')

A step makes them three times over and once more: the forward pass, the
checkpointed layers' recomputed forward, and a backward pass of two products a
forward product — ``delta_rule_flops``, two operations a multiply-accumulate,
every value head, DeltaNet layer and worker.  ``delta_rule_bytes``: what the
same passes have to move at the least, float32: a forward reads q and k (at the
KEY heads' width: a program may hand one key head to the value heads it serves),
v, g and beta and writes o; the backward pass reads those and o's cotangent and
writes the five gradients.  The chunk's matrices and the states between chunks
are left out: a program that keeps them on the chip moves none of them, so the
two are a floor, and the share of it cannot pass 100.
"""


def layer_kinds(shape):
    interval = shape["full_attention_interval"]
    return ["full" if (i + 1) % interval == 0 else "delta"
            for i in range(shape["num_hidden_layers"])]


def delta_rule_chunk_macs(chunk, dk, dv):
    """Multiply-accumulates of one chunk of one head, forward (the table above)."""
    strict, inclusive = chunk * (chunk - 1) // 2, chunk * (chunk + 1) // 2
    return (strict * dk + strict * (dk + dv) + inclusive * dk + inclusive * dv
            + 3 * chunk * dk * dv)


def delta_rule_macs(shape):
    """The same of one sequence through one DeltaNet layer, every value head."""
    chunks = -(-shape["sequence_length"] // shape["delta_chunk"])
    return (chunks * shape["linear_num_value_heads"] * delta_rule_chunk_macs(
        shape["delta_chunk"], shape["linear_key_head_dim"], shape["linear_value_head_dim"]))


def forward_macs(shape, vocabulary):
    length, d = shape["sequence_length"], shape["hidden_size"]
    keys = shape["linear_num_key_heads"] * shape["linear_key_head_dim"]
    values = shape["linear_num_value_heads"] * shape["linear_value_head_dim"]
    heads, kv, dh = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    visits = shape["num_experts_per_tok"] * len(shape["experts_held"]) / shape["num_experts"]
    ffn = (d * shape["num_experts"] + d + 3 * d * shape["shared_expert_intermediate_size"]
           + visits * 3 * d * shape["moe_intermediate_size"])
    delta = (d * (2 * keys + 2 * values) + d * 2 * shape["linear_num_value_heads"]
             + (2 * keys + values) * shape["linear_conv_kernel_dim"] + values * d)
    full = d * 2 * heads * dh + 2 * d * kv * dh + heads * dh * d
    total = length * d * vocabulary
    for kind in layer_kinds(shape):
        if kind == "delta":
            total += length * (delta + ffn) + delta_rule_macs(shape)
        else:
            total += length * (full + ffn) + 2 * (length * (length + 1) // 2) * heads * dh
    return int(total)


#: products of a step over a forward pass's: forward, recomputed forward, and
#: a backward pass of two products a forward product
PASSES = 4


def delta_rule_flops(shape, workers):
    """Operations a step of the chunked delta rule alone, two a
    multiply-accumulate: every DeltaNet layer's three passes, every worker."""
    layers = layer_kinds(shape).count("delta")
    return 2 * PASSES * delta_rule_macs(shape) * layers * workers


def delta_rule_bytes(shape, workers):
    """Bytes a step that the same passes have to move, float32 (the module
    docstring says what is counted)."""
    length = shape["sequence_length"]
    keys = shape["linear_num_key_heads"] * shape["linear_key_head_dim"]
    values = shape["linear_num_value_heads"] * shape["linear_value_head_dim"]
    gates = 2 * shape["linear_num_value_heads"]
    forward = length * (2 * keys + 2 * values + gates) * 4      # q, k, v, g, beta in; o out
    backward = 2 * forward - length * values * 4                # those and do in; five gradients out
    return (2 * forward + backward) * layer_kinds(shape).count("delta") * workers
