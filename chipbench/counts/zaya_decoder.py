"""What one step of one pipeline stage's share of a ``zaya`` decoder
REQUIRES, from the cell's shapes alone: compressed convolutional attention
(grouped-query, q and k mixed by two causal convolutions, half of v from the
position before), an expert layer after each of which this chip holds
``num_experts_held`` of ``num_experts`` with a router that is an MLP, a tied
head over the rows of the vocabulary held.

Counted: the forward once and the backward (twice the forward's products).
Products of q, k, the two halves of v, o; the second convolution's [d, d]
product a head and tap; the router's four matrices; the routed experts at
the EXPECTED number of token-slots that reach the experts held (tokens x
experts per token x held / all: every slot where all are held); the head.
Attention over the pairs the causal mask keeps, S(S+1)/2 a sequence. Not
counted: the first convolution's multiply-adds, the mean, the norms, rotary
positions, recompute, gathers, sorts, the optimizer. The numbers never look
at the implementation.
"""


def kept_pairs(seq):
    """Score pairs of one head and one sequence that the causal mask keeps."""
    return seq * (seq + 1) // 2


def slots_held(m, tokens):
    """Token-slots that reach the experts held, at even routing."""
    return tokens * m["num_experts_per_tok"] * m["num_experts_held"] \
        // m["num_experts"]


def forward_parts(work):
    """{part: operations of ONE forward over the step's tokens}."""
    m = work["model"]
    D, H, G, d = (m["hidden_size"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    R, E = m["router_hidden_size"], m["num_experts"]
    B, S = work["batch"], work["seq_len"]
    tokens, layers = B * S, len(m["layer_types"])
    return {
        "attn_proj": layers * tokens * 2 * D * d * (2 * H + 2 * G),
        "conv1": layers * tokens * m["cca_time1"] * (H + G) * 2 * d * d,
        "attn_pairs": layers * 2 * 2 * H * d * kept_pairs(S) * B,
        "router": layers * tokens * 2 * (D * R + 2 * R * R + R * E),
        "routed": layers * slots_held(m, tokens)
        * 3 * 2 * D * m["moe_intermediate_size"],
        "head": tokens * 2 * D * m["vocab_rows_held"],
    }


def required(work):
    """-> {"step_flops", "kernels": {"mx_flash_": {"flops", "bytes"},
    "mx_gmm_": {"flops", "bytes"}}}: the whole step, the attention that the
    flash kernels compute, and the routed experts' grouped products
    (forward three a layer, backward six)."""
    m = work["model"]
    D, H, G, d = (m["hidden_size"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    tokens = work["batch"] * work["seq_len"]
    parts = forward_parts(work)
    layers = len(m["layer_types"])
    width = 2 if work["dtype"] in ("bfloat16", "float16") else 4
    Fm, slots = m["moe_intermediate_size"], slots_held(m, tokens)
    return {"step_flops": 3 * sum(parts.values()),
            "kernels": {
                # forward reads q k v, writes o; backward reads q k v o do,
                # writes dq dk dv: 6 activations of H heads and 6 of G
                "mx_flash_": {
                    "flops": 3 * parts["attn_pairs"],
                    "bytes": 6 * (H + G) * d * tokens * width * layers},
                # nine grouped products a layer: a [slots, D] and a
                # [slots, F] activation between them, a group's matrices once
                "mx_gmm_": {
                    "flops": 3 * parts["routed"],
                    "bytes": 9 * (slots * (D + Fm)
                                  + m["num_experts_held"] * D * Fm)
                    * width * layers}}}
