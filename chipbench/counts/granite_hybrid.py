"""What one step of one chip's share of a ``granitemoehybrid`` decoder
REQUIRES, from the cell's shapes alone: Mamba-2 mixer layers and attention
layers (grouped-query, no positions), every layer followed by routed experts
of which this chip holds ``num_experts_held`` of ``num_local_experts`` beside
one shared expert, a tied head over the rows of the vocabulary held.

Counted: the forward once and the backward (twice the forward's products).
A mixer: ``in_proj`` and ``out_proj``; the conv's 2 x taps a channel; the
RECURRENCE's 5 P N a head and position (decay the state, add the outer
product, read it out: what the layer computes, not what a chunked form
spends on it). Attention: q, k, v, o and the score pairs the causal mask
keeps. Every layer: the router over ALL experts, the shared expert, the
routed experts at the EXPECTED number of token-slots that reach the experts
held (tokens x experts per token x held / all). The head over the rows held.
Not counted: recompute, gathers, sorts, norms, gates, the optimizer. The
numbers never look at the implementation.
"""


def kept_pairs(seq):
    """Score pairs of one head and one sequence that the causal mask keeps."""
    return seq * (seq + 1) // 2


def slots_held(m, tokens):
    """Token-slots that reach the experts held, at even routing."""
    return tokens * m["num_experts_per_tok"] * m["num_experts_held"] \
        // m["num_local_experts"]


def forward_parts(work):
    """{part: operations of ONE forward over the step's tokens}."""
    m = work["model"]
    D, H, G = (m["hidden_size"], m["num_attention_heads"],
               m["num_key_value_heads"])
    dh = D // H
    B, S = work["batch"], work["seq_len"]
    tokens = B * S
    Hm, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    inner = Hm * P
    conv = inner + 2 * m["mamba_n_groups"] * N
    mixers = sum(k == "mamba" for k in m["layer_types"])
    layers = len(m["layer_types"])
    expert = 3 * 2 * D * m["intermediate_size"]
    return {
        "mixer_proj": mixers * tokens * 2 * D * (2 * inner + conv + Hm),
        "mixer_conv": mixers * tokens * 2 * m["mamba_d_conv"] * conv,
        "mixer_recurrence": mixers * tokens * 5 * P * N * Hm,
        "attn_proj": (layers - mixers) * tokens * 2 * D * dh * (2 * H + 2 * G),
        "attn_pairs": (layers - mixers) * 2 * 2 * H * dh * kept_pairs(S) * B,
        "router": layers * tokens * 2 * D * m["num_local_experts"],
        "shared": layers * tokens * 3 * 2 * D * m["shared_intermediate_size"],
        "routed": layers * slots_held(m, tokens) * expert,
        "head": tokens * 2 * D * m["vocab_rows_held"],
    }


def required(work):
    """-> {"step_flops", "kernels": {"mx_flash_": {"flops", "bytes"},
    "mx_gmm_": {"flops", "bytes"}}}: the whole step, the attention that the
    flash kernels compute on the attention layers, and the routed experts'
    grouped products (forward three a layer, backward six)."""
    m = work["model"]
    D, H, G = (m["hidden_size"], m["num_attention_heads"],
               m["num_key_value_heads"])
    tokens = work["batch"] * work["seq_len"]
    parts = forward_parts(work)
    layers = len(m["layer_types"])
    attn_layers = layers - sum(k == "mamba" for k in m["layer_types"])
    width = 2 if work["dtype"] in ("bfloat16", "float16") else 4
    Fm, slots = m["intermediate_size"], slots_held(m, tokens)
    return {"step_flops": 3 * sum(parts.values()),
            "kernels": {
                # forward reads q k v, writes o; backward reads q k v o do,
                # writes dq dk dv: 6 activations of H heads and 6 of G
                "mx_flash_": {
                    "flops": 3 * parts["attn_pairs"],
                    "bytes": 6 * (H + G) * (D // H) * tokens * width
                    * attn_layers},
                # nine grouped products a layer: a [slots, D] and a
                # [slots, F] activation between them, a group's matrices once
                "mx_gmm_": {
                    "flops": 3 * parts["routed"],
                    "bytes": 9 * (slots * (D + Fm)
                                  + m["num_experts_held"] * D * Fm)
                    * width * layers}}}
