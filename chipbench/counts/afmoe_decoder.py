"""What one step of one chip's share of an ``afmoe`` decoder REQUIRES, from
the cell's shapes alone: grouped-query attention with a gate, sliding-window
and full layers, a leading dense layer, expert layers of which this chip
holds ``num_experts_held`` of ``num_experts``, a head over the rows of the
vocabulary held.

Counted: the forward once and the backward (twice the forward's products).
Products of q, k, v, gate, o; the dense and the shared feed-forward; the
router over ALL experts; the routed experts at the EXPECTED number of
token-slots that reach the experts held (tokens x experts per token x held /
all: what uniform routing gives); the head. Attention over the pairs the
mask keeps: S(S+1)/2 a sequence on a full layer, W(W+1)/2 + (S-W)W on a
sliding one. Not counted: recompute, gathers, sorts, norms, the optimizer.
The numbers never look at the implementation.
"""


def kept_pairs(seq, window=None):
    """Score pairs of one head and one sequence that the mask keeps."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_kinds(m):
    """[(attention kind, is it an expert layer)] of the layers held."""
    return [(kind.split("_")[0], i >= m["num_dense_layers"])
            for i, kind in enumerate(m["layer_types"])]


def required(work):
    """-> {"step_flops", "kernels": {"mx_flash_": {"flops", "bytes"},
    "mx_gmm_": {"flops", "bytes"}}}: the whole step, the attention that the
    flash kernels compute, and the routed experts' grouped products
    (forward three a layer, backward six; names starting ``mx_gmm_``)."""
    m = work["model"]
    D, H, G, dh = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    B, S = work["batch"], work["seq_len"]
    tokens = B * S
    attn_proj = 2 * D * dh * (3 * H + 2 * G)         # q, gate, o; k, v
    dense = 3 * 2 * D * m["intermediate_size"]
    expert = 3 * 2 * D * m["moe_intermediate_size"]
    slots = tokens * m["num_experts_per_tok"] * m["num_experts_held"] \
        // m["num_experts"]
    fwd = attn = expert_layers = 0
    for kind, experts in layer_kinds(m):
        pairs = kept_pairs(S, m["sliding_window"] if kind == "sliding"
                           else None)
        attn += 2 * 2 * H * dh * pairs * B            # QK^T and PV
        fwd += tokens * attn_proj
        if experts:
            fwd += tokens * (2 * D * m["num_experts"]
                             + m["num_shared_experts"] * expert)
            fwd += slots * expert
            expert_layers += 1
        else:
            fwd += tokens * dense
    fwd += tokens * 2 * D * m["vocab_rows_held"] + attn
    # forward reads q k v, writes o; backward reads q k v o do, writes
    # dq dk dv: 6 activations of H heads and 6 of G a layer
    width = 2 if work["dtype"] in ("bfloat16", "float16") else 4
    # the grouped products: each of the nine reads a [slots, D] and a
    # [slots, F] activation between them (one read, one written, or both
    # read for a weight's gradient) and a group's matrices once
    Fm = m["moe_intermediate_size"]
    return {"step_flops": 3 * fwd,
            "kernels": {
                "mx_flash_": {
                    "flops": 3 * attn,
                    "bytes": 6 * (H + G) * dh * tokens * width
                    * len(m["layer_types"])},
                "mx_gmm_": {
                    "flops": 3 * slots * expert * expert_layers,
                    "bytes": 9 * (slots * (D + Fm)
                                  + m["num_experts_held"] * D * Fm)
                    * width * expert_layers}}}
