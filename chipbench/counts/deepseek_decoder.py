"""What one step of one chip's share of a ``deepseek_v3`` decoder REQUIRES,
from the cell's shapes alone: latent attention over ``heads_held`` of the
heads, a leading dense layer, expert layers of which this chip holds
``num_experts_held`` of ``n_routed_experts``, a head over the rows of the
vocabulary held.

Counted: the forward once and the backward (twice the forward's products).
The latent down-projections (q_a, kv_a: every head's), the up-projections
(q_b, kv_b) and the output rows of the heads held; the dense and the shared
feed-forward; the router over ALL experts; the routed experts at the
EXPECTED number of token-slots that reach the experts held (tokens x experts
per token x held / all: what uniform routing gives); the head. Attention of
the heads held at their one head size (q and k nope + rope, v as large)
over the pairs the causal mask keeps, S(S+1)/2 a sequence. Not counted: the
norms, rotary positions, the group selection, recompute, gathers, sorts, the
optimizer. The numbers never look at the implementation.
"""


def kept_pairs(seq):
    """Score pairs of one head and one sequence that the causal mask keeps."""
    return seq * (seq + 1) // 2


def slots_held(m, tokens):
    """Token-slots that reach the experts held, at even routing."""
    return tokens * m["num_experts_per_tok"] * m["num_experts_held"] \
        // m["n_routed_experts"]


def forward_parts(work):
    """{part: operations of ONE forward over the step's tokens}."""
    m = work["model"]
    D, h = m["hidden_size"], m["heads_held"]
    lq, lkv = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    B, S = work["batch"], work["seq_len"]
    tokens, layers = B * S, m["num_hidden_layers"]
    dense = m["first_k_dense_replace"]
    moe = layers - dense
    expert = 3 * 2 * D * m["moe_intermediate_size"]
    return {
        "latent": layers * tokens * 2 * (
            D * lq + lq * h * (nope + rope) + D * (lkv + rope)
            + lkv * h * (nope + dv)),
        "attn_out": layers * tokens * 2 * h * dv * D,
        "attn_pairs": layers * 2 * h * (nope + rope + dv) * kept_pairs(S)
        * B,
        "dense": dense * tokens * 3 * 2 * D * m["intermediate_size"],
        "shared": moe * tokens * m["n_shared_experts"] * expert,
        "router": moe * tokens * 2 * D * m["n_routed_experts"],
        "routed": moe * slots_held(m, tokens) * expert,
        "head": tokens * 2 * D * m["vocab_rows_held"],
    }


def required(work):
    """-> {"step_flops", "kernels": {"mx_flash_": {"flops", "bytes"},
    "mx_gmm_": {"flops", "bytes"}}}: the whole step, the attention that the
    flash kernels compute, and the routed experts' grouped products
    (forward three a layer, backward six)."""
    m = work["model"]
    D, h = m["hidden_size"], m["heads_held"]
    d = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    tokens = work["batch"] * work["seq_len"]
    parts = forward_parts(work)
    layers = m["num_hidden_layers"]
    moe = layers - m["first_k_dense_replace"]
    width = 2 if work["dtype"] in ("bfloat16", "float16") else 4
    Fm, slots = m["moe_intermediate_size"], slots_held(m, tokens)
    return {"step_flops": 3 * sum(parts.values()),
            "kernels": {
                # forward reads q k v, writes o; backward reads q k v o do,
                # writes dq dk dv: 6 activations of the h query heads and 6
                # of the h key-value heads, each of the one head size
                "mx_flash_": {
                    "flops": 3 * parts["attn_pairs"],
                    "bytes": 6 * 2 * h * d * tokens * width * layers},
                # nine grouped products a layer: a [slots, D] and a
                # [slots, F] activation between them, a group's matrices once
                "mx_gmm_": {
                    "flops": 3 * parts["routed"],
                    "bytes": 9 * (slots * (D + Fm)
                                  + m["num_experts_held"] * D * Fm)
                    * width * moe}}}
