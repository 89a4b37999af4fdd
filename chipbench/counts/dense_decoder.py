"""What one step of a dense decoder (MHA, gated FFN, untied head) REQUIRES,
from the cell's shapes alone.

Counted: the forward once and the backward (twice the forward's products).
Not counted: anything recomputed under remat, the embedding gather, the
optimizer's elementwise update. Causal attention counts the S(S+1)/2 pairs
a causal mask keeps. The numbers never look at the implementation, so they
read the same whatever kernel or remat policy a later PR brings.
"""


def required(work):
    """-> {"step_flops", "kernels": {"mx_flash_": {"flops", "bytes"}}}: the
    whole step, and the attention that the flash kernels (forward, dq,
    dk/dv: names starting ``mx_flash_``) compute."""
    m = work["model"]
    D, F, V, L = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    B, S = work["batch"], work["seq_len"]
    tokens = B * S
    proj = 2 * (4 * D * D + 3 * D * F)        # q k v o, gate up down
    pairs = S * (S + 1) // 2                  # per sequence
    attn_fwd = 2 * 2 * D * pairs * B * L      # QK^T and PV over kept pairs
    fwd = tokens * (L * proj + 2 * D * V) + attn_fwd
    # forward reads q k v, writes o; backward reads q k v o do, writes
    # dq dk dv: 12 activations of B*S*D elements a layer
    width = 2 if work["dtype"] in ("bfloat16", "float16") else 4
    return {"step_flops": 3 * fwd,
            "kernels": {"mx_flash_": {
                "flops": 3 * attn_fwd,
                "bytes": 12 * tokens * D * width * L}}}
