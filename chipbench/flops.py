"""Operations and bytes a step REQUIRES, from the cell's shapes alone.

Counted: the forward once and the backward (twice the forward's products).
Not counted: anything recomputed under remat, the embedding gather, the
optimizer's elementwise update. Causal attention counts the S(S+1)/2 pairs
a causal mask keeps. The numbers never look at the implementation, so they
read the same whatever kernel or remat policy a later PR brings.
"""


def dense_decoder(work):
    """-> {"step_flops", "attention_flops", "attention_bytes"} for one step
    of a dense decoder (MHA, gated FFN, untied head)."""
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
    return {"step_flops": 3 * fwd, "attention_flops": 3 * attn_fwd,
            "attention_bytes": 12 * tokens * D * width * L}


def resnet_v1_convs(layers=(3, 4, 6, 3), image=224, classes=1000):
    """[(macs, name)] of every convolution and the dense layer of a
    bottleneck ResNet v1 as the Gluon model zoo builds it: the stride of a
    stage's first block sits on its first 1x1 convolution and on the 1x1
    projection of the shortcut."""
    out = []
    size = image // 2                          # 7x7 stride 2
    out.append((size * size * 64 * 3 * 49, "conv0"))
    size //= 2                                 # 3x3 max pool stride 2
    cin = 64
    for stage, blocks in enumerate(layers):
        mid, cout = 64 * 2 ** stage, 256 * 2 ** stage
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            size //= stride
            px = size * size
            name = "stage%d.block%d" % (stage + 1, b)
            out.append((px * cin * mid, name + ".conv1x1a"))
            out.append((px * mid * mid * 9, name + ".conv3x3"))
            out.append((px * mid * cout, name + ".conv1x1b"))
            if b == 0:
                out.append((px * cin * cout, name + ".shortcut"))
            cin = cout
    out.append((cin * classes, "dense"))
    return out


def resnet_v1(work):
    m = work["model"]
    macs = sum(n for n, _ in resnet_v1_convs(
        tuple(m["layers"]), work["image"], m["classes"]))
    return {"step_flops": 3 * 2 * macs * work["batch"]}


KINDS = {"dense_decoder": dense_decoder, "resnet_v1": resnet_v1}


def required(work):
    return KINDS[work["kind"]](work)
