"""What one step of a bottleneck ResNet v1 REQUIRES, from the cell's shapes
alone: the forward's multiply-adds once and the backward's twice. Not
counted: BatchNorm, activations, pooling, the optimizer's update."""


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


def required(work):
    """-> {"step_flops"}: the kind has no kernel family of its own yet."""
    m = work["model"]
    macs = sum(n for n, _ in resnet_v1_convs(
        tuple(m["layers"]), work["image"], m["classes"]))
    return {"step_flops": 3 * 2 * macs * work["batch"]}
