"""Plain reference: bottleneck ResNet v1 (He et al. 2015, arXiv:1512.03385,
table 1) as the Gluon model zoo lays it out (the stride of a stage's first
block on its first 1x1 convolution), trained by SGD with momentum on the
mean softmax cross-entropy. Straightforward ``jax.numpy``; it imports
nothing of the program under test.

float32, every convolution and product at ``highest`` precision, BatchNorm
on the batch's own statistics (biased variance, eps 1e-5). As in
dense_decoder.py, weights and momentum are HELD in the type the
configuration states and the optimizer's results are rounded to it.
BatchNorm ties the rows of a batch together, so the blocks here are the
network's own: a hand-written reverse sweep over ``jax.vjp`` of one
bottleneck at a time, each update applied as soon as its gradient is whole.

``variant``: "exact", "fp8" (the control: both operands of every convolution
and of the dense layer rounded to float8_e4m3 under a per-tensor scale) or
"half_batch" (a planted fault: the second half of the batch left out) or
"unchanged" (a planted fault: the weights never move).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_decoder import _f32, _fp8, _moved, _sq_diff

HIGHEST = lax.Precision.HIGHEST
EPS = 1e-5


def segments(layers=(3, 4, 6, 3), classes=1000):
    """The network as a list of segments, each a list of (leaf, shape) in
    the order the model zoo creates its trainable parameters."""
    def bn(n, c):
        return [(n + ".gamma", (c,)), (n + ".beta", (c,))]
    segs = [("stem", None, [("conv0.weight", (64, 3, 7, 7))] + bn("bn0", 64))]
    cin = 64
    for stage, blocks in enumerate(layers):
        mid, cout = 64 * 2 ** stage, 256 * 2 ** stage
        for b in range(blocks):
            n = "stage%d.block%d" % (stage + 1, b)
            stride = 2 if (b == 0 and stage > 0) else 1
            leaves = ([(n + ".conv1.weight", (mid, cin, 1, 1))]
                      + bn(n + ".bn1", mid)
                      + [(n + ".conv2.weight", (mid, mid, 3, 3))]
                      + bn(n + ".bn2", mid)
                      + [(n + ".conv3.weight", (cout, mid, 1, 1))]
                      + bn(n + ".bn3", cout))
            if b == 0:
                leaves += ([(n + ".down.weight", (cout, cin, 1, 1))]
                           + bn(n + ".down_bn", cout))
            segs.append((n, stride, leaves))
            cin = cout
    segs.append(("head", None, [("dense.weight", (classes, cin)),
                                ("dense.bias", (classes,))]))
    return segs


def _conv(x, w, stride, pad, variant):
    if variant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return ((x - mean) * lax.rsqrt(var + EPS) * gamma[None, :, None, None]
            + beta[None, :, None, None])


def _apply(name, stride, p, x, variant):
    """One segment. p: {leaf's name within the segment: float32}; the head
    returns logits. ``name`` is "stem", "head" or "block": blocks of one
    shape share one compiled program."""
    if name == "stem":
        y = jax.nn.relu(_bn(_conv(x, p["conv0.weight"], 2, 3, variant),
                            p["bn0.gamma"], p["bn0.beta"]))
        return lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 [(0, 0), (0, 0), (1, 1), (1, 1)])
    if name == "head":
        feat = jnp.mean(x, axis=(2, 3))
        w = p["dense.weight"]
        if variant == "fp8":
            feat, w = _fp8(feat), _fp8(w)
        return jnp.einsum("nc,kc->nk", feat, w, precision=HIGHEST) \
            + p["dense.bias"]
    g = p.__getitem__
    y = jax.nn.relu(_bn(_conv(x, g("conv1.weight"), stride, 0, variant),
                        g("bn1.gamma"), g("bn1.beta")))
    y = jax.nn.relu(_bn(_conv(y, g("conv2.weight"), 1, 1, variant),
                        g("bn2.gamma"), g("bn2.beta")))
    y = _bn(_conv(y, g("conv3.weight"), 1, 0, variant),
            g("bn3.gamma"), g("bn3.beta"))
    if "down.weight" in p:
        x = _bn(_conv(x, g("down.weight"), stride, 0, variant),
                g("down_bn.gamma"), g("down_bn.beta"))
    return jax.nn.relu(y + x)


@functools.partial(jax.jit, static_argnames=("name", "stride", "variant"))
def _fwd(name, stride, p, x, variant):
    return _apply(name, stride, _f32(p), x, variant)


@functools.partial(jax.jit, static_argnames=("name", "stride", "variant"))
def _bwd(name, stride, p, x, dy, variant):
    _, vjp = jax.vjp(lambda q, a: _apply(name, stride, q, a, variant),
                     _f32(p), x)
    return vjp(dy)      # (dp, dx)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_bwd(p, x, labels, variant):
    """-> (mean loss, dp, dx) of the head with the softmax cross-entropy."""
    def f(q, a):
        logits = _apply("head", None, q, a, variant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tgt)
    loss, (dp, dx) = jax.value_and_grad(f, argnums=(0, 1))(_f32(p), x)
    return loss, dp, dx


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgd(p, m, g, lr):
    """mom' = 0.9 mom - lr g; w' = w + mom' (the form MXNet's SGD has), in
    float32, results held in the state's type. -> (w', mom', sum g^2)."""
    m_new = (0.9 * m.astype(jnp.float32) - lr * g).astype(m.dtype)
    p_new = (p.astype(jnp.float32) + m_new.astype(jnp.float32))
    return p_new.astype(p.dtype), m_new, jnp.sum(jnp.square(g))


def train(make_weights, batches, lr, steps, variant="exact",
          layers=(3, 4, 6, 3), classes=1000):
    """make_weights: () -> {leaf: array} in the type the state is held in.
    batches: list of (images [B, 3, H, W], labels [B] int).
    -> {"loss", "grad_norm", "delta_norm", "moved"} as dense_decoder.train
    gives."""
    segs = segments(layers, classes)

    def local(seg):
        """(kind, stride, {name within the segment: full leaf name})."""
        name, stride, leaves = seg
        if stride is None:
            return name, None, {n: n for n, _ in leaves}
        return "block", stride, {n[len(name) + 1:]: n for n, _ in leaves}

    w = make_weights()
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, grad_sq = [], None
    if variant == "unchanged":
        lr = 0.0
    for step in range(steps):
        x, labels = batches[step]
        if variant == "half_batch":
            x, labels = x[:len(x) // 2], labels[:len(labels) // 2]
        x = jnp.asarray(x).astype(jnp.float32)
        labels = jnp.asarray(labels).astype(jnp.int32)
        sq, xs = {}, []
        def update(names, dp):
            for short, n in names.items():
                w[n], m[n], s = _sgd(w[n], m[n], dp[short], lr)
                sq[n] = float(s)

        for seg in segs[:-1]:
            kind, stride, names = local(seg)
            xs.append(x)
            x = _fwd(kind, stride, {k: w[n] for k, n in names.items()}, x,
                     variant)
        _, _, names = local(segs[-1])
        loss, dp, dx = _head_bwd({k: w[n] for k, n in names.items()}, x,
                                 labels, variant)
        losses.append(float(loss))
        for seg, x_in in zip(reversed(segs[:-1]), reversed(xs)):
            update(names, dp)   # the segment above, now that dx has left it
            kind, stride, names = local(seg)
            dp, dx = _bwd(kind, stride, {k: w[n] for k, n in names.items()},
                          x_in, dx, variant)
        update(names, dp)
        del xs
        if grad_sq is None:
            grad_sq = sq
    del m
    w0 = make_weights()
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: float(_sq_diff(w[n], w0[n])) ** 0.5
                           for n in w},
            "moved": {n: int(_moved(w[n], w0[n])) for n in w}}
