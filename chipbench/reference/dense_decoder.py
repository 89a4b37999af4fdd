"""Plain reference: a dense decoder-only language model trained by SGD with
momentum, written from the published description in straightforward
``jax.numpy``: RMSNorm (eps 1e-6), rotary positions (theta 10000, halves
rotated), causal multi-head softmax attention, SwiGLU, an untied output head,
mean token cross-entropy. It imports nothing of the program under test.

Arithmetic is float32 with every matrix product at ``highest`` precision.
The one departure from "all float32" is the storage the configuration
states: weights and momentum are *held* in ``state_dtype`` (bfloat16 for the
Baichuan cells) and the optimizer's results are rounded to it, because a
weight that cannot move by less than its last bit is part of what the
configuration trains.

So that it fits beside nothing else on one 16 GB chip at the timed sizes it
works layer by layer (hand-written reverse sweep over ``jax.vjp`` of one
layer), one sequence at a time, attention in blocks of queries, the head in
blocks of tokens; a layer's update is applied as soon as its gradient is
whole, so no full gradient is ever held.

``variant``:
  "exact"       the reference.
  "fp8"         the control: both operands of every matrix product rounded to
                float8_e4m3 under a per-tensor scale (straight-through in the
                backward), the step below bfloat16 that would tempt a later PR.
  "half_batch"  a planted fault: the second half of the batch (of a batch of
                one sequence: of its tokens) is left out and the mean taken
                over the rest.
  "unchanged"   a planted fault: every step returns its state unchanged (the
                gradient still reaches the momentum, the weights never move).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                "w_down")


def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale; identity gradient."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + lax.stop_gradient(q - x)


def _mm(spec, a, b, variant):
    if variant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _rope(x, theta=10000.0):
    """x: [S, H, Dh] -> rotated by position along S."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, block, variant):
    """Causal softmax attention of one sequence. q, k, v: [S, H, Dh]."""
    s, h, dh = q.shape
    block = min(block, s)
    nb = s // block
    qb = q.reshape(nb, block, h, dh)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = _mm("qhd,khd->hqk", qi, k, variant) * (dh ** -0.5)
        rows = start + jnp.arange(block)
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", p, v, variant)

    out = lax.map(one, (qb, jnp.arange(nb) * block))
    return out.reshape(s, h, dh)


def layer(lp, x, block, variant):
    """One decoder layer on one sequence. lp, x: float32; x is [S, D]."""
    h = _rms_norm(x, lp["ln1"])
    q = _rope(_mm("sd,dhk->shk", h, lp["wq"], variant))
    k = _rope(_mm("sd,dhk->shk", h, lp["wk"], variant))
    v = _mm("sd,dhk->shk", h, lp["wv"], variant)
    o = _attention(q, k, v, block, variant)
    x = x + _mm("shk,hkd->sd", o, lp["wo"], variant)
    h = _rms_norm(x, lp["ln2"])
    g = jax.nn.silu(_mm("sd,df->sf", h, lp["w_gate"], variant))
    u = _mm("sd,df->sf", h, lp["w_up"], variant)
    return x + _mm("sf,fd->sd", g * u, lp["w_down"], variant)


def head_nll(ln_f, w_out, x, targets, variant):
    """Summed token negative log-likelihood of a block of tokens."""
    h = _rms_norm(x, ln_f)
    logits = _mm("td,dv->tv", h, w_out, variant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - tgt)


def _f32(tree):
    """The held weights as float32, outside any vjp: a gradient taken with
    respect to a bfloat16 array would be rounded to bfloat16."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("block", "variant"))
def _layer_fwd(lp, x, block, variant):
    return layer(_f32(lp), x, block, variant)


@functools.partial(jax.jit, static_argnames=("block", "variant"),
                   donate_argnums=(3,))
def _layer_bwd(lp, x, dy, acc, block, variant):
    """-> (dx, acc + this sequence's gradient of the layer's weights)."""
    _, vjp = jax.vjp(lambda p, a: layer(p, a, block, variant), _f32(lp), x)
    dlp, dx = vjp(dy)
    return dx, jax.tree_util.tree_map(jnp.add, acc, dlp)


@functools.partial(jax.jit, static_argnames=("variant",),
                   donate_argnums=(4,))
def _head_bwd(ln_f, w_out, x, targets, acc, scale, variant):
    """-> (summed nll, dx, acc + gradient of (ln_f, w_out)), for one block
    of tokens; ``scale`` is 1 / (tokens the mean is taken over)."""
    nll, vjp = jax.vjp(
        lambda a, b, c: head_nll(a, b, c, targets, variant),
        _f32(ln_f), _f32(w_out), x)
    dl, dw, dx = vjp(scale)
    return nll, dx, (acc[0] + dl, acc[1] + dw)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgd(p, m, g, lr):
    """SGD with momentum 0.9 in float32, results held in the state's type.
    -> (p', m', sum g^2)."""
    m32 = 0.9 * m.astype(jnp.float32) + g
    m_new = m32.astype(m.dtype)
    p_new = (p.astype(jnp.float32) - lr * m_new.astype(jnp.float32))
    return p_new.astype(p.dtype), m_new, jnp.sum(jnp.square(g))


@jax.jit
def _sq_diff(a, b):
    return jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))


@jax.jit
def _moved(a, b):
    return jnp.sum(a != b)


def _zeros_like_f32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), tree)


def train(make_weights, batches, lr, steps, variant="exact", block=512,
          head_block=2048, devices=None):
    """Follow ``steps`` steps of training from ``make_weights()``.

    make_weights: () -> {"embed": [V, D], "layers": {leaf: [L, ...]},
        "ln_f": [D], "w_out": [D, V]} in the type the state is held in.
        Called again at the end for the first weights, so nothing is kept
        twice while the steps run.
    batches: list of (tokens [B, S], targets [B, S]) int arrays, one a step.
    devices: where the layers live, spread evenly (a model that one chip
        cannot hold); the embedding and the head live on the first.

    -> {"loss": [one a step], "grad_norm": {leaf: norm of the FIRST step's
        gradient}, "delta_norm": {leaf: norm of the weights' change over all
        the steps}, "moved": {leaf: how many of its elements the steps
        moved}} with a stacked leaf's norm and count taken over all its
        layers.
    """
    devices = devices or [jax.devices()[0]]
    w = make_weights()
    n_layers = next(iter(w["layers"].values())).shape[0]
    where = [devices[l * len(devices) // n_layers] for l in range(n_layers)]
    home = devices[0]
    layers = [jax.device_put({n: w["layers"][n][l] for n in LAYER_LEAVES},
                             where[l]) for l in range(n_layers)]
    top = {n: jax.device_put(w[n], home) for n in ("embed", "ln_f", "w_out")}
    del w
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    m_layers, m_top = [zeros(lp) for lp in layers], zeros(top)
    losses, grad_sq = [], None
    if variant == "unchanged":
        lr = 0.0

    for step in range(steps):
        tokens, targets = batches[step]
        if variant == "half_batch":
            if tokens.shape[0] > 1:
                keep = tokens.shape[0] // 2
                tokens, targets = tokens[:keep], targets[:keep]
            else:   # one sequence: the second half of its tokens
                keep = tokens.shape[1] // 2
                tokens, targets = tokens[:, :keep], targets[:, :keep]
        n_seq, seq = tokens.shape
        inv = jnp.float32(1.0 / (n_seq * seq))
        sq = {n: 0.0 for n in LAYER_LEAVES + ("embed", "ln_f", "w_out")}
        # forward, keeping each layer's input for every sequence
        xs = [[None] * n_seq for _ in range(n_layers + 1)]
        for b in range(n_seq):
            x = jnp.take(top["embed"], jax.device_put(tokens[b], home),
                         axis=0).astype(jnp.float32)
            for l in range(n_layers):
                x = jax.device_put(x, where[l])
                xs[l][b] = x
                x = _layer_fwd(layers[l], x, block, variant)
            xs[n_layers][b] = jax.device_put(x, home)
        # head: loss and its gradient, in blocks of tokens
        acc = (jnp.zeros(top["ln_f"].shape, jnp.float32),
               jnp.zeros(top["w_out"].shape, jnp.float32))
        acc = jax.device_put(acc, home)
        nll, dxs = 0.0, []
        hb = min(head_block, seq)
        for b in range(n_seq):
            parts = []
            for t in range(0, seq, hb):
                tg = jax.device_put(targets[b, t:t + hb], home)
                one, dx, acc = _head_bwd(top["ln_f"], top["w_out"],
                                         xs[n_layers][b][t:t + hb], tg, acc,
                                         inv, variant)
                nll = nll + one
                parts.append(dx)
            dxs.append(jnp.concatenate(parts, axis=0))
            xs[n_layers][b] = None
        losses.append(float(nll * inv))
        for n, g in (("ln_f", acc[0]), ("w_out", acc[1])):
            top[n], m_top[n], s = _sgd(top[n], m_top[n], g, lr)
            sq[n] = float(s)
        del acc
        # reverse sweep, one layer at a time, its update applied at once
        for l in reversed(range(n_layers)):
            acc = jax.device_put(_zeros_like_f32(layers[l]), where[l])
            for b in range(n_seq):
                dy = jax.device_put(dxs[b], where[l])
                dxs[b], acc = _layer_bwd(layers[l], xs[l][b], dy, acc, block,
                                         variant)
                xs[l][b] = None
            for n in LAYER_LEAVES:
                layers[l][n], m_layers[l][n], s = _sgd(
                    layers[l][n], m_layers[l][n], acc[n], lr)
                sq[n] += float(s)
            del acc
        # embedding: scatter the sequences' input gradients into its rows
        g = jnp.zeros(top["embed"].shape, jnp.float32, device=home)
        for b in range(n_seq):
            g = g.at[jax.device_put(tokens[b], home)].add(
                jax.device_put(dxs[b], home))
        top["embed"], m_top["embed"], s = _sgd(top["embed"], m_top["embed"],
                                               g, lr)
        sq["embed"] = float(s)
        del g, dxs, xs
        if grad_sq is None:
            grad_sq = sq
    del m_layers, m_top
    w0 = make_weights()
    delta, moved = {}, {}
    for n in (*top, *LAYER_LEAVES):
        pairs = [(top[n], jax.device_put(w0[n], home))] if n in top else [
            (layers[l][n], jax.device_put(w0["layers"][n][l], where[l]))
            for l in range(n_layers)]
        delta[n] = sum(float(_sq_diff(a, b)) for a, b in pairs)
        moved[n] = sum(int(_moved(a, b)) for a, b in pairs)
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: v ** 0.5 for n, v in delta.items()},
            "moved": moved}
