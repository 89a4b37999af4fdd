"""Plain reference: one pipeline stage's share of a ``zaya`` decoder (Zyphra
ZAYA1: compressed convolutional attention, arXiv:2510.04476, and a top-1
expert layer whose router is an MLP with a state, arXiv:2511.17127) trained
by SGD with momentum, written from the published descriptions' equations in
straightforward ``jax.numpy``. It imports nothing of the program under test;
the rounding control, rotary positions, the optimizer and the small helpers
are ``dense_decoder``'s, grouped attention by blocks of queries and the
gated feed-forward ``afmoe_decoder``'s.

The equations, per layer on a sequence x [S, D] with the router's state
r_prev [S, R] of the layer before (H query heads, G key-value heads of d,
query head i reading key-value head i // (H/G); position -1 reads nought):

  h = RMSNorm(x; ln1); q0 = h Wq [S, H, d]; k0 = h Wk [S, G, d];
  c = [q0 | k0] head beside head (H + G heads);
  c1_t = a0 * c_{t-1} + a1 * c_t + b0             (one pair a channel)
  c2_t[j] = c1_{t-1}[j] M0_j + c1_t[j] M1_j + b1_j   ([d, d] a head and tap)
  [qc | kc] = c2; m_q[i] = (q0[i] + k0[i // (H/G)]) / 2; m_k[g] = the mean
  of m_q over the group's query heads; q = qc + m_q; k = kc + m_k;
  v_t = [h_t Wv_cur | h_{t-1} Wv_prev]  (the first G/2 key-value heads from
  this position, the rest from the one before);
  q = sqrt(d) q / |q|; k = tau_g sqrt(d) k / |k| over a head;
  rotary positions on the first ``rope_dims`` dims of each head (halves of
  those rotated, base theta), the rest pass;
  o = causal softmax(q k^T / sqrt d) v;
  x = (s1 * x + t1) + (u1 * (o Wo) + w1);   h = RMSNorm(x; ln2);
  r = h W_down + gamma * r_prev;
  p = softmax(gelu(gelu(RMSNorm(r; norm) W1) W2) W_out) over all E;
  e = the k largest of p + b (b a fixed buffer: it selects, no gradient);
  y = sum over the chosen e HELD HERE of p_e FFN_e(h), FFN = (silu(h Wg) *
  (h Wu)) Wd, the weight p_e NOT normalised over the k;
  x = (s2 * x + t2) + (u2 * y + w2); r goes on to the next layer.
  x0 = Emb[tokens]; loss = mean next-token cross-entropy of RMSNorm(x; ln_f)
  Emb^T over the rows of the vocabulary held: the head reads the embedding's
  rows, whose gradient is the sum of both uses.

THE SHARE (the experts held, the rows held), the storage in ``state_dtype``
and float32 arithmetic at ``highest`` precision are as ``afmoe_decoder``
states them; the first layer held starts from r_prev = 0. No kernel and no
plan: the convolutions tap by tap, attention by blocks of queries, the
experts over the tokens SORTED by their expert, in blocks of rows, each
block computing the experts that have a row in it and masking the rest
(every expert for every token would be sixteen times the work). Layer by
layer (a reverse sweep over ``jax.vjp`` of one layer, the state's gradient
handed back beside x's), one sequence at a time.

``variant``: "exact"; the control "fp8"; the planted faults "half_batch" and
"unchanged" as ``dense_decoder`` has them; and four of this model's own:
"mix_dropped" (no convolutions: q = q0 + m_q, k = k0 + m_k), "shift_dropped"
(``Wv_prev`` reads this position), "state_dropped" (r_prev nought in every
layer) and "weight_normalised" (the slot's weight 1: the chosen
probabilities normalised over the k).
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.afmoe_decoder import _attention, _gated, _rms_norm
from chipbench.reference.dense_decoder import (
    _f32, _mm, _moved, _rope, _sgd, _sq_diff, _zeros_like_f32)

Model = collections.namedtuple("Model", "eps k first theta rope_dims")
TOP = ("embed", "ln_f")
BUFFERS = ("moe_bias",)     # held beside the weights; never updated


def _before(a):
    """a[t] <- a[t - 1] along positions (axis 0), nought at t = 0."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def mix(lp, q0, k0, variant):
    """The two convolutions and the mean of the latents. q0: [S, H, d];
    k0: [S, G, d] -> (q, k) before their norms."""
    heads, groups = q0.shape[1], k0.shape[1]
    m_q = (q0.reshape(q0.shape[0], groups, heads // groups, -1)
           + k0[:, :, None]) / 2
    m_k = jnp.mean(m_q, axis=2)
    m_q = m_q.reshape(q0.shape)
    if variant == "mix_dropped":
        return q0 + m_q, k0 + m_k
    c = jnp.concatenate([q0, k0], axis=1)              # [S, H + G, d]
    w0 = lp["cca_conv0_w"].reshape((-1,) + c.shape[1:])
    assert w0.shape[0] == 2 and lp["cca_conv1_w"].shape[0] == 2, "two taps"
    c1 = w0[0] * _before(c) + w0[1] * c + lp["cca_conv0_b"].reshape(
        c.shape[1:])
    c2 = _mm("sjd,jde->sje", _before(c1), lp["cca_conv1_w"][0], variant) \
        + _mm("sjd,jde->sje", c1, lp["cca_conv1_w"][1], variant) \
        + lp["cca_conv1_b"]
    return c2[:, :heads] + m_q, c2[:, heads:] + m_k


def _unit(x):
    d = x.shape[-1]
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-20) \
        * (d ** 0.5)


def _partial_rope(x, m):
    return jnp.concatenate([_rope(x[..., :m.rope_dims], m.theta),
                            x[..., m.rope_dims:]], axis=-1)


def attention(lp, h, m, block, variant):
    q, k = mix(lp, _mm("sd,dhk->shk", h, lp["wq"], variant),
               _mm("sd,dhk->shk", h, lp["wk"], variant), variant)
    h_prev = h if variant == "shift_dropped" else _before(h)
    v = jnp.concatenate([_mm("sd,dhk->shk", h, lp["wv_cur"], variant),
                         _mm("sd,dhk->shk", h_prev, lp["wv_prev"], variant)],
                        axis=1)
    q = _partial_rope(_unit(q), m)
    k = _partial_rope(_unit(k) * lp["cca_temp"][:, None], m)
    o = _attention(q, k, v, None, block, variant)
    return _mm("shk,hkd->sd", o, lp["wo"], variant)


def route(lp, h, r_prev, m, variant):
    """-> (chosen [S, k], their weights [S, k], r [S, R])."""
    if variant == "state_dropped":
        r_prev = jnp.zeros_like(r_prev)
    r = _mm("sd,dr->sr", h, lp["moe_router_down"], variant) \
        + lp["moe_router_gamma"] * r_prev
    a = _rms_norm(r, lp["moe_router_norm"], m.eps)
    for name in ("moe_router_w1", "moe_router_w2"):
        a = jax.nn.gelu(_mm("sr,rq->sq", a, lp[name], variant),
                        approximate=False)
    p = jax.nn.softmax(_mm("sr,re->se", a, lp["moe_router_out"], variant),
                       axis=-1)
    _, chosen = lax.top_k(p + lax.stop_gradient(lp["moe_bias"]), m.k)
    weights = jnp.take_along_axis(p, chosen, axis=-1)
    if variant == "weight_normalised":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights, r


def experts(lp, h, chosen, weights, m, block, variant):
    """The held experts' part of sum_j weights[:, j] FFN_chosen[:, j](h)."""
    s, d = h.shape
    block = min(block, s)
    held = jnp.arange(lp["moe_w_gate"].shape[0]) + m.first
    stacks = (held, lp["moe_w_gate"], lp["moe_w_up"], lp["moe_w_down"])
    out = jnp.zeros_like(h)
    for j in range(m.k):
        # the tokens sorted by their j-th expert, in blocks of rows: a
        # block computes the experts that have a row in it, masks the rest
        order = jnp.argsort(chosen[:, j], stable=True)
        hs, es = h[order], chosen[order, j]

        @jax.checkpoint
        def one_block(args):
            hb, eb = args

            def one_expert(acc, xs):
                e, w_gate, w_up, w_down = xs
                mine = eb == e
                part = lax.cond(
                    jnp.any(mine),
                    lambda: _gated(hb, w_gate, w_up, w_down, variant),
                    lambda: jnp.zeros_like(hb))
                return acc + jnp.where(mine[:, None], part, 0.0), None

            return lax.scan(one_expert, jnp.zeros_like(hb), stacks)[0]

        ys = lax.map(one_block, (hs.reshape(s // block, block, d),
                                 es.reshape(s // block, block)))
        out = out + weights[:, j, None] * ys.reshape(s, d)[jnp.argsort(order)]
    return out


def _added(lp, half, x, a):
    return (lp["res%s_s" % half] * x + lp["res%s_t" % half]) \
        + (lp["res%s_u" % half] * a + lp["res%s_w" % half])


def layer(lp, x, r_prev, m, block, variant):
    """One layer on one sequence. lp, x, r_prev: float32; x is [S, D],
    r_prev [S, R]. -> (x, r)."""
    h = _rms_norm(x, lp["ln1"], m.eps)
    x = _added(lp, "1", x, attention(lp, h, m, block, variant))
    h = _rms_norm(x, lp["ln2"], m.eps)
    chosen, weights, r = route(lp, h, r_prev, m, variant)
    y = experts(lp, h, chosen, weights, m, block, variant)
    return _added(lp, "2", x, y), r


def head_nll(ln_f, embed, x, targets, m, variant):
    """Summed token negative log-likelihood of a block of tokens, the head
    reading the embedding's rows."""
    logits = _mm("td,vd->tv", _rms_norm(x, ln_f, m.eps), embed, variant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnames=("m", "block", "variant"))
def _layer_fwd(lp, x, r_prev, m, block, variant):
    return layer(_f32(lp), x, r_prev, m, block, variant)


@functools.partial(jax.jit, static_argnames=("m", "block", "variant"),
                   donate_argnums=(5,))
def _layer_bwd(lp, x, r_prev, dy, dr, acc, m, block, variant):
    """-> (dx, the gradient of r_prev, acc + this sequence's gradient of the
    layer's weights)."""
    _, vjp = jax.vjp(lambda p, a, r: layer(p, a, r, m, block, variant),
                     _f32(lp), x, r_prev)
    dlp, dx, dr_prev = vjp((dy, dr))
    return dx, dr_prev, jax.tree_util.tree_map(jnp.add, acc, dlp)


@functools.partial(jax.jit, static_argnames=("m", "variant"),
                   donate_argnums=(4,))
def _head_bwd(ln_f, embed, x, targets, acc, scale, m, variant):
    nll, vjp = jax.vjp(
        lambda a, b, c: head_nll(a, b, c, targets, m, variant),
        _f32(ln_f), _f32(embed), x)
    dl, dw, dx = vjp(scale)
    return nll, dx, (acc[0] + dl, acc[1] + dw)


def split_layers(w):
    """The stacked weights [layers, ...] as a list of layers in order."""
    n = next(iter(w["layers"].values())).shape[0]
    return [{leaf: a[l] for leaf, a in w["layers"].items()}
            for l in range(n)]


def train(make_weights, batches, lr, steps, m, variant="exact", block=512,
          head_block=2048, devices=None):
    """Follow ``steps`` steps of training from ``make_weights()``.

    make_weights: () -> {"embed": [V, D], "ln_f": [D], "layers": {leaf:
        [layers, ...]}} in the type the state is held in. Called again
        at the end for the first weights.
    batches: list of (tokens [B, S], targets [B, S]) int arrays, one a step.
    m: a ``Model``.

    -> {"loss": [one a step], "grad_norm": {leaf: norm of the FIRST step's
        gradient}, "delta_norm": {leaf: norm of the weights' change over all
        the steps}, "moved": {leaf: how many of its elements the steps
        moved}}, a stacked leaf's norm and count taken over all its layers.
    """
    home = (devices or [jax.devices()[0]])[0]
    up = lambda t: jax.device_put(t, home)   # noqa: E731
    w = make_weights()
    layers = [up(lp) for lp in split_layers(w)]
    top = {n: up(w[n]) for n in TOP}
    width = w["layers"]["moe_router_down"].shape[-1]
    del w
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    m_layers, m_top = [zeros(lp) for lp in layers], zeros(top)
    names = sorted(n for n in layers[0] if n not in BUFFERS)
    losses, grad_sq = [], None
    if variant == "unchanged":
        lr = 0.0

    for step in range(steps):
        tokens, targets = batches[step]
        if variant == "half_batch":
            keep = tokens.shape[0] // 2
            tokens, targets = tokens[:keep], targets[:keep]
        n_seq, seq = tokens.shape
        inv = jnp.float32(1.0 / (n_seq * seq))
        sq = dict.fromkeys(names + list(TOP), 0.0)
        # forward, keeping each layer's inputs (x and the router's state)
        # for every sequence
        xs = [[None] * n_seq for _ in range(len(layers) + 1)]
        rs = [[None] * n_seq for _ in range(len(layers) + 1)]
        for b in range(n_seq):
            xs[0][b] = jnp.take(top["embed"], up(tokens[b]), axis=0).astype(
                jnp.float32)
            rs[0][b] = jnp.zeros((seq, width), jnp.float32, device=home)
            for l, lp in enumerate(layers):
                xs[l + 1][b], rs[l + 1][b] = _layer_fwd(
                    lp, xs[l][b], rs[l][b], m, block, variant)
        # head: loss and its gradient, in blocks of tokens
        acc = (jnp.zeros(top["ln_f"].shape, jnp.float32, device=home),
               jnp.zeros(top["embed"].shape, jnp.float32, device=home))
        nll, dxs = 0.0, []
        hb = min(head_block, seq)
        for b in range(n_seq):
            parts = []
            for t in range(0, seq, hb):
                one, dx, acc = _head_bwd(
                    top["ln_f"], top["embed"], xs[len(layers)][b][t:t + hb],
                    up(targets[b, t:t + hb]), acc, inv, m, variant)
                nll = nll + one
                parts.append(dx)
            dxs.append(jnp.concatenate(parts, axis=0))
            xs[len(layers)][b] = None
        losses.append(float(nll * inv))
        top["ln_f"], m_top["ln_f"], s = _sgd(top["ln_f"], m_top["ln_f"],
                                             acc[0], lr)
        sq["ln_f"] = float(s)
        g_embed = acc[1]        # the head's use of the embedding's rows
        del acc
        # reverse sweep, one layer at a time, its update applied at once;
        # nothing reads the last layer's state, so its gradient is nought
        drs = [jnp.zeros((seq, width), jnp.float32, device=home)
               for _ in range(n_seq)]
        for l in reversed(range(len(layers))):
            lp = layers[l]
            acc = up(_zeros_like_f32(lp))
            for b in range(n_seq):
                dxs[b], drs[b], acc = _layer_bwd(
                    lp, xs[l][b], rs[l][b], dxs[b], drs[b], acc, m, block,
                    variant)
                xs[l][b] = rs[l][b] = None
            for n in names:
                lp[n], m_layers[l][n], s = _sgd(lp[n], m_layers[l][n],
                                                acc[n], lr)
                sq[n] += float(s)
            del acc
        # embedding: the sequences' input gradients scattered into its rows,
        # on top of what the head gave them
        for b in range(n_seq):
            g_embed = g_embed.at[up(tokens[b])].add(dxs[b])
        top["embed"], m_top["embed"], s = _sgd(top["embed"], m_top["embed"],
                                               g_embed, lr)
        sq["embed"] = float(s)
        del g_embed, dxs, drs, xs, rs
        if grad_sq is None:
            grad_sq = sq
    del m_layers, m_top
    w0 = make_weights()
    delta = {n: float(_sq_diff(top[n], up(w0[n]))) for n in TOP}
    delta.update(dict.fromkeys(names, 0.0))
    moved = {n: int(_moved(top[n], up(w0[n]))) for n in TOP}
    moved.update(dict.fromkeys(names, 0))
    for lp, lp0 in zip(layers, split_layers(w0)):
        for n in names:
            first = up(lp0[n])
            delta[n] += float(_sq_diff(lp[n], first))
            moved[n] += int(_moved(lp[n], first))
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: v ** 0.5 for n, v in delta.items()},
            "moved": moved}
