"""Plain reference: one chip's share of an ``afmoe`` decoder (Arcee Trinity:
``model_type`` "afmoe") trained by SGD with momentum, written from the
published modeling code's equations in straightforward ``jax.numpy``. It
imports nothing of the program under test; the rounding control, rotary
positions, the optimizer and the small helpers are ``dense_decoder``'s.

The equations, per layer of kind "sliding" or "full" on a sequence x [S, D]:

  h = RMSNorm(x; ln1); q = h Wq [S, H, d]; k = h Wk, v = h Wv [S, G, d];
  gate = h Wg [S, H, d]; q, k = RMSNorm over d (q_norm, k_norm);
  sliding only: rotary positions on q and k (theta 10000, halves rotated);
  a_i = sum_j softmax_j(q_i k_j / sqrt d) v_j, head h reading key-value head
  h // (H/G), over j <= i and, on sliding layers, j > i - W;
  x = x + RMSNorm((a * sigmoid(gate)) Wo; ln1_post);  h = RMSNorm(x; ln2);
  dense layer: m = (silu(h W_gate) * (h W_up)) W_down;
  expert layer: s = sigmoid(h W_r) [S, E]; chosen = top-k of s + b;
    w_e = s_e / (sum over the chosen of s + 1e-20) * route_scale;
    m = Shared(h) + sum over the chosen e HELD HERE of w_e FFN_e(h);
  x = x + RMSNorm(m; ln2_post).
  Embedding x = Emb[tokens] sqrt(D); loss = mean next-token cross-entropy of
  RMSNorm(x; ln_f) W_out over the rows of the vocabulary held.

Departures from the published model, each stated by the configuration:
- THE SHARE. The layer routes over all E experts and normalises over all k
  chosen, but sums only the terms of the experts held here (``first`` to
  ``first + held``) and the shared expert; that partial result goes on to the
  next layer. Logits, loss and token ids are over the rows held. Nothing
  stands in for the absent chips.
- b is a fixed buffer (no update rule, no gradient); no auxiliary loss.
- Weights and momentum are *held* in ``state_dtype`` (bfloat16) and the
  optimizer's results rounded to it, as the configuration trains them.
  Arithmetic is float32 with every matrix product at ``highest`` precision.

No kernel and no sort: an expert held is a mask over the tokens that chose
it, every held expert computed for every token. Layer by layer (a reverse
sweep over ``jax.vjp`` of one layer), one sequence at a time, attention in
blocks of queries, the experts one at a time, the head in blocks of tokens,
so that it fits beside nothing else on one chip.

``variant``: "exact"; the control "fp8" (operands of every product, the
router's too, rounded to float8_e4m3); the planted faults "half_batch" and
"unchanged" as ``dense_decoder`` has them; and two of its own: "no_window"
(sliding layers computed as full) and "expert_missing" (the first expert
held is left out).
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_decoder import (
    _f32, _mm, _moved, _rope, _sgd, _sq_diff, _zeros_like_f32)

Model = collections.namedtuple(
    "Model", "eps window k route_scale first theta")
TOP = ("embed", "ln_f", "w_out")
BUFFERS = ("moe_bias",)     # held beside the weights; never updated


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _attention(q, k, v, window, block, variant):
    """Causal softmax attention of one sequence with grouped heads.
    q: [S, H, d]; k, v: [S, G, d]; ``window`` None: every earlier key."""
    s, h, d = q.shape
    g = k.shape[1]
    block = min(block, s)
    nb = s // block
    qb = q.reshape(nb, block, g, h // g, d)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = _mm("qgrd,kgd->grqk", qi, k, variant) * (d ** -0.5)
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (cols[None, :] > rows[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
        return _mm("grqk,kgd->qgrd", p, v, variant)

    out = lax.map(one, (qb, jnp.arange(nb) * block))
    return out.reshape(s, h, d)


def _gated(h, w_gate, w_up, w_down, variant):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w_gate, variant))
               * _mm("sd,df->sf", h, w_up, variant), w_down, variant)


def _experts(lp, h, m, variant):
    """Shared(h) + the held experts' part of the routed sum."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, lp["moe_router"], variant))
    _, chosen = lax.top_k(scores + lax.stop_gradient(lp["moe_bias"]), m.k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * m.route_scale

    @jax.checkpoint
    def one(acc, xs):
        e, w_gate, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return acc + w_e[:, None] * _gated(h, w_gate, w_up, w_down,
                                           variant), None

    held = jnp.arange(lp["moe_w_gate"].shape[0]) + m.first
    stacks = (held, lp["moe_w_gate"], lp["moe_w_up"], lp["moe_w_down"])
    if variant == "expert_missing":
        stacks = tuple(a[1:] for a in stacks)
    out = _gated(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], variant)
    return lax.scan(one, out, stacks)[0]


def layer(lp, x, kind, m, block, variant):
    """One layer on one sequence. lp, x: float32; x is [S, D]."""
    h = _rms_norm(x, lp["ln1"], m.eps)
    q = _rms_norm(_mm("sd,dhk->shk", h, lp["wq"], variant), lp["q_norm"],
                  m.eps)
    k = _rms_norm(_mm("sd,dhk->shk", h, lp["wk"], variant), lp["k_norm"],
                  m.eps)
    v = _mm("sd,dhk->shk", h, lp["wv"], variant)
    gate = _mm("sd,dhk->shk", h, lp["w_attn_gate"], variant)
    window = None
    if kind == "sliding":
        q, k = _rope(q, m.theta), _rope(k, m.theta)
        window = None if variant == "no_window" else m.window
    a = _attention(q, k, v, window, block, variant) * jax.nn.sigmoid(gate)
    x = x + _rms_norm(_mm("shk,hkd->sd", a, lp["wo"], variant),
                      lp["ln1_post"], m.eps)
    h = _rms_norm(x, lp["ln2"], m.eps)
    if "moe_router" in lp:
        out = _experts(lp, h, m, variant)
    else:
        out = _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"], variant)
    return x + _rms_norm(out, lp["ln2_post"], m.eps)


def head_nll(ln_f, w_out, x, targets, eps, variant):
    """Summed token negative log-likelihood of a block of tokens."""
    logits = _mm("td,dv->tv", _rms_norm(x, ln_f, eps), w_out, variant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnames=("kind", "m", "block", "variant"))
def _layer_fwd(lp, x, kind, m, block, variant):
    return layer(_f32(lp), x, kind, m, block, variant)


@functools.partial(jax.jit, static_argnames=("kind", "m", "block", "variant"),
                   donate_argnums=(3,))
def _layer_bwd(lp, x, dy, acc, kind, m, block, variant):
    """-> (dx, acc + this sequence's gradient of the layer's weights)."""
    _, vjp = jax.vjp(lambda p, a: layer(p, a, kind, m, block, variant),
                     _f32(lp), x)
    dlp, dx = vjp(dy)
    return dx, jax.tree_util.tree_map(jnp.add, acc, dlp)


@functools.partial(jax.jit, static_argnames=("eps", "variant"),
                   donate_argnums=(4,))
def _head_bwd(ln_f, w_out, x, targets, acc, scale, eps, variant):
    nll, vjp = jax.vjp(
        lambda a, b, c: head_nll(a, b, c, targets, eps, variant),
        _f32(ln_f), _f32(w_out), x)
    dl, dw, dx = vjp(scale)
    return nll, dx, (acc[0] + dl, acc[1] + dw)


def leaf_name(group, leaf):
    """A leaf's name among the norms: the leading dense layers' leaves are
    "dense.<leaf>", the expert layers' "<leaf>"."""
    return leaf if group == "layers" else group + "." + leaf


def split_layers(w, kinds):
    """The stacked weights as a list of layers in order: the ``dense`` stack
    [n, ...], then ``layers`` [periods, P, ...] row by row.
    -> [(group, kind, {leaf: this layer's array})]."""
    dense = w.get("dense") or {}
    out = [("dense", {n: a[i] for n, a in dense.items()})
           for i in range(next(iter(dense.values())).shape[0] if dense else 0)]
    periods, width = next(iter(w["layers"].values())).shape[:2]
    for p in range(periods):
        for j in range(width):
            out.append(("layers", {n: a[p, j]
                                   for n, a in w["layers"].items()}))
    assert len(out) == len(kinds), (len(out), kinds)
    return [(g, kind, lp) for (g, lp), kind in zip(out, kinds)]


def train(make_weights, batches, lr, steps, kinds, m, variant="exact",
          block=512, head_block=2048, devices=None):
    """Follow ``steps`` steps of training from ``make_weights()``.

    make_weights: () -> {"embed": [V, D], "dense": {leaf: [n, ...]},
        "layers": {leaf: [periods, P, ...]}, "ln_f": [D], "w_out": [D, V]}
        in the type the state is held in. Called again at the end for the
        first weights, so nothing is kept twice while the steps run.
    batches: list of (tokens [B, S], targets [B, S]) int arrays, one a step.
    kinds: "sliding" or "full" for each layer in order; m: a ``Model``.

    -> {"loss": [one a step], "grad_norm": {leaf: norm of the FIRST step's
        gradient}, "delta_norm": {leaf: norm of the weights' change over all
        the steps}}, a stacked leaf's norm taken over all its layers.
    """
    home = (devices or [jax.devices()[0]])[0]
    w = make_weights()
    layers = [(g, kind, jax.device_put(lp, home))
              for g, kind, lp in split_layers(w, kinds)]
    top = {n: jax.device_put(w[n], home) for n in TOP}
    scale = float(top["embed"].shape[1]) ** 0.5
    del w
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    m_layers, m_top = [zeros(lp) for _, _, lp in layers], zeros(top)
    names = sorted({leaf_name(g, n) for g, _, lp in layers for n in lp
                    if n not in BUFFERS})
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
        sq = dict.fromkeys(names + list(TOP), 0.0)
        # forward, keeping each layer's input for every sequence
        xs = [[None] * n_seq for _ in range(len(layers) + 1)]
        for b in range(n_seq):
            x = jnp.take(top["embed"], jax.device_put(tokens[b], home),
                         axis=0).astype(jnp.float32) * scale
            for l, (_, kind, lp) in enumerate(layers):
                xs[l][b] = x
                x = _layer_fwd(lp, x, kind, m, block, variant)
            xs[len(layers)][b] = x
        # head: loss and its gradient, in blocks of tokens
        acc = (jnp.zeros(top["ln_f"].shape, jnp.float32, device=home),
               jnp.zeros(top["w_out"].shape, jnp.float32, device=home))
        nll, dxs = 0.0, []
        hb = min(head_block, seq)
        for b in range(n_seq):
            parts = []
            for t in range(0, seq, hb):
                tg = jax.device_put(targets[b, t:t + hb], home)
                one, dx, acc = _head_bwd(top["ln_f"], top["w_out"],
                                         xs[len(layers)][b][t:t + hb], tg,
                                         acc, inv, m.eps, variant)
                nll = nll + one
                parts.append(dx)
            dxs.append(jnp.concatenate(parts, axis=0))
            xs[len(layers)][b] = None
        losses.append(float(nll * inv))
        for n, g in (("ln_f", acc[0]), ("w_out", acc[1])):
            top[n], m_top[n], s = _sgd(top[n], m_top[n], g, lr)
            sq[n] = float(s)
        del acc
        # reverse sweep, one layer at a time, its update applied at once
        for l in reversed(range(len(layers))):
            group, kind, lp = layers[l]
            acc = jax.device_put(_zeros_like_f32(lp), home)
            for b in range(n_seq):
                dxs[b], acc = _layer_bwd(lp, xs[l][b], dxs[b], acc, kind, m,
                                         block, variant)
                xs[l][b] = None
            for n in lp:
                if n in BUFFERS:
                    continue
                lp[n], m_layers[l][n], s = _sgd(lp[n], m_layers[l][n],
                                                acc[n], lr)
                sq[leaf_name(group, n)] += float(s)
            del acc
        # embedding: scatter the sequences' input gradients into its rows
        g = jnp.zeros(top["embed"].shape, jnp.float32, device=home)
        for b in range(n_seq):
            g = g.at[jax.device_put(tokens[b], home)].add(dxs[b] * scale)
        top["embed"], m_top["embed"], s = _sgd(top["embed"], m_top["embed"],
                                               g, lr)
        sq["embed"] = float(s)
        del g, dxs, xs
        if grad_sq is None:
            grad_sq = sq
    del m_layers, m_top
    w0 = make_weights()
    delta = {n: float(_sq_diff(top[n], jax.device_put(w0[n], home)))
             for n in TOP}
    delta.update(dict.fromkeys(names, 0.0))
    moved = {n: int(_moved(top[n], jax.device_put(w0[n], home)))
             for n in TOP}
    moved.update(dict.fromkeys(names, 0))
    for (group, _, lp), (_, _, lp0) in zip(layers, split_layers(w0, kinds)):
        for n in lp:
            if n not in BUFFERS:
                first = jax.device_put(lp0[n], home)
                delta[leaf_name(group, n)] += float(_sq_diff(lp[n], first))
                moved[leaf_name(group, n)] += int(_moved(lp[n], first))
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: v ** 0.5 for n, v in delta.items()},
            "moved": moved}
