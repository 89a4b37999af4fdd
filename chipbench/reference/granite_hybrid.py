"""Plain reference: one chip's share of a ``granitemoehybrid`` decoder (IBM
Granite 4.0-H: Mamba-2 mixers and a few attention layers in one period,
every layer followed by routed experts beside a shared one, a tied head)
trained by SGD with momentum, written from the published modeling code's
equations in straightforward ``jax.numpy``. It imports nothing of the
program under test; the rounding control, the optimizer and the small
helpers are ``dense_decoder``'s.

The equations, per layer on a sequence x [S, D] (r = residual_multiplier):

  h = RMSNorm(x; ln1);  x = x + r * Mixer(h)  or  x + r * Attention(h);
  h = RMSNorm(x; ln2);  x = x + r * (Shared(h) + Routed(h)).

  Mixer (H heads of size P, state N, one group): [z | xBC | dt] = h W_in;
    xBC = silu(b + sum over the four taps k of w_k xBC[t - 3 + k]) (nought
    before the sequence); [xs | B | C] = xBC; dt = softplus(dt + dt_bias);
    A = -exp(A_log); for head n, position by position from S_0 = 0:
    S_t = exp(dt_t A_n) S_{t-1} + dt_t xs_t (outer) B_t;  y_t = S_t C_t + D_n xs_t;
    out = RMSNorm(y * silu(z); norm over all H P channels) W_out.
  Attention: q = h Wq [S, H, d]; k = h Wk, v = h Wv [S, G, d]; NO positions;
    a_i = sum over j <= i of softmax_j(q_i k_j * attention_multiplier) v_j,
    head h reading key-value head h // (H/G); out = a Wo.
  Routed: l = h W_r [S, E]; the k largest; w = softmax over those k logits;
    sum over the chosen e HELD HERE of w_e FFN_e(h); FFN = (silu(h Wg) *
    (h Wu)) Wd. The published ``input_linear`` is [Wg | Wu] fused: held
    here as its two halves. Shared: the same form, computed for every token.
  x0 = embedding_multiplier * Emb[tokens]; loss = mean next-token
  cross-entropy of (RMSNorm(x; ln_f) Emb^T) / logits_scaling over the rows
  of the vocabulary held: the head reads the embedding's rows, whose
  gradient is the sum of both uses.

THE SHARE, the storage in ``state_dtype`` and float32 arithmetic at
``highest`` precision are as ``afmoe_decoder`` states them. The recurrence
is computed as written, one position at a time (an outer scan over blocks of
positions under ``jax.checkpoint``, an inner one over positions), so that it
shares no algebra with a chunked form; the convolution is the explicit sum
over its taps. Layer by layer (a reverse sweep over ``jax.vjp`` of one
layer), one sequence at a time; a layer's momentum waits on the host between
its uses, so that one chip holds the weights, the layer at work in float32
and the layers' inputs, and no more.

``variant``: "exact"; the control "fp8"; the planted faults "half_batch" and
"unchanged" as ``dense_decoder`` has them; and three of its own:
"state_dropped" (every ``chunk`` positions the state starts from nought: a
chunked scan that forgets to pass its states), "expert_missing" (the first
expert held is left out) and "leaf_unchanged" (ONE leaf's update is dropped:
the first mixer layer's ``ssm_out`` never moves, every other leaf of every
layer does; the one fault that the worst leaf's change sees and the median
leaf's does not).
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_decoder import (
    _f32, _mm, _moved, _sgd, _sq_diff, _zeros_like_f32)

Model = collections.namedtuple(
    "Model", "eps k first residual embedding logits attention heads "
             "head_size state chunk")
TOP = ("embed", "ln_f")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def conv(x, w, b):
    """x: [S, C]; w: [taps, C]; b: [C]. y[t] = b + sum_k w[k] x[t-(taps-1)+k]."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    return b + sum(w[k] * padded[k:k + s] for k in range(taps))


def recurrence(xs, dt, a_head, bm, cm, d_head, block, fresh_every=None):
    """The mixer's recurrence, one position at a time. xs: [S, H, P]; dt:
    [S, H]; a_head, d_head: [H]; bm, cm: [S, N]. ``fresh_every``: the
    planted fault, the state set to nought at every such position."""
    s, h, p = xs.shape
    block = min(block, s)
    assert s % block == 0, (s, block)
    keep = jnp.ones(s) if fresh_every is None \
        else (jnp.arange(s) % fresh_every != 0).astype(jnp.float32)

    def step(state, at):
        x, d, b, c, kept = at
        state = (kept * jnp.exp(d * a_head))[:, None, None] * state \
            + (d[:, None] * x)[:, :, None] * b[None, None, :]
        return state, jnp.sum(state * c[None, None, :], axis=-1)

    @jax.checkpoint
    def positions(state, ats):
        # unrolled by eight: the same steps in the same order, fewer turns
        # of the loop
        return lax.scan(step, state, ats, unroll=8)

    _, y = lax.scan(
        positions, jnp.zeros((h, p, bm.shape[1])),
        tuple(a.reshape((s // block, block) + a.shape[1:])
              for a in (xs, dt, bm, cm, keep)))
    return y.reshape(s, h, p) + d_head[:, None] * xs


def mixer(lp, h, m, block, variant):
    inner, n = m.heads * m.head_size, m.state
    zxbcdt = _mm("sd,de->se", h, lp["ssm_in"], variant)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * n],
                  zxbcdt[:, 2 * inner + 2 * n:])
    xbc = jax.nn.silu(conv(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"]))
    y = recurrence(
        xbc[:, :inner].reshape(-1, m.heads, m.head_size),
        jax.nn.softplus(dt + lp["ssm_dt_bias"]), -jnp.exp(lp["ssm_a_log"]),
        xbc[:, inner:inner + n], xbc[:, inner + n:], lp["ssm_d"], block,
        m.chunk if variant == "state_dropped" else None)
    y = _rms_norm(y.reshape(-1, inner) * jax.nn.silu(z), lp["ssm_norm"], m.eps)
    return _mm("se,ed->sd", y, lp["ssm_out"], variant)


def attention(lp, h, m, block, variant):
    """Causal softmax attention with grouped heads and no positions."""
    q = _mm("sd,dhk->shk", h, lp["wq"], variant)
    k = _mm("sd,dhk->shk", h, lp["wk"], variant)
    v = _mm("sd,dhk->shk", h, lp["wv"], variant)
    s, heads, d = q.shape
    g = k.shape[1]
    block = min(block, s)
    qb = q.reshape(s // block, block, g, heads // g, d)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = _mm("qgrd,kgd->grqk", qi, k, variant) * m.attention
        seen = cols[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
        return _mm("grqk,kgd->qgrd", p, v, variant)

    a = lax.map(one, (qb, jnp.arange(s // block) * block))
    return _mm("shk,hkd->sd", a.reshape(s, heads, d), lp["wo"], variant)


def _gated(h, w_gate, w_up, w_down, variant):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w_gate, variant))
               * _mm("sd,df->sf", h, w_up, variant), w_down, variant)


def experts(lp, h, m, variant):
    """Shared(h) + the held experts' part of the routed sum: experts
    ``m.first`` onward, as many as ``lp`` holds."""
    logits = _mm("sd,de->se", h, lp["moe_router"], variant)
    picked, chosen = lax.top_k(logits, m.k)
    weights = jax.nn.softmax(picked, axis=-1)

    @jax.checkpoint
    def one(acc, xs):
        e, w_gate, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return acc + w_e[:, None] * _gated(h, w_gate, w_up, w_down,
                                           variant), None

    stacks = (jnp.arange(lp["moe_w_gate"].shape[0]) + m.first,
              lp["moe_w_gate"], lp["moe_w_up"], lp["moe_w_down"])
    if variant == "expert_missing":
        stacks = tuple(a[1:] for a in stacks)
    out = _gated(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], variant)
    return lax.scan(one, out, stacks)[0]


def layer(lp, x, kind, m, block, variant):
    """One layer on one sequence. lp, x: float32; x is [S, D]; ``kind``:
    "mamba" or "attention"."""
    h = _rms_norm(x, lp["ln1"], m.eps)
    half = mixer(lp, h, m, block // 4, variant) if kind == "mamba" \
        else attention(lp, h, m, block, variant)
    x = x + m.residual * half
    h = _rms_norm(x, lp["ln2"], m.eps)
    return x + m.residual * experts(lp, h, m, variant)


def head_nll(ln_f, embed, x, targets, m, variant):
    """Summed token negative log-likelihood of a block of tokens, the head
    reading the embedding's rows."""
    logits = _mm("td,vd->tv", _rms_norm(x, ln_f, m.eps), embed, variant) \
        * m.logits
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


@functools.partial(jax.jit, static_argnames=("m", "variant"),
                   donate_argnums=(4,))
def _head_bwd(ln_f, embed, x, targets, acc, scale, m, variant):
    nll, vjp = jax.vjp(
        lambda a, b, c: head_nll(a, b, c, targets, m, variant),
        _f32(ln_f), _f32(embed), x)
    dl, dw, dx = vjp(scale)
    return nll, dx, (acc[0] + dl, acc[1] + dw)


def leaf_name(stack, leaf):
    """A leaf's name among the norms: "<leaf>" on an attention layer,
    "mamba.<leaf>" on a mixer layer, whichever run of its kind ("mamba",
    "mamba_1") holds it: a leaf's norm is over them all."""
    group = stack.split("_")[0]
    return leaf if group == "layers" else group + "." + leaf


def split_layers(w, runs):
    """The stacked weights as a list of layers in order. ``runs``: [(stack,
    kind, layers a period)] in the period's order; each stack is [periods,
    n, ...]. -> [(stack, kind, {leaf: this layer's array})]."""
    periods = next(iter(w[runs[0][0]].values())).shape[0]
    return [(stack, kind, {leaf: a[p, j] for leaf, a in w[stack].items()})
            for p in range(periods) for stack, kind, n in runs
            for j in range(n)]


def train(make_weights, batches, lr, steps, runs, m, variant="exact",
          block=512, head_block=2048, devices=None):
    """Follow ``steps`` steps of training from ``make_weights()``.

    make_weights: () -> {"embed": [V, D], "ln_f": [D], <stack>: {leaf:
        [periods, n, ...]} for each of ``runs``} in the type the state is
        held in. Called again at the end for the first weights.
    batches: list of (tokens [B, S], targets [B, S]) int arrays, one a step.
    runs: ``split_layers``'s; m: a ``Model``.

    -> {"loss": [one a step], "grad_norm": {leaf: norm of the FIRST step's
        gradient}, "delta_norm": {leaf: norm of the weights' change over all
        the steps}, "moved": {leaf: how many of its elements the steps
        moved}}, a stacked leaf's norm and count taken over all its layers.
    """
    home = (devices or [jax.devices()[0]])[0]
    up = lambda t: jax.device_put(t, home)   # noqa: E731
    w = make_weights()
    layers = [(stack, kind, up(lp))
              for stack, kind, lp in split_layers(w, runs)]
    top = {n: up(w[n]) for n in TOP}
    del w
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    m_layers = [None] * len(layers)     # on the host between uses
    m_top = zeros(top)
    names = sorted({leaf_name(s, n) for s, _, lp in layers for n in lp})
    losses, grad_sq = [], None
    if variant == "unchanged":
        lr = 0.0
    unmoved = (next(l for l, (_, kind, _) in enumerate(layers)
                    if kind == "mamba"), "ssm_out")

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
            xs[0][b] = jnp.take(top["embed"], up(tokens[b]), axis=0).astype(
                jnp.float32) * m.embedding
        for l, (_, kind, lp) in enumerate(layers):
            for b in range(n_seq):
                xs[l + 1][b] = _layer_fwd(lp, xs[l][b], kind, m, block,
                                          variant)
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
        # reverse sweep, one layer at a time, its update applied at once
        for l in reversed(range(len(layers))):
            stack, kind, lp = layers[l]
            mom = zeros(lp) if m_layers[l] is None else up(m_layers[l])
            acc = up(_zeros_like_f32(lp))
            for b in range(n_seq):
                dxs[b], acc = _layer_bwd(lp, xs[l][b], dxs[b], acc, kind, m,
                                         block, variant)
                xs[l][b] = None
            for n in list(lp):
                # the planted fault: the gradient still reaches the
                # momentum, this one leaf of this one layer never moves
                still = variant == "leaf_unchanged" and (l, n) == unmoved
                lp[n], mom[n], s = _sgd(lp[n], mom[n], acc[n],
                                        0.0 if still else lr)
                sq[leaf_name(stack, n)] += float(s)
            if step + 1 < steps:    # the last step's is read by no one
                m_layers[l] = jax.device_get(mom)
            del acc, mom
        # embedding: the sequences' input gradients scattered into its rows,
        # on top of what the head gave them
        for b in range(n_seq):
            g_embed = g_embed.at[up(tokens[b])].add(dxs[b] * m.embedding)
        top["embed"], m_top["embed"], s = _sgd(top["embed"], m_top["embed"],
                                               g_embed, lr)
        sq["embed"] = float(s)
        del g_embed, dxs, xs
        if grad_sq is None:
            grad_sq = sq
    del m_layers, m_top
    w0 = make_weights()
    delta = {n: float(_sq_diff(top[n], up(w0[n]))) for n in TOP}
    delta.update(dict.fromkeys(names, 0.0))
    moved = {n: int(_moved(top[n], up(w0[n]))) for n in TOP}
    moved.update(dict.fromkeys(names, 0))
    for (stack, _, lp), (_, _, lp0) in zip(layers, split_layers(w0, runs)):
        for n in lp:
            first = up(lp0[n])
            delta[leaf_name(stack, n)] += float(_sq_diff(lp[n], first))
            moved[leaf_name(stack, n)] += int(_moved(lp[n], first))
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: v ** 0.5 for n, v in delta.items()},
            "moved": moved}
