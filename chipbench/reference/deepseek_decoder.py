"""Plain reference: one chip's share of a ``deepseek_v3`` decoder (multi-head
latent attention, YaRN positions, group-limited sigmoid routing, a shared
expert) trained by SGD with momentum, written from the published modeling
code's equations in straightforward ``jax.numpy``. It imports nothing of the
program under test; the rounding control, the optimizer and the small
helpers are ``dense_decoder``'s, the head, the gated feed-forward and the
layer bookkeeping ``afmoe_decoder``'s.

The equations, per layer on a sequence x [S, D], h heads held of d = nope +
rope, v's head of d too:

  h = RMSNorm(x; ln1);
  q = RMSNorm(h W_qa; q_a_norm) W_qb [S, h, d] = [q_nope | q_pe];
  [c | k_pe] = h W_kva [S, kv_latent + rope];
  [k_nope | v] = RMSNorm(c; kv_a_norm) W_kvb [S, h, nope + d];
  q_pe and k_pe (one vector a position, every head's) rotated with YaRN's
  frequencies (dim j with dim j + rope/2), cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim);
  q = [q_nope | q_pe]; k = [k_nope | k_pe];
  a = sum over the heads held of softmax_j(q_i k_j * d^-0.5 *
  mscale(factor, mscale_all_dim)^2, j <= i) v_j W_o[head];
  x = x + a;  h = RMSNorm(x; ln2);
  dense layer: m = (silu(h W_gate) * (h W_up)) W_down;
  expert layer: s = sigmoid(h W_r) [S, E]; b = s + bias; the E experts in
    n groups, each group's score the sum of its two largest b, the ``kept``
    best groups kept and every other expert's b -inf; chosen = top-k of b;
    w_e = s_e / (sum over the chosen of s + 1e-20) * route_scale;
    m = Shared(h) + sum over the chosen e HELD HERE of w_e FFN_e(h);
  x = x + m.
  Embedding x = Emb[tokens]; loss = mean next-token cross-entropy of
  RMSNorm(x; ln_f) W_out over the rows of the vocabulary held.
  mscale(f, m) = 0.1 m ln f + 1.

Departures from the published model, each stated by the configuration:
- THE SHARE. The heads held are the only heads computed past the latents;
  their part of the output goes on as it is. The layer routes over all E
  experts and normalises over all k chosen, but sums only the terms of the
  experts held here and the shared expert. Logits, loss and token ids are
  over the rows held. Nothing stands in for the absent chips.
- An expert outside the kept groups is never chosen (-inf); the published
  code fills 0.0, which differs only where a kept expert's b is negative.
- The rope dims are paired (j, j + rope/2), not interleaved (2i, 2i+1): with
  seeded weights a fixed relabelling of W_qb's and W_kva's rope columns.
- b is a fixed buffer (no update rule, no gradient); no auxiliary loss.
- Weights and momentum are *held* in ``state_dtype`` (bfloat16), the
  optimizer's results rounded to it. Arithmetic is float32 with every
  matrix product at ``highest`` precision.

No kernel and no sort: an expert held is a mask over the tokens that chose
it, every held expert computed for every token. Layer by layer (a reverse
sweep over ``jax.vjp`` of one layer), one sequence at a time, attention in
blocks of queries, the head in blocks of tokens, the layers' momentum on the
host between its updates.

``variant``: "exact"; the control "fp8" (operands of every product, the
router's too, rounded to float8_e4m3); the planted faults "half_batch" and
"unchanged" as ``dense_decoder`` has them, "expert_missing" as
``afmoe_decoder``; and two of its own: "groups_ignored" (the top k over
every expert, no group limit) and "yarn_dropped" (plain rotary frequencies
and scale).
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.afmoe_decoder import (
    _gated, _head_bwd, _rms_norm, leaf_name)
from chipbench.reference.dense_decoder import (
    _f32, _mm, _moved, _sgd, _sq_diff, _zeros_like_f32)

# yarn: (factor, original_max_position_embeddings, beta_fast, beta_slow,
# mscale, mscale_all_dim)
Model = collections.namedtuple(
    "Model", "eps k route_scale first groups kept nope theta yarn")
TOP = ("embed", "ln_f", "w_out")
BUFFERS = ("moe_bias",)     # held beside the weights; never updated


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(rope, theta, scaling):
    """(inverse frequencies [rope / 2], factor on cos and sin, factor on the
    scores) of the published ``rope_scaling`` of type yarn."""
    factor, orig, fast, slow, m, m_all = scaling
    half = rope // 2

    def correction(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), rope - 1)
    j = jnp.arange(half, dtype=jnp.float32)
    ramp = jnp.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    extra = theta ** (-2.0 * j / rope)
    freqs = extra * (1 - ramp) + extra / factor * ramp
    return freqs, mscale(factor, m) / mscale(factor, m_all), \
        mscale(factor, m_all) ** 2


def _rope(x, freqs, mult):
    """x: [S, heads, rope], dim j turned with dim j + rope/2 by position."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = (jnp.cos(ang) * mult)[:, None], (jnp.sin(ang) * mult)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale, block, variant):
    """Causal softmax attention of one sequence. q, k, v: [S, heads, d]."""
    s = q.shape[0]
    block = min(block, s)
    nb = s // block
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = _mm("qhd,khd->hqk", qi, k, variant) * scale
        rows = start + jnp.arange(block)
        seen = cols[None, None, :] <= rows[None, :, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p, v, variant)

    out = lax.map(one, (q.reshape(nb, block, *q.shape[1:]),
                        jnp.arange(nb) * block))
    return out.reshape(s, *out.shape[2:])


def attention(lp, h, m, block, variant):
    """The attention of the heads held, summed over them: [S, D]."""
    nope = m.nope
    lkv = lp["kv_a_norm"].shape[0]
    rope = lp["wkv_a"].shape[1] - lkv
    q = _mm("sl,lhk->shk", _rms_norm(_mm("sd,dl->sl", h, lp["wq_a"], variant),
                                     lp["q_a_norm"], m.eps),
            lp["wq_b"], variant)
    ckv = _mm("sd,dl->sl", h, lp["wkv_a"], variant)
    kv = _mm("sl,lhk->shk", _rms_norm(ckv[:, :lkv], lp["kv_a_norm"], m.eps),
             lp["wkv_b"], variant)
    d = q.shape[-1]
    if variant == "yarn_dropped":
        freqs = m.theta ** (-2.0 * jnp.arange(rope // 2,
                                              dtype=jnp.float32) / rope)
        mult, factor = 1.0, 1.0
    else:
        freqs, mult, factor = yarn(rope, m.theta, m.yarn)
    q_pe = _rope(q[..., nope:], freqs, mult)
    k_pe = _rope(ckv[:, None, lkv:], freqs, mult)
    heads = q.shape[1]
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (h.shape[0], heads, rope))],
                        -1)
    o = _attention(q, k, kv[..., nope:], d ** -0.5 * factor, block, variant)
    return _mm("shk,hkd->sd", o, lp["wo"], variant)


def route(lp, h, m, variant):
    """-> (chosen [S, k], weights [S, k]): the group-limited top k."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, lp["moe_router"], variant))
    b = scores + lax.stop_gradient(lp["moe_bias"])
    s, e = b.shape
    if m.groups > 1 and variant != "groups_ignored":
        by_group = b.reshape(s, m.groups, e // m.groups)
        group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], -1)
        rank = jnp.argsort(jnp.argsort(-group_score, axis=-1), axis=-1)
        b = jnp.where((rank < m.kept)[:, :, None], by_group,
                      -jnp.inf).reshape(s, e)
    _, chosen = lax.top_k(b, m.k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * m.route_scale


def experts(lp, h, m, variant):
    """Shared(h) + the held experts' part of the routed sum."""
    chosen, weights = route(lp, h, m, variant)

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


def layer(lp, x, m, block, variant):
    """One layer on one sequence. lp, x: float32; x is [S, D]."""
    x = x + attention(lp, _rms_norm(x, lp["ln1"], m.eps), m, block, variant)
    h = _rms_norm(x, lp["ln2"], m.eps)
    if "moe_router" in lp:
        return x + experts(lp, h, m, variant)
    return x + _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"], variant)


@functools.partial(jax.jit, static_argnames=("m", "block", "variant"))
def _layer_fwd(lp, x, m, block, variant):
    return layer(_f32(lp), x, m, block, variant)


@functools.partial(jax.jit, static_argnames=("m", "block", "variant"),
                   donate_argnums=(3,))
def _layer_bwd(lp, x, dy, acc, m, block, variant):
    """-> (dx, acc + this sequence's gradient of the layer's weights)."""
    _, vjp = jax.vjp(lambda p, a: layer(p, a, m, block, variant),
                     _f32(lp), x)
    dlp, dx = vjp(dy)
    return dx, jax.tree_util.tree_map(jnp.add, acc, dlp)


def split_layers(w):
    """The stacked weights as a list of layers in order: the ``dense``
    stack [n, ...], then the expert layers ``layers`` [L, ...].
    -> [(group, {leaf: this layer's array})]."""
    return [(group, {n: a[i] for n, a in w[group].items()})
            for group in ("dense", "layers") if w.get(group)
            for i in range(next(iter(w[group].values())).shape[0])]


def train(make_weights, batches, lr, steps, m, variant="exact", block=512,
          head_block=2048, devices=None):
    """Follow ``steps`` steps of training from ``make_weights()``.

    make_weights: () -> {"embed": [V, D], "dense": {leaf: [n, ...]},
        "layers": {leaf: [L, ...]}, "ln_f": [D], "w_out": [D, V]}
        in the type the state is held in. Called again at the end for the
        first weights, so nothing is kept twice while the steps run.
    batches: list of (tokens [B, S], targets [B, S]) int arrays, one a step.

    -> {"loss": [one a step], "grad_norm": {leaf: norm of the FIRST step's
        gradient}, "delta_norm": {leaf: norm of the weights' change over all
        the steps}, "moved": {leaf: elements the steps moved}}, a stacked
        leaf's norm taken over all its layers.
    """
    home = (devices or [jax.devices()[0]])[0]
    w = make_weights()
    layers = [(g, jax.device_put(lp, home)) for g, lp in split_layers(w)]
    top = {n: jax.device_put(w[n], home) for n in TOP}
    del w
    # the layers' momentum waits on the host between its updates: weights,
    # momentum and a layer's float32 gradient would not fit one chip
    m_layers = [{n: np.zeros(a.shape, a.dtype) for n, a in lp.items()}
                for _, lp in layers]
    m_top = jax.tree_util.tree_map(jnp.zeros_like, top)
    names = sorted({leaf_name(g, n) for g, lp in layers for n in lp
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
                         axis=0).astype(jnp.float32)
            for i, (_, lp) in enumerate(layers):
                xs[i][b] = x
                x = _layer_fwd(lp, x, m, block, variant)
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
        for i in reversed(range(len(layers))):
            group, lp = layers[i]
            acc = jax.device_put(_zeros_like_f32(lp), home)
            for b in range(n_seq):
                dxs[b], acc = _layer_bwd(lp, xs[i][b], dxs[b], acc, m, block,
                                         variant)
                xs[i][b] = None
            for n in lp:
                if n in BUFFERS:
                    continue
                lp[n], mom, s = _sgd(lp[n], jax.device_put(m_layers[i][n],
                                                           home), acc[n], lr)
                m_layers[i][n] = np.asarray(mom)
                sq[leaf_name(group, n)] += float(s)
            del acc
        # embedding: scatter the sequences' input gradients into its rows
        g = jnp.zeros(top["embed"].shape, jnp.float32, device=home)
        for b in range(n_seq):
            g = g.at[jax.device_put(tokens[b], home)].add(dxs[b])
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
    for (group, lp), (_, lp0) in zip(layers, split_layers(w0)):
        for n in lp:
            if n not in BUFFERS:
                first = jax.device_put(lp0[n], home)
                delta[leaf_name(group, n)] += float(_sq_diff(lp[n], first))
                moved[leaf_name(group, n)] += int(_moved(lp[n], first))
    return {"loss": losses,
            "grad_norm": {n: v ** 0.5 for n, v in grad_sq.items()},
            "delta_norm": {n: v ** 0.5 for n, v in delta.items()},
            "moved": moved}
