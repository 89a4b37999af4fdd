"""The mixing stage of compressed convolutional attention (arXiv:2510.04476):
what lies between an attention layer's projections and its kernel.

q0 [B, S, H, d] and k0 [B, S, G, d] are the latents as projected. Packed
head beside head, c = [q0 | k0] (H + G heads, (H + G) d channels), they pass
two causal convolutions over positions (position -1 reads nought):

    c1_t    = a0 * c_{t-1} + a1 * c_t + b0              one pair a channel
    c2_t[j] = c1_{t-1}[j] M0_j + c1_t[j] M1_j + b1_j    [d, d] a head and tap

then take the mean of the two latents BEFORE the convolutions,

    m_q[i] = (q0[i] + k0[i // (H/G)]) / 2;  m_k[g] = mean of its heads' m_q
    q = qc + m_q;  k = kc + m_k                         ([qc | kc] = c2)

and are normalised to unit length over a head, times sqrt(d), k also times
one learned temperature a key-value head. Half of v comes from the position
before (``shift``). Conv 0 is shifted multiply-adds (``ssm.shifted_sum``),
conv 1 a batched product a tap, so the MXU does it. The first convolution's
sum, the sum of the second's taps, the mean, the norms and the temperature
are float32 inside their fusions; what a stage or a product hands on is in
the activations' type. No Pallas kernel: the
stage is XLA's, under the scope ``mx.cca_mix``. ``metrics()["cca"]`` says
what the newest step traced mixes and the least bytes a layer's stage moves.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .. import profiler as _profiler
from . import ssm as _ssm

__all__ = ["mix_leaves", "mix", "shift", "mix_bytes"]


def mix_leaves(cfg):
    """{leaf: (shape of one layer, how it is made, spec of one layer)}: the
    stage's rows of ``transformer._layer_leaves``. How: a fan_in (N(0,
    1/fan_in)), None (ones) or "zeros". ``cca_conv0_w`` is [taps, channels],
    tap k on position t - (taps - 1) + k; ``cca_conv1_w`` [taps, heads, d,
    d] likewise."""
    heads, d = cfg.n_heads + cfg.kv_heads, cfg.head_dim
    t0, t1 = cfg.mix_taps
    return {"cca_conv0_w": ((t0, heads * d), t0, (None, None)),
            "cca_conv0_b": ((heads * d,), "zeros", (None,)),
            "cca_conv1_w": ((t1, heads, d, d), t1 * d,
                            (None, None, None, None)),
            "cca_conv1_b": ((heads, d), "zeros", (None, None)),
            "cca_temp": ((cfg.kv_heads,), None, (None,))}


def shift(a):
    """a[:, t] <- a[:, t - 1] along positions (axis 1), nought at t = 0."""
    front = [(0, 0)] * a.ndim
    front[1] = (1, 0)
    return jnp.pad(a, front)[:, :-1]


def _unit(x, scale=None):
    """sqrt(d) x / |x| over a head, float32 (times ``scale`` a head)."""
    d = x.shape[-1]
    out = x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-20) \
        * (d ** 0.5)
    return out if scale is None else out * scale[:, None]


def mix(q0, k0, lp):
    """q0: [B, S, H, d]; k0: [B, S, G, d]; lp: ``mix_leaves``. -> (q, k) in
    their own shapes and type: convolved, the mean of the latents added,
    unit length times sqrt(d), k times its temperature."""
    f32, dtype = jnp.float32, q0.dtype
    B, S, H, d = q0.shape
    G = k0.shape[2]
    c = jnp.concatenate([q0, k0], axis=2).reshape(B, S, (H + G) * d)
    w0, w1 = lp["cca_conv0_w"], lp["cca_conv1_w"]
    # ``ssm.conv_taps``'s values, c padded in its own type: cast first, XLA
    # writes a padded float32 copy of it (PERF.md section 6, PR 35)
    c1 = _ssm.shifted_sum(
        jnp.pad(c, ((0, 0), (w0.shape[0] - 1, 0), (0, 0))), w0,
        lp["cca_conv0_b"], S).astype(dtype).reshape(B, S, H + G, d)
    taps = w1.shape[0]
    c1p = jnp.pad(c1, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    # a tap's product leaves the MXU in the activations' type, as every
    # projection's does; the taps, the bias and the mean are summed in
    # float32 inside the fusion that takes the norms
    c2 = lp["cca_conv1_b"].astype(f32)
    for k in range(taps):
        c2 = c2 + jnp.einsum("bsjd,jde->bsje", c1p[:, k:k + S],
                             w1[k]).astype(f32)
    m_q = (q0.astype(f32).reshape(B, S, G, H // G, d)
           + k0.astype(f32)[:, :, :, None]) * 0.5
    q = c2[:, :, :H] + m_q.reshape(B, S, H, d)
    k = c2[:, :, H:] + jnp.mean(m_q, axis=3)
    return (_unit(q).astype(dtype),
            _unit(k, lp["cca_temp"].astype(f32)).astype(dtype))


def mix_bytes(batch, seq, channels, shifted, itemsize):
    """The least bytes one layer's stage moves through HBM, forward and
    backward, with every array in the activations' type: the forward reads c
    and writes [q | k], the backward reads c and their gradient and writes
    dc (five arrays of ``channels``); the shifted half of v is read and
    written, and so is its gradient (four of ``shifted``). What the layer's
    recompute runs again is not in it."""
    return itemsize * batch * seq * (5 * channels + 4 * shifted)


# metrics()["cca"]: what the newest step traced mixes
# mxlint: disable=MX003 (GIL-atomic stores while a step is traced; one writer, the tracing thread)
_CCA = {"layers": 0, "q_latent": 0, "kv_latent": 0, "taps": [],
        "mix_bytes": 0}


def note(cfg, batch, seq):
    """Called while a step of ``cfg`` on [batch, seq] tokens is traced: a
    fact of the program and not a count, so no reset clears it."""
    d = cfg.head_dim
    _CCA.update(layers=cfg.attn_layers, q_latent=cfg.n_heads * d,
                kv_latent=cfg.kv_heads * d, taps=list(cfg.mix_taps),
                mix_bytes=mix_bytes(
                    batch, seq, (cfg.n_heads + cfg.kv_heads) * d,
                    (cfg.kv_heads - cfg.kv_heads // 2) * d
                    if cfg.v_shift else 0, jnp.dtype(cfg.dtype).itemsize))


def cca_stats():
    """``metrics()['cca']``: of the newest train step traced whose attention
    has the mixing stage: ``layers`` (attention layers), ``q_latent`` and
    ``kv_latent`` (channels of the query and of the key latents), ``taps``
    (of the two convolutions), ``mix_bytes`` (the least bytes a layer's
    stage moves, ``mix_bytes``: what a trace's ``mx.cca_mix`` milliseconds
    are read against). Noughts where no step has one."""
    return dict(_CCA, taps=list(_CCA["taps"]))


_profiler.register_stats_provider("cca", cca_stats)
