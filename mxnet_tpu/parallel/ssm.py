"""A state-space mixer (Mamba-2, arXiv:2405.21060) in attention's place.

For head n of H (size P, state N, ONE group: B and C are shared by all
heads) the layer is a recurrence over positions,

    S_t = exp(dt_t A) S_{t-1} + dt_t * xs_t (outer) Bm_t     [P, N], S_0 = 0
    y_t = S_t Cm_t + D xs_t

with ``dt = softplus(. + dt_bias)`` and ``A = -exp(A_log)`` a head. Here it
is computed in its CHUNKED form, ``chunk`` positions at a time, so that the
work is matrix products: within a chunk the masked product
``(Cm Bm^T * decay) (dt * xs)``; a chunk's own state from its own tokens;
the states passed from chunk to chunk; the carried state's part of each
output. dt, A, the decays and the states are float32; the products' operands
are in the activations' type and accumulate in float32. JAX differentiates
it, under the layer remat.

The decay of one chunk is a [chunk, chunk] float32 array a head and a chunk:
[chunks, heads, 256, 256] for all heads at once is 1 GiB at 8192 tokens. So
the heads go in blocks (``block_heads``, from shapes), each block under a
remat of its own: what the backward keeps of a block is its inputs.

Between ``in_proj`` and ``out_proj`` every [tokens, channels] array crosses
HBM in the activations' type; float32 lives inside a fusion. Two places
need help to keep that rule. The conv (``conv_silu``) shifts x in its own
type and has a backward of its own: JAX's derivative of the shifted sum
keeps a padded float32 copy of x and four float32 products. And the scan's
output is handed to the gate behind a ``lax.optimization_barrier``: XLA
otherwise hoists the gate's cast to float32 above the change of layout from
the scan's blocks to [tokens, channels], and moves the array three times at
twice the width, in the forward and again in the layer's recompute.

Scopes: ``mx.ssm_proj`` (the products in and out, the layer's first norm),
``mx.ssm_conv``, ``mx.ssm_scan`` (dt, decays, the chunked scan, the D skip),
``mx.ssm_gate`` (gate and norm). ``metrics()["ssm"]`` says how the newest
step traced runs its scan, and the least bytes its conv and gate must move.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler as _profiler

__all__ = ["conv_taps", "shifted_sum", "conv_silu", "chunked_scan",
           "block_heads", "scan_temp_bytes", "stage_bytes", "mixer",
           "mixer_leaves"]

# the largest temporary a block of heads may make (its decays): the
# backward holds a few of that size at once, 0.3 GB of them at 8192 tokens
_SCAN_TEMP_BYTES = 64 << 20


def mixer_leaves(cfg):
    """{leaf: (shape of one layer, how it is made, spec of one layer)} of a
    mixer: ``transformer._layer_leaves``'s rows for the kind "mamba". How:
    a fan_in (N(0, 1/fan_in)), None (ones), "a_log" (log of uniform(1, 16))
    or "dt_bias" (the inverse softplus of a log-uniform(0.001, 0.1) step).
    ``ssm_in`` is the published ``in_proj``, [gate | conv channels | dt];
    ``ssm_conv_w`` the published depthwise kernel as [taps, channels], tap k
    on position t - (taps - 1) + k. The mixer is whole on every chip: with
    one group its norm and its B and C span all heads."""
    D, H, N = cfg.dim, cfg.ssm_heads, cfg.ssm_state
    inner = H * cfg.ssm_head_size
    conv = inner + 2 * N
    return {"ssm_in": ((D, 2 * inner + 2 * N + H), D, (None, None)),
            "ssm_conv_w": ((cfg.ssm_conv, conv), cfg.ssm_conv, (None, None)),
            "ssm_conv_b": ((conv,), cfg.ssm_conv, (None,)),
            "ssm_dt_bias": ((H,), "dt_bias", (None,)),
            "ssm_a_log": ((H,), "a_log", (None,)),
            "ssm_d": ((H,), None, (None,)),
            "ssm_norm": ((inner,), None, (None,)),
            "ssm_out": ((inner, D), inner, (None, None))}


def init_leaf(key, how, shape):
    """A leaf whose start is no Gaussian (``mixer_leaves``), in float32."""
    u = jax.random.uniform(key, shape)
    if how == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    assert how == "dt_bias", how
    step = jnp.exp(jnp.log(0.001) + u * (jnp.log(0.1) - jnp.log(0.001)))
    return step + jnp.log(-jnp.expm1(-step))     # softplus(this) == step


def shifted_sum(xp, w, b, seq):
    """b + sum_k w[k] xp[t + k] for ``seq`` positions t, tap by tap in that
    order. ``xp`` is x already padded, in whatever type: each shifted view is
    cast to float32 inside the sum, and since a shift and a cast commute the
    values do not depend on which came first. -> float32 [B, seq, C]."""
    w = w.astype(jnp.float32)
    u = b.astype(jnp.float32)
    for k in range(w.shape[0]):
        u = u + w[k] * xp[:, k:k + seq].astype(jnp.float32)
    return u


def conv_taps(x, w, b):
    """Causal depthwise convolution as shifted multiply-adds. x: [B, S, C];
    w: [K, C]; b: [C]. y[t] = b + sum_k w[k] x[t - (K - 1) + k], positions
    before the sequence reading nought. The plain form, x cast to float32
    and then padded: what ``conv_silu`` is held to. -> float32 [B, S, C]."""
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    return shifted_sum(xp, w, b, x.shape[1])


@jax.custom_vjp
def conv_silu(x, w, b):
    """``silu(conv_taps(x, w, b))`` in x's type, to the bit, with x padded
    in ITS OWN type (no float32 copy of it is made) and a backward written
    out: what crosses HBM is x, y, dy and dx in x's type and ONE float32
    array (``ds``); the pre-activation is recomputed from x. JAX's own
    derivative of ``conv_taps`` keeps five float32 [B, S, C] arrays."""
    front = ((0, 0), (w.shape[0] - 1, 0), (0, 0))
    return jax.nn.silu(shifted_sum(jnp.pad(x, front), w, b,
                                   x.shape[1])).astype(x.dtype)


def _conv_silu_fwd(x, w, b):
    return conv_silu(x, w, b), (x, w, b)


def _conv_silu_bwd(res, dy):
    """With u the pre-activation and ds = dy silu'(u):
    dx[t] = sum_k w[k] ds[t + (K-1) - k], dw[k] = sum_t ds[t] x[t - (K-1) + k],
    db = sum_t ds[t]. x and dy are padded in their own type by K-1 positions
    past the sequence's end too, where dy, and so ds, reads nought: every
    shift is then a slice, none leaves its own sequence, and no float32 array
    is padded."""
    x, w, b = res
    taps, seq = w.shape[0], x.shape[1]
    ext = seq + taps - 1
    xp = jnp.pad(x, ((0, 0), (taps - 1, taps - 1), (0, 0)))
    u = shifted_sum(xp, w, b, ext)
    sig = jax.nn.sigmoid(u)
    ds = jnp.pad(dy, ((0, 0), (0, taps - 1), (0, 0))).astype(jnp.float32) \
        * (sig * (1.0 + u * (1.0 - sig)))
    wf = w.astype(jnp.float32)
    dx = sum(wf[k] * ds[:, taps - 1 - k:taps - 1 - k + seq]
             for k in range(taps))
    dw = jnp.stack([jnp.sum(ds * xp[:, k:k + ext].astype(jnp.float32),
                            axis=(0, 1)) for k in range(taps)])
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            jnp.sum(ds, axis=(0, 1)).astype(b.dtype))


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def scan_temp_bytes(batch, seq, chunk, heads):
    """Bytes of the scan's largest temporary for ``heads`` heads at once:
    their decays, float32 [batch, chunks, heads, chunk, chunk]."""
    return 4 * batch * seq * min(chunk, seq) * heads


def stage_bytes(batch, seq, channels, inner, itemsize):
    """The least bytes one layer's conv and gate move through HBM, forward
    and backward, with every array in the activations' type: the conv reads
    x and writes y, its backward reads x and dy and writes dx (five arrays
    of ``channels``); the gate reads y and z and writes g, its backward
    reads y, z and dout and writes dy and dz (eight of ``inner``). What the
    layer's recompute runs again (the five forward arrays) is not in it."""
    return itemsize * batch * seq * (5 * channels + 8 * inner)


def block_heads(batch, seq, chunk, heads):
    """The most heads a block may take: the largest divisor of ``heads``
    whose decays stay under ``_SCAN_TEMP_BYTES`` (one head where none
    does)."""
    fit = [n for n in range(1, heads + 1) if heads % n == 0 and
           scan_temp_bytes(batch, seq, chunk, n) <= _SCAN_TEMP_BYTES]
    return max(fit, default=1)


def _blocks(batch, seq, heads, chunk, heads_at_once=None):
    """-> (positions a chunk, heads a block) of a scan over [batch, seq]:
    a sequence shorter than ``chunk`` is one chunk; ``heads_at_once`` None
    is ``block_heads``'s choice."""
    chunk = min(chunk, seq)
    if seq % chunk:
        raise ValueError("seq_len=%d is not whole chunks of %d"
                         % (seq, chunk))
    hb = heads_at_once or block_heads(batch, seq, chunk, heads)
    if heads % hb:
        raise ValueError("heads_at_once=%d does not divide the %d heads"
                         % (hb, heads))
    return chunk, hb


def _block(xs, dt, a_head, d_head, bm, cm, cb):
    """The chunked scan of one block of heads, every chunk at once.
    xs: [B, c, h, Q, P]; dt: [B, c, h, Q] float32; a_head, d_head: [h];
    bm, cm: [B, c, Q, N]; cb: [B, c, Q, Q] = cm bm^T. -> [B, c, h, Q, P]."""
    f32, dtype = jnp.float32, xs.dtype
    q = xs.shape[3]
    cum = jnp.cumsum(dt * a_head[:, None], axis=-1)       # <= 0, falling
    # within a chunk: position i reads j <= i through exp(cum_i - cum_j)
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    xdt = xs.astype(f32) * dt[..., None]
    y = jnp.einsum("bchij,bchjp->bchip",
                   (cb[:, :, None] * decay).astype(dtype), xdt.astype(dtype),
                   preferred_element_type=f32)
    # a chunk's own state: its tokens, each decayed to the chunk's end
    total = cum[..., -1]                                  # [B, c, h]
    own = jnp.einsum(
        "bchjp,bcjn->bchpn",
        (xdt * jnp.exp(total[..., None] - cum)[..., None]).astype(dtype), bm,
        preferred_element_type=f32)
    # from chunk to chunk: chunk c starts from the states of the chunks
    # before it, each decayed through the chunks between
    upto = jnp.cumsum(total, axis=1)
    before = upto - total
    earlier = jnp.tril(jnp.ones((xs.shape[1],) * 2, bool), -1)
    passed = jnp.exp(jnp.where(
        earlier[None, :, :, None],
        before[:, :, None, :] - upto[:, None, :, :], -jnp.inf))
    start = jnp.einsum("bceh,behpn->bchpn", passed, own,
                       precision=lax.Precision.HIGHEST)
    # the carried state's part of each output
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcin,bchpn->bchip", cm, start.astype(dtype),
        preferred_element_type=f32)
    return (y + d_head[:, None, None] * xs.astype(f32)).astype(dtype)


def chunked_scan(xs, dt, a_head, bm, cm, d_head, chunk):
    """The recurrence of the module's docstring in its chunked form.
    xs: [B, S, H, P]; dt: [B, S, H] float32, positive; a_head: [H] float32,
    negative; bm, cm: [B, S, N]; d_head: [H]; ``chunk``: positions a chunk
    (a sequence shorter than one is one chunk); the heads go in blocks
    chosen from shapes (``_blocks``). -> y [B, S, H, P]."""
    B, S, H, _ = xs.shape
    return _scan(xs, dt, a_head, bm, cm, d_head, *_blocks(B, S, H, chunk))


def _scan(xs, dt, a_head, bm, cm, d_head, chunk, hb):
    """``chunked_scan`` with ``_blocks``'s answer: positions a chunk, heads
    a block."""
    B, S, H, P = xs.shape
    c, nb, f32 = S // chunk, H // hb, jnp.float32
    bm_c = bm.reshape(B, c, chunk, -1)
    cm_c = cm.reshape(B, c, chunk, -1)
    cb = jnp.einsum("bcin,bcjn->bcij", cm_c, bm_c, preferred_element_type=f32)
    # [block, B, chunks, heads of the block, chunk, ...]
    xs_b = jnp.transpose(xs.reshape(B, c, chunk, nb, hb, P),
                         (3, 0, 1, 4, 2, 5))
    dt_b = jnp.transpose(dt.astype(f32).reshape(B, c, chunk, nb, hb),
                         (3, 0, 1, 4, 2))

    @jax.checkpoint
    def one(args):
        return _block(*args, bm_c, cm_c, cb)

    y = lax.map(one, (xs_b, dt_b, a_head.astype(f32).reshape(nb, hb),
                      d_head.astype(f32).reshape(nb, hb)))
    return jnp.transpose(y, (1, 2, 4, 0, 3, 5)).reshape(B, S, H, P)


def mixer(h, lp, cfg):
    """h: [B, S, D], the layer's normed input; lp: ``mixer_leaves``.
    -> [B, S, D], what the layer adds to its residual."""
    B, S, _ = h.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_size, cfg.ssm_state
    inner = H * P
    with jax.named_scope("mx.ssm_proj"):
        zxbcdt = jnp.einsum("bsd,de->bse", h, lp["ssm_in"])
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:2 * inner + 2 * N]
        dt = zxbcdt[..., 2 * inner + 2 * N:]
    with jax.named_scope("mx.ssm_conv"):
        xbc = conv_silu(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"])
    with jax.named_scope("mx.ssm_scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["ssm_dt_bias"].astype(jnp.float32))
        y = chunked_scan(
            xbc[..., :inner].reshape(B, S, H, P), dt,
            -jnp.exp(lp["ssm_a_log"].astype(jnp.float32)),
            xbc[..., inner:inner + N], xbc[..., inner + N:], lp["ssm_d"],
            cfg.ssm_chunk)
    with jax.named_scope("mx.ssm_gate"):
        # handed over as the array it is, or XLA hoists the cast below above
        # the copies out of the scan's blocks (the module's docstring)
        y = lax.optimization_barrier(y.reshape(B, S, inner))
        # gate first, then the norm over ALL channels (one group)
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        g = (g * lax.rsqrt(var + cfg.norm_eps)).astype(h.dtype) \
            * lp["ssm_norm"]
    with jax.named_scope("mx.ssm_proj"):
        return jnp.einsum("bse,ed->bsd", g, lp["ssm_out"])


# metrics()["ssm"]: how the newest step traced runs its mixers' scans
# mxlint: disable=MX003 (GIL-atomic stores while a step is traced; one writer, the tracing thread)
_SSM = {"layers": 0, "chunk": 0, "heads_at_once": 0, "scan_temp_bytes": 0,
        "stage_bytes": 0}


def note(cfg, batch, seq):
    """Called while a step of ``cfg`` on [batch, seq] tokens is traced: a
    fact of the program and not a count, so no reset clears it."""
    chunk, hb = _blocks(batch, seq, cfg.ssm_heads, cfg.ssm_chunk)
    inner = cfg.ssm_heads * cfg.ssm_head_size
    _SSM.update(layers=sum(k == "mamba" for k in cfg.layer_pattern)
                * cfg.periods, chunk=chunk, heads_at_once=hb,
                scan_temp_bytes=scan_temp_bytes(batch, seq, chunk, hb),
                stage_bytes=stage_bytes(batch, seq, inner + 2 * cfg.ssm_state,
                                        inner, jnp.dtype(cfg.dtype).itemsize))


def ssm_stats():
    """``metrics()['ssm']``: of the newest train step traced that has
    mixers: ``layers`` (mixer layers), ``chunk`` (positions a chunk),
    ``heads_at_once`` (heads a block of the scan), ``scan_temp_bytes`` (the
    scan's largest temporary, by shapes), ``stage_bytes`` (the least bytes a
    layer's conv and gate move, ``stage_bytes``: what a trace's
    ``mx.ssm_conv`` and ``mx.ssm_gate`` milliseconds are read against).
    Noughts where no step has any."""
    return dict(_SSM)


_profiler.register_stats_provider("ssm", ssm_stats)
