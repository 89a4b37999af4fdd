"""Fused training-mode BatchNorm (stats + normalize + optional
activation) as Pallas TPU kernels.

Why this kernel exists: BENCH_r05's worst non-matmul numerics outlier
is BatchNorm (11,482 ULP vs the CPU golden) and the XLA lowering of the
fallback materializes the activation between the stat reduction and the
normalize. This kernel computes the whole training-mode BN

    mean, var = moments(x)           # f32 accumulation, deterministic
    y = (x - mean) / sqrt(var + eps) * gamma + beta
    out = act(y)                     # optional relu, fused

with every intermediate held in VMEM in f32:

- ``stats`` kernel: grid (channel tiles, row tiles) with the row sweep
  innermost; each cell folds its (TR, TC) block with the deterministic
  pairwise tree (``tree_fold_rows``) and accumulates sum/sum-of-squares
  partials into a VMEM-resident f32 block (the conv_fused d-weight
  accumulation pattern), converting to mean/var on the last row tile.
  Single-pass E[x^2]-E[x]^2 in f32 with a >=0 clamp: the cancellation
  term is ~mean^2 * 2^-24, negligible against every reachable eps.
- ``apply`` kernel: elementwise normalize + optional relu over the same
  tiling, reading the (1, C) stats once per channel tile. The
  activation never exists unnormalized in HBM.
- backward: two kernels in the same shape — a reduce kernel
  accumulating dbeta/dgamma (recomputing xhat and the relu mask in
  VMEM) and an elementwise d-input kernel applying the standard
  batch-stat backward ``dx = gamma*inv*(dy' - E[dy'] - xhat*E[dy'*xhat])``.

Numerics contract: stats accumulate in f32 regardless of input dtype
and the normalize chain is correctly-rounded primitives only
(sub/mul/add, ``1/sqrt`` instead of the approximate ``lax.rsqrt``), so
kernel-vs-reference parity is ULP-bounded (gated in
``BENCH_MODEL=fused_kernels`` and tests/test_pallas_kernels.py).
``ops/nn.py:batch_norm`` routes its training-mode, channels-last path
here on TPU (``MXTPU_FUSED_BN``; ``use_global_stats`` / inference and
non-trailing-axis layouts keep the XLA fallback, whose stats share the
same deterministic ``tree_fold_rows``). Moving-stat updates stay with
the caller (gluon layer), exactly as for the fallback.

The reference's analog is the fused BatchNorm+activation CUDA path
(ref: src/operator/nn/batch_norm.cu + cudnn_batch_norm); the TPU-native
design additionally pins the reduction ORDER so CPU goldens and device
runs agree to a few ULP.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ._compile_attr import attributed
from .conv_fused import _use_pallas
from ..base import getenv as _getenv

__all__ = ["fused_batch_norm", "batchnorm_reference", "tree_fold_rows",
           "engaged"]

_ENV = "MXTPU_FUSED_BN"


def _setting():
    return _getenv(_ENV, "1")


def _force_interpret():
    return _setting() == "interpret"


# The deterministic reduction, in three composable pieces. The shape of
# the algorithm is chosen so the Pallas kernel's tiling DECOMPOSES the
# reference tree exactly: ``fold_blocks`` sums fixed 64-row blocks with
# a contiguous-halves tree (any row tile that is a multiple of 64
# produces the identical per-block partials), ``fold_partials`` folds
# the per-block partials with the same tree, and the whole pipeline
# contains only f32 ADDS over already-rounded values — the one
# reduction shape that is bitwise-reproducible across platforms,
# fusion contexts, and tilings (a mul feeding an add would get
# FMA-contracted differently per compiled program; see ``exact_sq``
# for how the variance path neutralizes that too).

FOLD_BLOCK = 64


def _fold_pow2(v, axis):
    """Contiguous-halves fold of a power-of-two axis down to length 1."""
    p = v.shape[axis]
    while p > 1:
        p //= 2
        lo = jax.lax.slice_in_dim(v, 0, p, axis=axis)
        hi = jax.lax.slice_in_dim(v, p, 2 * p, axis=axis)
        v = lo + hi
    return v


def fold_blocks(v):
    """(R, C) -> (ceil(R/64), C): per-64-row-block column sums, each
    block folded by a contiguous-halves tree. Rows pad to a block
    multiple with exact zeros. Runs identically as XLA ops and inside
    a Mosaic kernel (static leading-dim reshape + sublane slicing)."""
    n, c = v.shape
    nb = -(-n // FOLD_BLOCK)
    if nb * FOLD_BLOCK != n:
        v = jnp.concatenate(
            [v, jnp.zeros((nb * FOLD_BLOCK - n, c), v.dtype)], axis=0)
    return _fold_pow2(v.reshape(nb, FOLD_BLOCK, c), 1).reshape(nb, c)


def fold_partials(parts):
    """(NB, C) block partials -> (1, C) total, padding NB to the next
    power of two with exact zeros and folding contiguous halves."""
    n = parts.shape[0]
    p = 1
    while p < n:
        p *= 2
    if p != n:
        parts = jnp.concatenate(
            [parts, jnp.zeros((p - n,) + parts.shape[1:], parts.dtype)],
            axis=0)
    return _fold_pow2(parts, 0)


def tree_fold_rows(v):
    """Deterministic column sum: (R, C) -> (1, C), f32 in f32 out.
    ``fold_partials(fold_blocks(v))`` — every platform and every
    fusion context executes the SAME sequence of correctly-rounded f32
    adds, so CPU goldens, TPU runs, and the Pallas kernel's tiled
    partials produce bitwise-identical sums. The property the
    BatchNorm stats (and the per-op ULP gate in
    benchmark/tpu_numerics.py, budget 64) rest on."""
    return fold_partials(fold_blocks(v))


def exact_sq(x):
    """x^2 by exact-product splitting, immune to FMA contraction.

    LLVM/Mosaic may contract ``t = x*x`` feeding an add into an FMA —
    a choice that differs per compiled program, which would make any
    sum of squares context-dependent in the last bit. Split x by
    mantissa masking (pure bit ops) into xh + xl with <=12 significant
    bits each: xh^2, 2*xh*xl and xl^2 are then EXACTLY representable
    f32 products, and contracting an exact product into an add is a
    rounding no-op — so ``xh^2 + (2*xh*xl + xl^2)`` is deterministic
    everywhere (and slightly MORE accurate than round(x*x))."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    xh = jax.lax.bitcast_convert_type(
        bits & jnp.int32(-4096), jnp.float32)  # keep top 11 mantissa bits
    xl = x - xh
    t = xh * xh + (2.0 * (xh * xl) + xl * xl)
    # inf: xl = inf - inf = nan; mirror plain x*x for non-finite inputs
    return jnp.where(jnp.isfinite(x), t, x * x)


def exact_mul(a, b):
    """a*b by the same exact-product splitting as ``exact_sq`` —
    deterministic under any FMA contraction choice, and the building
    block that makes the whole BN normalize chain bitwise-reproducible:
    ``exact_mul(x - mean, inv*gamma) + beta`` ends in an add whose
    multiply operand is already rounded, so no backend can contract it
    differently."""
    abits = jax.lax.bitcast_convert_type(a, jnp.int32)
    bbits = jax.lax.bitcast_convert_type(b, jnp.int32)
    ah = jax.lax.bitcast_convert_type(abits & jnp.int32(-4096),
                                      jnp.float32)
    bh = jax.lax.bitcast_convert_type(bbits & jnp.int32(-4096),
                                      jnp.float32)
    al, bl = a - ah, b - bh
    t = ah * bh + (ah * bl + (al * bh + al * bl))
    return jnp.where(jnp.isfinite(a) & jnp.isfinite(b), t, a * b)


def batchnorm_reference(x, gamma, beta, eps=1e-3, act=None):
    """jnp semantics of the fused op (fallback + autodiff + goldens).

    x: (..., C) channels-last; gamma, beta: (C,).
    Returns (out[x.dtype], mean32, var32) with (C,) f32 stats. The stat
    math is the kernel's exactly: deterministic tree-fold sums, f32
    single-pass variance clamped at 0, ``1/sqrt`` normalize.
    """
    C = x.shape[-1]
    x2 = x.reshape(-1, C)
    R = x2.shape[0]
    xf = x2.astype(jnp.float32)
    mean = tree_fold_rows(xf)[0] / R
    var = jnp.maximum(
        tree_fold_rows(exact_sq(xf))[0] / R - exact_sq(mean), 0.0)
    inv = 1.0 / jnp.sqrt(var + eps)
    y = exact_mul(xf - mean, inv * gamma.astype(jnp.float32)) \
        + beta.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype).reshape(x.shape), mean, var


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

from jax.experimental import pallas as pl                # noqa: E402

# same conservative working budget as conv_fused (Mosaic's scoped-VMEM
# accounting runs a few MB above the block-size sum)
_VMEM_BUDGET = 7 * 1024 * 1024


def _stats_kernel(x_ref, sum_ref, sq_ref):
    # per-block partial sums only: the cross-tile combination happens
    # in the wrapper with fold_partials, so the kernel's tiling
    # reproduces the reference tree EXACTLY (tiles are multiples of
    # FOLD_BLOCK, and fold_blocks of a tile == that tile's slice of
    # fold_blocks over the full array)
    xf = x_ref[:].astype(jnp.float32)
    sum_ref[0] = fold_blocks(xf)
    sq_ref[0] = fold_blocks(exact_sq(xf))


def _apply_kernel(x_ref, g_ref, b_ref, mean_ref, var_ref, o_ref, *,
                  eps, act):
    inv = 1.0 / jnp.sqrt(var_ref[:] + eps)
    y = exact_mul(x_ref[:].astype(jnp.float32) - mean_ref[:],
                  inv * g_ref[:].astype(jnp.float32)) \
        + b_ref[:].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def _bwd_reduce_kernel(x_ref, g_ref, b_ref, mean_ref, var_ref, dy_ref,
                       db_ref, dg_ref, *, eps, act):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        db_ref[:] = jnp.zeros_like(db_ref)
        dg_ref[:] = jnp.zeros_like(dg_ref)

    inv = 1.0 / jnp.sqrt(var_ref[:] + eps)
    xhat = (x_ref[:].astype(jnp.float32) - mean_ref[:]) * inv
    dyf = dy_ref[:].astype(jnp.float32)
    if act == "relu":
        y = xhat * g_ref[:].astype(jnp.float32) \
            + b_ref[:].astype(jnp.float32)
        dyf = dyf * (y > 0.0)
    db_ref[:] += tree_fold_rows(dyf)
    dg_ref[:] += tree_fold_rows(dyf * xhat)


def _bwd_dx_kernel(x_ref, g_ref, b_ref, mean_ref, var_ref, dy_ref,
                   db_ref, dg_ref, dx_ref, *, R, eps, act):
    inv = 1.0 / jnp.sqrt(var_ref[:] + eps)
    g32 = g_ref[:].astype(jnp.float32)
    xhat = (x_ref[:].astype(jnp.float32) - mean_ref[:]) * inv
    dyf = dy_ref[:].astype(jnp.float32)
    if act == "relu":
        y = xhat * g32 + b_ref[:].astype(jnp.float32)
        dyf = dyf * (y > 0.0)
    dx = g32 * inv * (dyf - db_ref[:] / R - xhat * (dg_ref[:] / R))
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _tiles(R, C, xbytes, n_blocks):
    """(TR, TC, fits): row/channel tile so ``n_blocks`` streamed
    (TR, TC) blocks (double-buffered) plus their f32 working copies fit
    the VMEM budget. Row tiles are power-of-two multiples of
    FOLD_BLOCK so each tile's ``fold_blocks`` partials are exactly the
    reference tree's; the real-TPU path additionally requires
    lane-aligned channels (C % 128) and an exact row tiling."""
    tc = C
    tr = 1024

    def est(tr_, tc_):
        return tr_ * tc_ * (2 * n_blocks * xbytes + (n_blocks + 2) * 4)

    while tc > 128 and tc % 2 == 0 and est(min(tr, R), tc) > _VMEM_BUDGET:
        tc //= 2
    while tr > FOLD_BLOCK and (tr > R or R % tr != 0
                               or est(tr, tc) > _VMEM_BUDGET):
        tr //= 2
    fits = (C % 128 == 0 and C % tc == 0 and R % tr == 0
            and est(tr, tc) <= _VMEM_BUDGET)
    return tr, tc, fits


def _fwd_fits(x2):
    R, C = x2.shape
    return _tiles(R, C, jnp.dtype(x2.dtype).itemsize, 2)[2]


def _bwd_fits(x2):
    R, C = x2.shape
    return _tiles(R, C, jnp.dtype(x2.dtype).itemsize, 3)[2]


def _pallas_forward(x2, gamma, beta, eps, act, interpret):
    R, C = x2.shape
    xbytes = jnp.dtype(x2.dtype).itemsize
    TR, TC, _ = _tiles(R, C, xbytes, 2)
    if interpret and R % TR:
        TR = R  # single row tile: no divisibility constraints on CPU
    nr = pl.cdiv(R, TR)
    key = (R, C, str(x2.dtype), act)
    pt = -(-TR // FOLD_BLOCK)  # per-tile partial rows
    # partials are (row tile, pt, C): a row tile under 512 gives pt < 8,
    # and Mosaic takes a sublane block smaller than 8 only where it
    # spans that whole array dim — which the tile's own axis makes true
    part_spec = pl.BlockSpec((1, pt, TC), lambda c, r: (r, 0, c))
    part_shape = jax.ShapeDtypeStruct((nr, pt, C), jnp.float32)
    sums, sqs = attributed("batchnorm_fused.stats", key, lambda:
        pl.pallas_call(
            _stats_kernel,
            grid=(C // TC, nr),
            in_specs=[pl.BlockSpec((TR, TC), lambda c, r: (r, c))],
            out_specs=(part_spec, part_spec),
            out_shape=(part_shape, part_shape),
            interpret=interpret,
        )(x2))
    # finish the tree outside: fold_partials over the per-block sums is
    # bitwise the reference's tree_fold_rows (tile edges sit on
    # FOLD_BLOCK boundaries), so kernel stats == reference stats
    mean = fold_partials(sums.reshape(nr * pt, C)) / R
    var = jnp.maximum(
        fold_partials(sqs.reshape(nr * pt, C)) / R - exact_sq(mean), 0.0)
    g2 = gamma.astype(jnp.float32).reshape(1, C)
    b2 = beta.astype(jnp.float32).reshape(1, C)
    out = attributed("batchnorm_fused.apply", key, lambda:
        pl.pallas_call(
            functools.partial(_apply_kernel, eps=eps, act=act),
            grid=(C // TC, nr),
            in_specs=[
                pl.BlockSpec((TR, TC), lambda c, r: (r, c)),
                pl.BlockSpec((1, TC), lambda c, r: (0, c)),
                pl.BlockSpec((1, TC), lambda c, r: (0, c)),
                pl.BlockSpec((1, TC), lambda c, r: (0, c)),
                pl.BlockSpec((1, TC), lambda c, r: (0, c)),
            ],
            out_specs=pl.BlockSpec((TR, TC), lambda c, r: (r, c)),
            out_shape=jax.ShapeDtypeStruct((R, C), x2.dtype),
            interpret=interpret,
        )(x2, g2, b2, mean, var))
    return out, mean.reshape(C), var.reshape(C)


def _pallas_backward(x2, gamma, beta, mean, var, dy2, eps, act,
                     interpret):
    R, C = x2.shape
    xbytes = jnp.dtype(x2.dtype).itemsize
    TR, TC, _ = _tiles(R, C, xbytes, 3)
    if interpret and R % TR:
        TR = R  # single row tile: no divisibility constraints on CPU
    nr = pl.cdiv(R, TR)
    key = (R, C, str(x2.dtype), act)
    g2 = gamma.astype(jnp.float32).reshape(1, C)
    b2 = beta.astype(jnp.float32).reshape(1, C)
    m2 = mean.reshape(1, C)
    v2 = var.reshape(1, C)
    stat_spec = pl.BlockSpec((1, TC), lambda c, r: (0, c))
    blk_spec = pl.BlockSpec((TR, TC), lambda c, r: (r, c))
    db, dg = attributed("batchnorm_fused.bwd_reduce", key, lambda:
        pl.pallas_call(
            functools.partial(_bwd_reduce_kernel, eps=eps, act=act),
            grid=(C // TC, nr),
            in_specs=[blk_spec, stat_spec, stat_spec, stat_spec,
                      stat_spec, blk_spec],
            out_specs=(stat_spec, stat_spec),
            out_shape=(jax.ShapeDtypeStruct((1, C), jnp.float32),
                       jax.ShapeDtypeStruct((1, C), jnp.float32)),
            interpret=interpret,
        )(x2, g2, b2, m2, v2, dy2))
    dx = attributed("batchnorm_fused.bwd_dx", key, lambda:
        pl.pallas_call(
            functools.partial(_bwd_dx_kernel, R=R, eps=eps, act=act),
            grid=(C // TC, nr),
            in_specs=[blk_spec, stat_spec, stat_spec, stat_spec,
                      stat_spec, blk_spec, stat_spec, stat_spec],
            out_specs=blk_spec,
            out_shape=jax.ShapeDtypeStruct((R, C), x2.dtype),
            interpret=interpret,
        )(x2, g2, b2, m2, v2, dy2, db, dg))
    return dx, dg.reshape(C).astype(gamma.dtype), \
        db.reshape(C).astype(beta.dtype)


# ---------------------------------------------------------------------------
# custom_vjp dispatcher
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(x, gamma, beta, eps, act, interpret):
    if interpret or (_use_pallas(x) and _fwd_fits(
            x.reshape(-1, x.shape[-1]))):
        C = x.shape[-1]
        out2, mean, var = _pallas_forward(x.reshape(-1, C), gamma, beta,
                                          eps, act, interpret)
        return out2.reshape(x.shape), mean, var
    return batchnorm_reference(x, gamma, beta, eps, act)


def _fused_fwd(x, gamma, beta, eps, act, interpret):
    out, mean, var = _fused(x, gamma, beta, eps, act, interpret)
    return (out, mean, var), (x, gamma, beta, mean, var)


def _fused_bwd(eps, act, interpret, res, cts):
    x, gamma, beta, mean, var = res
    dy, gmean, gvar = cts
    C = x.shape[-1]
    x2 = x.reshape(-1, C)
    R = x2.shape[0]
    if interpret or (_use_pallas(x) and _bwd_fits(x2)):
        dx2, dgamma, dbeta = _pallas_backward(
            x2, gamma, beta, mean, var, dy.reshape(-1, C), eps, act,
            interpret)
        dx = dx2.reshape(x.shape)
    else:
        _, vjp = jax.vjp(
            lambda x_, g_, b_: batchnorm_reference(x_, g_, b_, eps,
                                                   act)[0], x, gamma,
            beta)
        dx, dgamma, dbeta = vjp(dy)
    # cotangents of the stat OUTPUTS (zero in every training loop — the
    # moving-stat update happens outside autograd — but a caller
    # differentiating through mean/var must still get the d mean/dx =
    # 1/R and d var/dx = 2(x-mean)/R terms)
    stat_ct = (gmean + 2.0 * (x2.astype(jnp.float32) - mean) * gvar) / R
    dx = dx + stat_ct.reshape(x.shape).astype(x.dtype)
    return dx, dgamma, dbeta


_fused.defvjp(_fused_fwd, _fused_bwd)


def engaged(x, axis):
    """Whether ops/nn.py:batch_norm should take the kernel for this
    training-mode call: enabled, channels-last, and either on TPU with
    a fitting plan or force-interpreted (``MXTPU_FUSED_BN=interpret``,
    the CPU test hook)."""
    if _setting() == "0" or x.ndim < 2 or axis != x.ndim - 1:
        return False
    if _force_interpret():
        return True
    R = 1
    for s in x.shape[:-1]:
        R *= int(s)
    fake = jax.ShapeDtypeStruct((R, x.shape[-1]), x.dtype)
    return _use_pallas(x) and _fwd_fits(fake) and _bwd_fits(fake)


def fused_batch_norm(x, gamma, beta, eps=1e-3, act=None,
                     interpret=False):
    """Training-mode BatchNorm over the trailing axis with fused stats,
    normalize, and optional activation (``act=None|'relu'``).

    x: (..., C) channels-last; gamma, beta: (C,). Returns
    ``(out, mean, var)`` with f32 (C,) batch stats — moving-average
    updates belong to the caller, matching ``ops/nn.py:batch_norm``.
    Falls back to ``batchnorm_reference`` (identical semantics) off-TPU
    or when the tiling does not fit VMEM; ``interpret=True`` runs the
    Pallas kernels in interpreter mode for CPU tests.
    """
    if x.ndim < 2 or gamma.shape != (x.shape[-1],) \
            or beta.shape != (x.shape[-1],):
        raise ValueError("fused_batch_norm: need (..., C) x and (C,) "
                         "gamma/beta, got %s / %s / %s"
                         % (x.shape, gamma.shape, beta.shape))
    if act not in (None, "relu"):
        raise ValueError("fused_batch_norm: act must be None or 'relu', "
                         "got %r" % (act,))
    interpret = bool(interpret) or _force_interpret()
    return _fused(x, gamma, beta, float(eps), act, interpret)
