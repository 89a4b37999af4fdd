"""Fused scale-bias-ReLU + 3x3 convolution as Pallas TPU kernels.

Why this kernel exists: XLA:TPU fuses elementwise producers into DOT
operand loads but NOT into convolutions (measured compiler-exact in
benchmark/fusion_probe.py: a conv consuming relu(x*s+b) moves 2.6x the
bytes of the equivalent dot). In a ResNet bottleneck the BN-apply+ReLU
chain between convs therefore materializes a full activation tensor to
HBM on the XLA path — and the step is HBM-bandwidth-bound (44 GB/step at
~880 GB/s, docs/ROADMAP.md "ResNet perf ceiling"). This kernel computes

    y = conv3x3(relu(x * s + b), W)        # stride 1, pad 1, NHWC

reading ``x`` (the raw previous conv output) straight from HBM and
applying the normalize/ReLU chain in VMEM, so the normalized activation
never exists in HBM in either direction:

- forward: NB images per grid cell (NB>1 for small feature maps so the
  MXU sees >=~400 rows); scale/bias/ReLU on the VPU in the compute
  dtype, then ONE dot_general over im2col patches built in VMEM —
  (NB*H*W, 9*Ci) against (9*Ci, Co) — so even Ci=64 layers present a
  576-deep contraction to the 128x128 MXU instead of nine thin dots.
- backward: two kernels in the same shape. d-input recomputes the ReLU
  mask from x and contracts shifted dy patches against the
  flipped-transposed weights ((NB*H*W, 9*Co) x (9*Co, Ci)); d-weight
  recomputes z = relu(x*s+b) in VMEM and accumulates the (9*Ci, Co)
  cotangent across the sequential batch grid in a VMEM-resident f32
  block (Co-tiled to fit). Per-channel ds/db partials accumulate the
  same way, so the only HBM traffic is one read of x and dy each per
  kernel.

Measured reality (v5e, b128, pipelined long-run): the explicit im2col
costs ~9x the activation bytes in VMEM copy traffic, which XLA's native
windowed conv avoids — so the fused kernel only BEATS the unfused
XLA chain on small feature maps where XLA's conv is least efficient
(7x7x512: 46 vs 37 TF/s effective; 56x56x64: 26 vs 47 — XLA wins).
The model-level fuse="auto" policy therefore applies the kernel to
deep stages only; see docs/ROADMAP.md for the full study.

The reference's closest analog is the cuDNN fused conv-bias-activation
path (ref: src/operator/nn/convolution.cu + fused op in
src/operator/fusion/fused_op.cu); the TPU-native design fuses the
*producer* side instead because that is the fusion XLA cannot do.

Used by the ``fuse=True|"auto"`` ResNet variants
(gluon/model_zoo/vision/resnet.py; "auto" = deep stages only, the
measured winning policy) and exposed functionally here.
Non-TPU backends (and any shape the kernel does not cover) fall back to
a jnp reference with identical semantics; ``interpret=True`` runs the
Pallas kernels in interpreter mode for CPU tests.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from ..base import getenv as _getenv

__all__ = ["fused_scale_relu_conv3x3", "fused_conv_reference"]


def _compute_dtype(x_dtype):
    """MXU input dtype: keep bf16 (full-rate), promote other halfs to
    f32-safe bf16, leave f32 alone."""
    d = jnp.dtype(x_dtype)
    if d == jnp.bfloat16 or d == jnp.float32:
        return d
    if d.itemsize <= 2:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def fused_conv_reference(x, s, b, w, relu=True):
    """jnp semantics of the fused op (fallback + autodiff + goldens).

    x: (N, H, W, Ci) — raw producer output (e.g. pre-BN conv out)
    s, b: (Ci,) f32 — folded BN scale/bias (s = gamma*rsqrt(var+eps))
    w: (3, 3, Ci, Co) HWIO
    """
    cdt = _compute_dtype(x.dtype)
    xc = x.astype(cdt)
    pre = xc * s.astype(cdt) + b.astype(cdt)
    z = jnp.maximum(pre, jnp.zeros((), cdt)) if relu else pre
    out = lax.conv_general_dilated(
        z, w.astype(z.dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _act(x, s, b, relu, cdt):
    """Scale-bias(-ReLU) in the compute dtype. For bf16 inputs the whole
    chain runs in bf16 — one fused VPU pass instead of three (cast-up,
    f32 math, cast-down), and the same precision class as the reference
    BN-apply which computes (x-mean)*inv*g+beta in x.dtype."""
    xc = x.astype(cdt)
    pre = xc * s.astype(cdt) + b.astype(cdt)
    return jnp.maximum(pre, jnp.zeros((), cdt)) if relu else pre


def _fill_patches(zp_scr, pat_scr, i, src, H, W, C, cdt):
    """im2col inside VMEM: zero-pad ``src`` into zp_scr, then write the 9
    shifted (H, W, C) views into pat_scr[i] channel-blocks -> (H, W, 9C),
    tap-major channel order matching w.reshape(9*Ci, Co). Explicit
    scratch stores — a 9-way jnp.concatenate of the same views hangs the
    Mosaic compiler (measured >300s vs 1.3s for this form)."""
    zp_scr[:] = jnp.zeros_like(zp_scr)
    zp_scr[1:H + 1, 1:W + 1, :] = src.astype(cdt)
    for ky in range(3):
        for kx in range(3):
            t = (ky * 3 + kx) * C
            pat_scr[i, :, :, t:t + C] = zp_scr[ky:ky + H, kx:kx + W, :]


def _fwd_kernel(x_ref, s_ref, b_ref, w_ref, o_ref, zp_scr, pat_scr, *,
                NB, H, W, relu, cdt):
    # grid is (co_tiles, n): the im2col patches are rebuilt per Co tile
    # (VPU cost) so the weight block (9Ci x TCo) fits VMEM at depth
    Ci = x_ref.shape[-1]
    for i in range(NB):
        z = _act(x_ref[i], s_ref[0], b_ref[0], relu, cdt)
        _fill_patches(zp_scr, pat_scr, i, z, H, W, Ci, cdt)
    acc = lax.dot_general(                           # (NB*H*W, TCo)
        pat_scr[:].reshape(NB * H * W, 9 * Ci), w_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[:] = acc.reshape(NB, H, W, w_ref.shape[-1]).astype(o_ref.dtype)


def _bwd_dx_kernel(x_ref, s_ref, b_ref, wt_ref, g_ref, dx_ref, ds_ref,
                   db_ref, gp_scr, pat_scr, *, NB, H, W, relu, cdt):
    # grid is (ci_tiles, n) with n innermost; all refs except g carry
    # only this cell's Ci tile, so deep layers' flipped-weight block
    # (9Co x Ci: 4.7 MB untiled at 512x512, double-buffered by Mosaic)
    # stays under the VMEM budget
    n = pl.program_id(1)
    Co = g_ref.shape[-1]
    Ci = x_ref.shape[-1]          # = this cell's Ci tile
    for i in range(NB):
        _fill_patches(gp_scr, pat_scr, i, g_ref[i], H, W, Co, cdt)
    dz = lax.dot_general(                            # (NB*H*W, TCi) f32
        pat_scr[:].reshape(NB * H * W, 9 * Co), wt_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(NB, H, W, Ci)
    s = s_ref[0]
    if relu:
        pre = _act(x_ref[:], s, b_ref[0], False, cdt)
        # compare in f32 — Mosaic has no bf16 vector cmpf
        dpre = dz * (pre.astype(jnp.float32) > 0.0)
    else:
        dpre = dz
    dx_ref[:] = (dpre * s).astype(dx_ref.dtype)

    @pl.when(n == 0)
    def _init():
        ds_ref[:] = jnp.zeros_like(ds_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    ds_ref[:] += jnp.sum(dpre * x_ref[:].astype(jnp.float32),
                         axis=(0, 1, 2))[None]
    db_ref[:] += jnp.sum(dpre, axis=(0, 1, 2))[None]


def _bwd_dx_tiles(N, H, W_, Ci, Co, cbytes):
    """(NB, TCi, fits) for the dx kernel under the ~11 MB VMEM working budget
    (flipped weights + patch scratch dominate; streamed blocks and the
    weight block are double-buffered by Mosaic)."""
    nb = _pick_nb(N, H, W_, Co, cbytes)

    def est(nb_, tci_):
        wt2 = 2 * 9 * Co * tci_ * cbytes
        pat = nb_ * H * W_ * 9 * Co * cbytes
        gp = (H + 2) * (W_ + 2) * Co * cbytes
        blocks = 2 * nb_ * H * W_ * (2 * tci_ + Co) * cbytes
        dz32 = nb_ * H * W_ * tci_ * 4
        return wt2 + pat + gp + blocks + dz32
    return _shrink(nb, Ci, est, _VMEM_BUDGET)


def _bwd_dw_kernel(x_ref, s_ref, b_ref, g_ref, dw_ref, zp_scr, pat_scr, *,
                   NB, H, W, relu, cdt):
    # grid is (co_tiles, n) with n innermost: the (9Ci, TCo) f32
    # accumulator block stays VMEM-resident across the whole batch sweep
    # of one Co tile. Tiling Co keeps deep layers (Ci=Co=512: a 9.4 MB
    # untiled accumulator, double-buffered by Mosaic) under the 16 MB
    # VMEM budget.
    n = pl.program_id(1)
    Ci = x_ref.shape[-1]
    for i in range(NB):
        z = _act(x_ref[i], s_ref[0], b_ref[0], relu, cdt)
        _fill_patches(zp_scr, pat_scr, i, z, H, W, Ci, cdt)
    # single contracting dim over the flattened spatial axis — Mosaic's
    # tpu.matmul rejects multi-dim contractions
    tap = lax.dot_general(                           # (9Ci, TCo) f32
        pat_scr[:].reshape(NB * H * W, 9 * Ci),
        g_ref[:].astype(cdt).reshape(NB * H * W, g_ref.shape[-1]),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(n == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:] += tap


# imported lazily at kernel-trace time on non-TPU hosts would be cleaner,
# but pallas imports are cheap and the module is part of jax
from jax.experimental import pallas as pl              # noqa: E402
from jax.experimental.pallas import tpu as pltpu       # noqa: E402


# Mosaic's scoped-VMEM accounting runs ~5-6 MB above the sum of block +
# scratch sizes (kernel temporaries, spills, extra buffering observed on
# v5e), so tile choices target this conservative working budget.
_VMEM_BUDGET = 7 * 1024 * 1024


def _pick_nb(N, H, W_, C, cbytes):
    """Images per grid cell: small feature maps (deep stages) batch
    several images so the im2col dot presents >=~400 rows to the MXU
    (7x7 alone is 49 sublane-padded rows); cap the patch buffer ~4 MB."""
    nb = 1
    for cand in (8, 4, 2):
        if (N % cand == 0 and H * W_ * cand <= 1024
                and cand * H * W_ * 9 * C * cbytes <= 4 * 1024 * 1024):
            nb = cand
            break
    return nb


def _shrink(nb, tile, est, budget, nb_first=False):
    """Shared tile-shrink policy: halve until est(nb, tile) fits the
    budget. Backward kernels halve the channel tile first (their
    weight/accumulator blocks dominate); the forward halves
    images-per-cell first (keeps the weight block whole and avoids
    rebuilding the im2col patches per Co tile)."""
    def shrink_tile():
        nonlocal tile
        while tile > 128 and tile % 2 == 0 and est(nb, tile) > budget:
            tile //= 2

    def shrink_nb():
        nonlocal nb
        while nb > 1 and est(nb, tile) > budget:
            nb //= 2

    if nb_first:
        shrink_nb()
        shrink_tile()
    else:
        shrink_tile()
        shrink_nb()
    # the floor is (nb=1, tile=128): past it the estimate can still
    # exceed the budget (huge feature maps with fuse forced on) — the
    # caller must fall back instead of dying at Mosaic compile time
    return nb, tile, est(nb, tile) <= budget


def _fwd_tiles(N, H, W_, Ci, Co, cbytes):
    """(NB, TCo, fits) for the forward kernel. The forward weight block is
    observed NOT to be double-buffered (stage-4 untiled compiles at
    ~10 MB), so it counts once. Unlike backward, NB shrinks FIRST:
    halving images-per-cell keeps the weight block whole and avoids
    rebuilding the im2col patches per Co tile."""
    nb = _pick_nb(N, H, W_, Ci, cbytes)

    def est(nb_, tco_):
        w2 = 9 * Ci * tco_ * cbytes
        pat = nb_ * H * W_ * 9 * Ci * cbytes
        zp = (H + 2) * (W_ + 2) * Ci * cbytes
        blocks = 2 * nb_ * H * W_ * (Ci + tco_) * cbytes
        acc32 = nb_ * H * W_ * tco_ * 4
        return w2 + pat + zp + blocks + acc32

    # forward budget is tighter than _VMEM_BUDGET would suggest at big
    # batch (b256 measured 408 KB over at 11 MB)
    return _shrink(nb, Co, est, 10 * 1024 * 1024, nb_first=True)


def _pallas_forward(x, s, b, w, relu, interpret):
    N, H, W_, Ci = x.shape
    Co = w.shape[-1]
    cdt = _compute_dtype(x.dtype)
    cbytes = jnp.dtype(cdt).itemsize
    NB, tco, _ = _fwd_tiles(N, H, W_, Ci, Co, cbytes)
    w2 = w.reshape(9 * Ci, Co).astype(cdt)
    s2 = s.astype(jnp.float32).reshape(1, Ci)
    b2 = b.astype(jnp.float32).reshape(1, Ci)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, NB=NB, H=H, W=W_, relu=relu,
                          cdt=cdt),
        grid=(Co // tco, N // NB),
        in_specs=[
            pl.BlockSpec((NB, H, W_, Ci), lambda c, n: (n, 0, 0, 0)),
            pl.BlockSpec((1, Ci), lambda c, n: (0, 0)),
            pl.BlockSpec((1, Ci), lambda c, n: (0, 0)),
            pl.BlockSpec((9 * Ci, tco), lambda c, n: (0, c)),
        ],
        out_specs=pl.BlockSpec((NB, H, W_, tco),
                               lambda c, n: (n, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, H, W_, Co), x.dtype),
        scratch_shapes=[pltpu.VMEM((H + 2, W_ + 2, Ci), cdt),
                        pltpu.VMEM((NB, H, W_, 9 * Ci), cdt)],
        interpret=interpret,
    )(x, s2, b2, w2)


def _bwd_dw_tiles(N, H, W_, Ci, Co, cbytes):
    """(NB, TCo, fits) for the d-weight kernel under _VMEM_BUDGET. The f32
    accumulator output block is double-buffered by Mosaic even though
    it is revisited (observed: 2x the block size on the VMEM stack), so
    it counts twice."""
    nb = _pick_nb(N, H, W_, Ci, cbytes)

    def est(nb_, tco_):
        return (nb_ * H * W_ * 9 * Ci * cbytes
                + (H + 2) * (W_ + 2) * Ci * cbytes
                + 2 * nb_ * H * W_ * (Ci + Co) * cbytes
                + 2 * 9 * Ci * tco_ * 4)

    return _shrink(nb, Co, est, _VMEM_BUDGET)


def _pallas_backward(x, s, b, w, relu, interpret, g):
    N, H, W_, Ci = x.shape
    Co = w.shape[-1]
    cdt = _compute_dtype(x.dtype)
    cbytes = jnp.dtype(cdt).itemsize
    s2 = s.astype(jnp.float32).reshape(1, Ci)
    b2 = b.astype(jnp.float32).reshape(1, Ci)
    # d-input: contract shifted dy patches with flipped-transposed taps
    NBx, tci, _ = _bwd_dx_tiles(N, H, W_, Ci, Co, cbytes)
    wt = w[::-1, ::-1].transpose(0, 1, 3, 2).reshape(9 * Co, Ci).astype(cdt)
    dx, ds, db = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, NB=NBx, H=H, W=W_, relu=relu,
                          cdt=cdt),
        grid=(Ci // tci, N // NBx),
        in_specs=[
            pl.BlockSpec((NBx, H, W_, tci), lambda c, n: (n, 0, 0, c)),
            pl.BlockSpec((1, tci), lambda c, n: (0, c)),
            pl.BlockSpec((1, tci), lambda c, n: (0, c)),
            pl.BlockSpec((9 * Co, tci), lambda c, n: (0, c)),
            pl.BlockSpec((NBx, H, W_, Co), lambda c, n: (n, 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((NBx, H, W_, tci), lambda c, n: (n, 0, 0, c)),
            pl.BlockSpec((1, tci), lambda c, n: (0, c)),
            pl.BlockSpec((1, tci), lambda c, n: (0, c)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N, H, W_, Ci), x.dtype),
            jax.ShapeDtypeStruct((1, Ci), jnp.float32),
            jax.ShapeDtypeStruct((1, Ci), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((H + 2, W_ + 2, Co), cdt),
                        pltpu.VMEM((NBx, H, W_, 9 * Co), cdt)],
        interpret=interpret,
    )(x, s2, b2, wt, g)
    # d-weight: accumulate (9Ci, TCo) across the sequential batch grid,
    # Co-tiled so the f32 accumulator + im2col scratch stay under VMEM.
    NBw, tco, _ = _bwd_dw_tiles(N, H, W_, Ci, Co, cbytes)
    w2 = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, NB=NBw, H=H, W=W_, relu=relu,
                          cdt=cdt),
        grid=(Co // tco, N // NBw),
        in_specs=[
            pl.BlockSpec((NBw, H, W_, Ci), lambda c, n: (n, 0, 0, 0)),
            pl.BlockSpec((1, Ci), lambda c, n: (0, 0)),
            pl.BlockSpec((1, Ci), lambda c, n: (0, 0)),
            pl.BlockSpec((NBw, H, W_, tco), lambda c, n: (n, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((9 * Ci, tco), lambda c, n: (0, c)),
        out_shape=jax.ShapeDtypeStruct((9 * Ci, Co), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H + 2, W_ + 2, Ci), cdt),
                        pltpu.VMEM((NBw, H, W_, 9 * Ci), cdt)],
        interpret=interpret,
    )(x, s2, b2, g)
    dw = w2.reshape(3, 3, Ci, Co).astype(w.dtype)
    return (dx, ds.reshape(Ci).astype(s.dtype),
            db.reshape(Ci).astype(b.dtype), dw)


def _use_pallas(x=None):
    if _getenv("MXTPU_NO_PALLAS", "0") == "1":
        return False
    # a CONCRETE array knows where it lives — eager ops on host-committed
    # arrays (default-ctx cpu NDArrays on a TPU-attached process) must
    # take the reference path even though the default platform is tpu
    if x is not None and isinstance(x, jax.Array) \
            and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform == "tpu"
    return jax.default_backend() == "tpu"


def _fwd_fits(x, w):
    """True when the forward kernel's shrunk (nb, tile) fits its VMEM
    budget. Reachable to FAIL with fuse=True/pallas_all forced on large
    feature maps; launching anyway would die at Mosaic compile time, so
    the dispatcher falls back to fused_conv_reference instead."""
    N, H, W_, Ci = x.shape
    Co = w.shape[-1]
    cbytes = jnp.dtype(_compute_dtype(x.dtype)).itemsize
    return _fwd_tiles(N, H, W_, Ci, Co, cbytes)[2]


def _bwd_fits(x, w):
    """Same gate for the two backward kernels (their budgets are
    tighter than the forward's, so they are checked separately — a
    forward-only workload keeps the fast kernel either way)."""
    N, H, W_, Ci = x.shape
    Co = w.shape[-1]
    cbytes = jnp.dtype(_compute_dtype(x.dtype)).itemsize
    return (_bwd_dx_tiles(N, H, W_, Ci, Co, cbytes)[2]
            and _bwd_dw_tiles(N, H, W_, Ci, Co, cbytes)[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused(x, s, b, w, relu, interpret):
    # forward gates on the FORWARD plan only: an inference-only call
    # must not lose the fast kernel because a backward plan (checked in
    # _fused_bwd) would not fit
    if interpret or (_use_pallas(x) and _fwd_fits(x, w)):
        return _pallas_forward(x, s, b, w, relu, interpret)
    return fused_conv_reference(x, s, b, w, relu)


def _fused_fwd(x, s, b, w, relu, interpret):
    return _fused(x, s, b, w, relu, interpret), (x, s, b, w)


def _fused_bwd(relu, interpret, res, g):
    x, s, b, w = res
    if interpret or (_use_pallas(x) and _bwd_fits(x, w)):
        return _pallas_backward(x, s, b, w, relu, interpret, g)
    _, vjp = jax.vjp(
        lambda x_, s_, b_, w_: fused_conv_reference(x_, s_, b_, w_, relu),
        x, s, b, w)
    return vjp(g)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_scale_relu_conv3x3(x, s, b, w, relu=True, interpret=False):
    """conv3x3(relu(x*s + b), w) with the normalize/ReLU chain fused into
    the conv's VMEM operand load (never materialized in HBM).

    x: (N, H, W, Ci) NHWC; s, b: (Ci,); w: (3, 3, Ci, Co) HWIO.
    Stride 1, SAME padding. Falls back to an identical-semantics jnp
    reference off-TPU. ``relu=False`` gives conv3x3(x*s + b, w).
    """
    if x.ndim != 4 or w.shape[:2] != (3, 3) or w.shape[2] != x.shape[-1]:
        raise ValueError("fused_scale_relu_conv3x3: need NHWC x and "
                         "(3,3,Ci,Co) w, got %s / %s"
                         % (x.shape, w.shape))
    return _fused(x, s, b, w, bool(relu), bool(interpret))
