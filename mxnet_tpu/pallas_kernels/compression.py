"""2-bit gradient compression kernels.

Semantics match the reference exactly (ref:
src/kvstore/gradient_compression-inl.h:40 quantize_2bit struct): each
value becomes 2 bits — ``11`` if ``residual + grad >= threshold`` (decodes
to +threshold), ``10`` if ``<= -threshold`` (decodes to -threshold), else
``00`` (decodes to 0) — with error-feedback residual accumulation. 16
values pack into one 32-bit word.

Layout note: the reference packs value i of a 16-group into byte ``i>>2``
bit-pair ``i&3`` of a float32 reinterpreted as chars; here the container
is an int32 with value i at bit-pair ``15-i`` (big-endian-in-word). The
wire format is internally consistent between quantize/dequantize and 4x
denser than fp32 either way — DCN-bound pushes ship 1/16 the bytes.

The Pallas version tiles words over a (rows, 128) lane layout so the
pack/unpack shift-or runs fully on the VPU; the jnp fallback is identical
math and serves CPU + autodiff-free paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["quantize_2bit", "dequantize_2bit", "quantize_2bit_jnp",
           "dequantize_2bit_jnp"]

_GROUP = 16  # values per 32-bit word


def _pad_to(x, multiple):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,), x.dtype)])
    return x


def quantize_2bit_jnp(grad, residual, threshold=0.5):
    """Returns (compressed int32 [ceil(n/16)], new_residual [n])."""
    n = grad.shape[0]
    r = residual + grad
    pos = r >= threshold
    neg = r <= -threshold
    codes = jnp.where(pos, 3, jnp.where(neg, 2, 0)).astype(jnp.int32)
    new_residual = r - pos * threshold + neg * threshold
    codes = _pad_to(codes, _GROUP).reshape(-1, _GROUP)
    shifts = 2 * (15 - jnp.arange(_GROUP, dtype=jnp.int32))
    # bit-pairs are disjoint, so sum == bitwise-or
    words = jnp.sum(codes << shifts[None, :], axis=1, dtype=jnp.int32)
    return words, new_residual[:n]


def dequantize_2bit_jnp(words, n, threshold=0.5):
    """Inverse of quantize_2bit_jnp: int32 words -> float32 [n]."""
    shifts = 2 * (15 - jnp.arange(_GROUP, dtype=jnp.int32))
    codes = (words[:, None] >> shifts[None, :]) & 3
    vals = jnp.where(codes == 3, threshold,
                     jnp.where(codes == 2, -threshold, 0.0))
    return vals.reshape(-1)[:n].astype(jnp.float32)


# -- Pallas versions --------------------------------------------------------

_LANES = 128


def _quant_kernel(r_ref, words_ref, newr_ref, *, threshold):
    # r_ref: (16, W) — row i holds bit-pair 15-i's values for each word
    r = r_ref[:]
    pos = r >= threshold
    neg = r <= -threshold
    codes = jnp.where(pos, 3, jnp.where(neg, 2, 0)).astype(jnp.int32)
    newr_ref[:] = r - pos.astype(r.dtype) * threshold \
        + neg.astype(r.dtype) * threshold
    shifts = 2 * (15 - jax.lax.broadcasted_iota(jnp.int32, codes.shape, 0))
    words_ref[:] = jnp.sum(codes << shifts, axis=0, keepdims=True)


def _dequant_kernel(words_ref, out_ref, *, threshold):
    words = words_ref[:]                       # (1, W)
    shifts = 2 * (15 - jax.lax.broadcasted_iota(
        jnp.int32, (_GROUP,) + words.shape[1:], 0))
    codes = (words >> shifts) & 3              # (16, W)
    out_ref[:] = jnp.where(
        codes == 3, jnp.float32(threshold),
        jnp.where(codes == 2, jnp.float32(-threshold), jnp.float32(0.0)))


def _pallas_ok():
    return jax.default_backend() == "tpu"


def quantize_2bit(grad, residual, threshold=0.5, interpret=False):
    """2-bit quantize with error feedback. grad/residual: float32 [n].
    Pallas on TPU, jnp elsewhere. Both produce identical words."""
    if not (interpret or _pallas_ok()):
        return quantize_2bit_jnp(grad, residual, threshold)
    import jax.experimental.pallas as pl

    n = grad.shape[0]
    r = _pad_to(residual + grad, _GROUP * _LANES)
    nwords = r.shape[0] // _GROUP
    # word w value i lives at flat index w*16+i → (nwords, 16) → T (16, W)
    r2 = r.reshape(nwords, _GROUP).T
    words, newr = pl.pallas_call(
        functools.partial(_quant_kernel, threshold=float(threshold)),
        grid=(nwords // _LANES,),
        in_specs=[pl.BlockSpec((_GROUP, _LANES), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, i)),
                   pl.BlockSpec((_GROUP, _LANES), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, nwords), jnp.int32),
                   jax.ShapeDtypeStruct((_GROUP, nwords), jnp.float32)],
        interpret=interpret,
    )(r2)
    # trim lane padding: the wire format is ceil(n/16) words, identical to
    # the jnp path
    out_words = (n + _GROUP - 1) // _GROUP
    return words.reshape(-1)[:out_words], newr.T.reshape(-1)[:n]


def dequantize_2bit(words, n, threshold=0.5, interpret=False):
    if not (interpret or _pallas_ok()):
        return dequantize_2bit_jnp(words, n, threshold)
    import jax.experimental.pallas as pl

    nwords = words.shape[0]
    pad = (-nwords) % _LANES
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), words.dtype)])
    total = words.shape[0]
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, threshold=float(threshold)),
        grid=(total // _LANES,),
        in_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, i))],
        out_specs=pl.BlockSpec((_GROUP, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((_GROUP, total), jnp.float32),
        interpret=interpret,
    )(words.reshape(1, total))
    return out.T.reshape(-1)[:n]
