"""Flash attention: tiled online-softmax attention as a Pallas TPU kernel.

The reference has no attention kernel at all (2019-era; its closest analog
is the fused cuDNN RNN, src/operator/rnn-inl.h). Long-context attention is
where a modern framework's FLOPs go, so this is the flagship custom
kernel: per (batch*head, q-block) grid cell, K/V stream through VMEM in
``block_k`` tiles while the m/l/o running softmax accumulates in
registers — HBM traffic is O(S·D) instead of the O(S^2) score matrix.

Composition with the parallelism layer: ring attention
(parallel/ring_attention.py) shards the sequence over the mesh and
rotates K/V via ppermute; each hop's local block product can use this
kernel, making the two-level scheme (inter-chip ring x intra-chip flash)
match Liu et al.'s blockwise formulation.

Backward is a pair of Pallas kernels in the flash-2 formulation: the
forward saves only the per-row logsumexp L = m + log(l) (O(S) extra);
the backward recomputes each (block_q, block_k) score tile inside the
kernel from Q/K/L, so dQ/dK/dV are produced with O(S*D) HBM traffic and
O(block^2) VMEM — the O(S^2) score matrix is never materialized in
either direction. On non-TPU backends (and when the kernel is bypassed)
the jnp reference's XLA vjp is used instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from ..base import getenv as _getenv

__all__ = ["flash_attention", "attention_reference"]

# Mosaic requires the minor block dim to be a multiple of 128 lanes, so
# per-row scalars (logsumexp, delta) are stored broadcast over 128 lanes.
_LANES = 128


def _fit_block(requested, size, quantum):
    """Largest block <= requested that divides `size` and is a multiple of
    `quantum` (Mosaic sublane/lane granularity). Falls back to `size`
    itself (one block spanning the axis) when no such divisor exists —
    but only while that still fits VMEM: for e.g. a prime seq length the
    whole-axis block would allocate a size^2 fp32 score tile and die in
    an opaque Mosaic compile error, so raise actionable padding guidance
    instead."""
    b = min(requested, size)
    if size % b == 0:
        return b
    b = (b // quantum) * quantum
    while b >= quantum:
        if size % b == 0:
            return b
        b -= quantum
    if size > 4 * max(requested, quantum):
        raise ValueError(
            "flash_attention: sequence length %d has no block divisor that "
            "is a multiple of %d; pad the sequence to a multiple of %d "
            "(e.g. with jnp.pad + masking) or pass a block size that "
            "divides it" % (size, quantum, quantum))
    return size


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain O(S^2) attention in jnp — fallback + autodiff path.
    q,k,v: [B, H, S, D]."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # scores + softmax in fp32 regardless of input dtype — same as the
    # Pallas kernel's accumulators, so the two paths agree under AMP bf16
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col > row, -jnp.inf, s)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype),
                      v).astype(q.dtype)


def _causal_dispatch(qi, ki, block_q, block_k, compute):
    """Run ``compute(masked)`` for one (q-block, k-block) causal cell:
    blocks strictly above the diagonal are skipped, diagonal-straddling
    blocks run masked, strictly-below blocks run unmasked. Shared by the
    forward and both backward kernels so the classification cannot
    drift."""
    import jax.experimental.pallas as pl

    below = ki * block_k + block_k - 1 <= qi * block_q

    @pl.when(jnp.logical_and(
        ki * block_k <= qi * block_q + block_q - 1,
        jnp.logical_not(below)))
    def _():
        compute(True)

    @pl.when(below)
    def _():
        compute(False)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, o_scr, *,
                block_q, block_k, causal, scale, n_kblocks):
    """One (batch*head, q-block, k-block) grid cell. The TPU grid runs
    sequentially with the k axis innermost, so VMEM scratch carries the
    m/l/o online-softmax state across k steps — only one (block_k, D)
    K/V tile is resident at a time, keeping VMEM O(block) instead of
    O(seq)."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        o_scr[:] = jnp.zeros_like(o_scr)

    def compute(masked):
        # dots run on the input dtype (bf16 hits the MXU at full rate;
        # f32 would be 8x slower) and accumulate in f32.
        # No isneginf guards: every q row's FIRST processed block (ki=0)
        # contains its valid col 0, so m stays finite from the first
        # step on, exp(-inf - finite) underflows to exactly 0 for both
        # masked scores and the m_prev=-inf init, and no exp(-inf+inf)
        # NaN can form. (Fully-masked rows cannot occur: causal row r
        # always sees cols 0..r.)
        q = q_ref[0]                                  # (block_q, D)
        k = k_ref[0]                                  # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
        if masked:
            row = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col > row, -jnp.inf, s)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = corr * l_scr[:, 0] + jnp.sum(p, axis=-1)
        o_scr[:] = corr[:, None] * o_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # most active blocks at long seq are strictly below the diagonal
        # and skip the per-element iota/compare/select VPU work
        _causal_dispatch(qi, ki, block_q, block_k, compute)
    else:
        compute(False)

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        # INVARIANT: no row is ever fully masked (causal row r sees cols
        # 0..r; non-causal sees everything; ring x flash skips
        # fully-masked hops before calling the kernel), so l > 0 and
        # lse is finite — the backward recompute relies on this.
        # Broadcast across a 128-lane minor dim — Mosaic requires the
        # last block dim to be a multiple of 128, so scalars-per-row
        # ride a full lane register.
        l = l_scr[:, 0]
        lse = m_scr[:, 0] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])
        o_ref[0] = (o_scr[:] / l[:, None]).astype(o_ref.dtype)


def _pallas_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = _fit_block(block_q, sq, 8)
    block_k = _fit_block(block_k, sk, 128)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    n_kblocks = sk // block_k
    kernel = functools.partial(_fwd_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale,
                               n_kblocks=n_kblocks)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),    # unnormalized output
        ],
        interpret=interpret,
        name="mx_flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qi, ki, block_q, block_k, masked, scale):
    """Shared flash-2 backward recompute: rebuild the (block_q, block_k)
    probability tile from Q/K and the saved row logsumexp, then
    dS = P * (dP - delta) * scale. Used by both _dq_kernel and
    _dkv_kernel so the masking/lse-safety logic cannot drift.
    ``masked`` is static: only diagonal-straddling blocks pay the iota
    mask; masked scores give p = exp(-inf - lse) = 0 exactly (causal
    rows always have a finite lse — see _fwd_kernel)."""
    q = q_ref[0]                                  # (block_q, D)
    k = k_ref[0]                                  # (block_k, D)
    v = v_ref[0]
    do = do_ref[0]                                # (block_q, D)
    lse = lse_ref[0][:, 0]                        # (block_q,)
    delta = delta_ref[0][:, 0]                    # (block_q,)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        row = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        col = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(col > row, -jnp.inf, s)
    p = jnp.exp(s - lse[:, None])                 # (block_q, block_k)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (block_q, block_k)
    ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, block_q, block_k, causal, scale, n_kblocks):
    """dQ for one (batch*head, q-block) cell; k innermost.
    dQ += dS @ K."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(masked):
        _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, qi, ki, block_q, block_k,
                                masked, scale)
        dq_scr[:] += jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        _causal_dispatch(qi, ki, block_q, block_k, compute)
    else:
        compute(False)

    @pl.when(ki == n_kblocks - 1)
    def _write():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q, block_k,
                causal, scale, n_qblocks):
    """dK/dV for one (batch*head, k-block) cell; q innermost.
    dV += P^T @ dO; dK += dS^T @ Q."""
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(masked):
        do = do_ref[0]
        p, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, qi, ki, block_q, block_k,
                                masked, scale)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (block_k, D)
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (block_k, D)

    if causal:
        _causal_dispatch(qi, ki, block_q, block_k, compute)
    else:
        compute(False)

    @pl.when(qi == n_qblocks - 1)
    def _write():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_backward(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                     interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = _fit_block(block_q, sq, 8)
    block_k = _fit_block(block_k, sk, 128)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = g.reshape(b * h, sq, d)
    # the O(S) per-row residual/correction vectors ride a 128-lane minor
    # dim only here, transiently, for the Mosaic block constraint — the
    # saved residual itself is (bh, sq)
    lse = jnp.broadcast_to(lse[:, :, None], (b * h, sq, _LANES))
    # delta_i = sum_d dO_i * O_i — the rowwise correction in dS; O(S*D)
    delta = jnp.broadcast_to(
        jnp.sum(dof.astype(jnp.float32)
                * o.reshape(b * h, sq, d).astype(jnp.float32),
                axis=-1, keepdims=True), (b * h, sq, _LANES))
    n_qblocks = sq // block_q
    n_kblocks = sk // block_k

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, n_kblocks=n_kblocks),
        grid=(b * h, n_qblocks, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="mx_flash_dq",
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, n_qblocks=n_qblocks),
        grid=(b * h, n_kblocks, n_qblocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, j, i: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_dkv",
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _use_pallas():
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    if interpret or _use_pallas():
        return _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret)[0]
    return attention_reference(q, k, v, causal=causal, scale=scale)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if interpret or _use_pallas():
        out, lse = _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                                   interpret)
        # keep one lane of the (bh, sq, 128) kernel output — the lane dim
        # exists only for Mosaic's block constraint, not worth 128x HBM
        # across the fwd->bwd interval
        return out, (q, k, v, out, lse[:, :, 0])
    out = attention_reference(q, k, v, causal=causal, scale=scale)
    return out, (q, k, v, None, None)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=causal,
                                                   scale=scale), q, k, v)
        return vjp(g)
    return _pallas_backward(q, k, v, o, lse, g, causal, scale, block_q,
                            block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Measured block optima, one v5e chip, causal fwd+bwd (round-3 scans).
# Isolated-kernel winners and in-context (full remat train step) winners
# DIFFER: at seq 2048 the isolated scan prefers (512,512) by 20%, but
# inside the remat'd transformer step (1024,1024) is 2% faster end to
# end — VMEM pressure and recompute scheduling shift the optimum. The
# table holds in-context winners; MXTPU_FLASH_AUTOTUNE=1 searches the
# exact shape (isolated — verify winners in context before pinning).
_BLOCK_TABLE = {
    2048: (1024, 1024),
    4096: (1024, 1024),
    8192: (1024, 1024),
}
_TUNE_CANDIDATES = [(512, 512), (512, 1024), (1024, 512), (1024, 1024),
                    (2048, 512), (256, 512)]
_TUNE_CACHE = {}  # mxlint: disable=MX003 (GIL-atomic memo of measured block sizes; a racing duplicate tune costs time, never correctness)


def _default_blocks(seq):
    if seq in _BLOCK_TABLE:
        return _BLOCK_TABLE[seq]
    if seq <= 2048:
        return (512, 512)
    if seq <= 4096:
        return (1024, 1024)
    return (2048, 512)


def _autotune_blocks(q, k, v, causal, scale):
    """Measure every candidate on the attached device for this exact
    shape and cache the winner (enabled by MXTPU_FLASH_AUTOTUNE=1 —
    the analog of the reference's cuDNN algo search,
    ref: src/operator/nn/cudnn/cudnn_algoreg-inl.h)."""
    import time
    key = (q.shape, causal)
    if key in _TUNE_CACHE:
        return _TUNE_CACHE[key]
    best, best_dt = None, float("inf")
    for bq, bk in _TUNE_CANDIDATES:
        if bq > q.shape[2] or bk > k.shape[2]:
            continue
        def loss(q_, k_, v_, bq=bq, bk=bk):
            o = _flash(q_, k_, v_, causal, float(scale), bq, bk, False)
            return jnp.sum(o.astype(jnp.float32))
        # grad over ALL inputs so the dk/dv backward kernel is part
        # of what gets timed (grad on q alone would let XLA DCE it)
        grad = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit  # mxlint: disable=MX005,MX022 (tuning micro-bench: compiled once per candidate block size inside the memoized autotune pass, timed by the autotuner itself)
        def many(q_, k_, v_):
            # chained fori so the device actually serializes the
            # iterations (async dispatch would lie to the timer)
            def body(i, qkv):
                qq, kk, vv = qkv
                dq, dk, dv = grad(qq, kk, vv)
                return (qq + 1e-12 * dq, kk + 1e-12 * dk,
                        vv + 1e-12 * dv)
            return lax.fori_loop(0, 5, body, (q_, k_, v_))[0]

        # a candidate the compiler refuses raises: the candidate list
        # is ours, so a refusal is a wrong list, not a slow block size
        warm = many(q, k, v)  # compile
        # allocation-ledger choke point (ISSUE 13a): the autotune
        # trial buffers are the 'workspace' tag — the transient HBM
        # spike a tuning pass costs shows up attributed, not as
        # anonymous growth
        from .. import storage as _storage
        _storage.ledger_register(warm, "workspace",
                                 site="flash.autotune")
        float(jnp.sum(warm.astype(jnp.float32)))
        # mxlint: disable=MX014 (host-side autotune timing: the measured winner is memoized per shape and MXTPU_FLASH_AUTOTUNE is a signature token, so timing noise never changes an already-cached executable)
        t0 = time.perf_counter()
        float(jnp.sum(many(q, k, v).astype(jnp.float32)))
        # mxlint: disable=MX014 (host-side autotune timing, see t0 above)
        dt = time.perf_counter() - t0
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best is None:
        # every candidate is larger than this sequence: nothing to time
        return _default_blocks(q.shape[2])
    _TUNE_CACHE[key] = best
    return best


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False):
    """Tiled attention. q,k,v: [B, H, S, D]. On TPU runs the Pallas
    kernel; elsewhere the jnp reference (or the kernel under
    ``interpret=True`` for testing). block_q/block_k default to the
    measured per-shape optimum (table above; exact-shape search with
    MXTPU_FLASH_AUTOTUNE=1); explicit values override. Blocks clamp to
    the sequence length."""
    import os
    if causal and q.shape[2] != k.shape[2]:
        # This kernel's causal mask is LEFT-aligned (col > row masked),
        # which is only the right semantics when q and kv index the
        # same positions. Decode-style calls (q_len=1 against an
        # N-entry KV cache) need RIGHT-aligned masking and would get
        # silently wrong attention here — reject loudly instead.
        # (A fully-masked row, the other classic hazard, cannot occur
        # under left alignment: row r always sees col 0.) Ring /
        # sequence-parallel callers handle per-hop offsets themselves
        # before calling in (parallel/ring_flash).
        raise ValueError(
            "flash_attention(causal=True) requires equal q/kv lengths "
            "(got %d vs %d): the causal mask is left-aligned, so "
            "decode-style q-against-longer-kv calls would be silently "
            "mis-masked; use attention_reference or slice the cache"
            % (q.shape[2], k.shape[2]))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if block_q is None or block_k is None:
        # autotune needs CONCRETE arrays (it executes candidates); under
        # jit tracing fall back to the table — tune eagerly once with
        # the training shapes, then the cached winner applies
        concrete = not isinstance(q, jax.core.Tracer)
        key = (q.shape, causal)
        if key in _TUNE_CACHE:
            dq, dk = _TUNE_CACHE[key]
        elif _getenv("MXTPU_FLASH_AUTOTUNE") == "1" \
                and concrete and jax.devices()[0].platform == "tpu":
            dq, dk = _autotune_blocks(q, k, v, causal, float(scale))
        else:
            dq, dk = _default_blocks(q.shape[2])
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    return _flash(q, k, v, causal, float(scale), int(block_q), int(block_k),
                  bool(interpret))
