"""Flash attention: tiled online-softmax attention as Pallas TPU kernels.

The reference has no attention kernel at all (2019-era; its closest analog
is the fused cuDNN RNN, src/operator/rnn-inl.h). Long-context attention is
where a modern framework's FLOPs go, so this is the flagship custom
kernel. The score matrix is cut into (block_q, block_k) tiles; a grid step
is one tile, K/V stream through VMEM one ``block_k`` tile at a time, and
the m/l/o running softmax lives in VMEM scratch across a q-block's steps
(m and l lane-replicated, so no 1-D vector and no relayout appears in the
loop) — HBM traffic is O(S·D) instead of the O(S^2) score matrix. Only
tiles the causal mask leaves something of are grid steps (``_steps``), and
inside a tile the work goes in chunks of rows, each stopping at the last
column its rows can see, so a diagonal tile costs about 5/8 of a full one
and only its last sub-block pays for the mask. K and V may have fewer heads
than Q (grouped-query attention: a group's query heads read one key-value
head from its own rows, and dk/dv are summed over the group inside the
kv-major kernel), and a sliding ``window`` drops the tiles wholly outside
it from the grid the same way and masks the chunks on its far edge.

Composition with the parallelism layer: ring attention
(parallel/ring_attention.py) shards the sequence over the mesh and
rotates K/V via ppermute; each hop's local block product can use this
kernel, making the two-level scheme (inter-chip ring x intra-chip flash)
match Liu et al.'s blockwise formulation.

Backward is a pair of Pallas kernels in the flash-2 formulation: the
forward saves only the per-row logsumexp L = m + log(l) (O(S) extra);
the backward recomputes each score tile inside the kernel from Q/K/L, so
dQ/dK/dV are produced with O(S*D) HBM traffic and O(block^2) VMEM — the
O(S^2) score matrix is never materialized in either direction. dQ works
q-major like the forward; dK/dV kv-major (S^T = K @ Q^T), so neither
contracts an operand over its major dim. Each of the three kernels has a
tile shape of its own (``_default_blocks``). On non-TPU backends (and when
the kernel is bypassed) the jnp reference's XLA vjp is used instead.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import profiler as _profiler

__all__ = ["flash_attention", "attention_reference"]

# Mosaic requires the minor block dim to be a multiple of 128 lanes:
# per-row statistics are lane-replicated (rows, 128) in VMEM and
# (1, rows) rows in HBM.
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


def attention_reference(q, k, v, causal=False, scale=None, window=None):
    """Plain O(S^2) attention in jnp — fallback + autodiff path.
    q: [B, H, S, D]; k, v: [B, G, S, D] with H a multiple of G (query head
    h reads key-value head h // (H/G)). ``window``: a query also sees only
    the last ``window`` keys, itself among them (needs ``causal``)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    # scores + softmax in fp32 regardless of input dtype — same as the
    # Pallas kernel's accumulators, so the two paths agree under AMP bf16
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col > row, -jnp.inf, s)
        if window is not None:
            s = jnp.where(col <= row - window, -jnp.inf, s)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype),
                      v).astype(q.dtype)


# Scores and statistics live in the log2 domain inside the kernels:
# s2 = (q . k) * scale * log2(e), p = 2^(s2 - m2). The scale and the
# exp's own log2(e) are one f32 multiply per score instead of two, and
# exp2 is the EUP's native op. lse leaves the kernel in natural log.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453
_NT = (((1,), (1,)), ((), ()))      # a @ b^T: both contract their minor dim
_NN = (((1,), (0,)), ((), ()))      # a @ b
# A causal tile's place relative to the diagonal is the static integer
# rel = (its first q position) - (its first k position); the kernels
# emit one specialised body per value a straddling tile can take. More
# values than this (blocks with a small common divisor, only tiny test
# shapes) take one body with a dynamic rel instead.
_MAX_DIAG_BODIES = 8
# Rows of the tile one softmax update works on (q rows in forward and
# dq, k rows in dk/dv). A (1024, 1024) f32 score tile is 1024 vector
# registers of a 64-register file; in chunks the scheduler keeps each
# chunk's statistics and accumulator rows in registers, and a chunk of
# a diagonal tile stops at the last column (row) its rows can see.
_CHUNK = 256
# Chunks of a tile off the diagonal run in a loop, this many to a loop
# body: within a body the scheduler overlaps one chunk's matmuls with the
# next one's softmax. All of them unrolled is 1.8% (forward), 2.0% (dq)
# and 1.8% (dk/dv) faster at 16k than these and 44 more bodies to trace
# and lower in every process (PERF.md, PR 27).
_GROUP = {"fwd": 4, "dq": 2, "dkv": 2}


def _chunk(block):
    """Largest multiple of 8 that divides ``block`` and is at most
    ``_CHUNK``; the block itself where none exists."""
    c = min(block, _CHUNK)
    c -= c % 8
    while c >= 8 and block % c:
        c -= 8
    return c if c >= 8 else block


# The chunk bodies below are written with lax primitives, not jnp
# operators: a kernel is traced and lowered again in every process (the
# persistent cache keys on the lowered module), a diagonal tile unrolls
# into a dozen bodies, and a jnp operator costs four times a lax bind to
# trace. That time is set-up.
def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes(x, n):
    """Per-row statistics kept lane-replicated as (rows, 128) -> (rows, n)
    without ever forming a 1-D vector (which Mosaic lays out along lanes:
    a sublane-to-lane relayout per use)."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return lax.concatenate([x] * (n // _LANES), 1)
    if n < _LANES:
        return lax.slice_in_dim(x, 0, n, axis=1)
    return lax.broadcast_in_dim(lax.slice_in_dim(x, 0, 1, axis=1),
                                (x.shape[0], n), (0, 1))


def _row_stat(reduce, x):
    """Row maximum or sum of a score chunk, lane-replicated (rows, 128)."""
    return lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], _LANES), (0,))


def _row(x):
    """(n, 128) lane-replicated -> (1, n): how per-row statistics cross
    HBM (n floats a block instead of 128 n). One XLU transpose a q-block,
    outside the k loop."""
    return jnp.transpose(x)[:1]


def _replicated(row):
    """(1, n) -> (n, 128) lane-replicated; the inverse of ``_row``."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _for_chunks(rel, block, chunk, group, body):
    """``body(lo)`` for each chunk of rows [lo, lo + chunk) of a tile. A
    tile on the diagonal (static ``rel``) is unrolled, so that each chunk
    gets its own visible range; every other tile is a loop over groups of
    ``group`` chunks, whose bodies the scheduler may overlap: the kernels
    are traced and lowered in every process, and that time is set-up."""
    if isinstance(rel, int):
        for lo in range(0, block, chunk):
            body(lo)
        return
    import jax.experimental.pallas as pl
    group = min(group, block // chunk)
    while (block // chunk) % group:
        group -= 1

    def step(i, carry):
        lo = pl.multiple_of(i * (group * chunk), group * chunk)
        for g in range(group):
            body(lo + g * chunk)
        return carry

    lax.fori_loop(0, block // (group * chunk), step, 0)


def _floor_to(x, q):
    return (x // q) * q


def _span(rel, lo, n, width, kv_major, window=None):
    """Which part of the other axis one chunk of a masked tile computes:
    (start, full_start, full_end, end) in lane tiles (the whole width where
    it has none). Positions [start, end) are computed; [start, full_start)
    and [full_end, end) are seen by some of the chunk's rows only and are
    masked, what lies between by all of them.

    q-major (forward, dq): the chunk is q rows [lo, lo+n) of the tile and
    the positions are k columns; the causal mask cuts the high side and the
    window the low one. kv-major (dk/dv): the chunk is k rows, the positions
    q columns, the causal mask on the low side and the window on the high.
    ``rel`` None means no mask at all; a traced ``rel`` masks the whole
    width, on both sides if there is a window."""
    quantum = _LANES if width % _LANES == 0 else width
    if rel is None:
        return 0, 0, width, width
    if not isinstance(rel, int):
        # traced: the causal side masks the whole width, and so does the
        # window's where there is one
        low = kv_major or window is not None
        high = not kv_major or window is not None
        return 0, width if low else 0, 0 if high else width, width
    clamp = lambda x: min(max(x, 0), width)  # noqa: E731
    if not kv_major:
        # column j is visible to row i iff rel - window < j - i <= rel
        full_end = _floor_to(rel + lo + 1, quantum)
        end = -_floor_to(-(rel + lo + n), quantum)
        start = full_start = 0
        if window is not None:
            start = _floor_to(rel + lo - window + 1, quantum)
            full_start = -_floor_to(-(rel + lo + n - window), quantum)
        return clamp(start), clamp(full_start), clamp(full_end), clamp(end)
    # q column i is visible to k row j iff j - rel <= i < j - rel + window
    start = _floor_to(lo - rel, quantum)
    full_start = -_floor_to(-(lo + n - 1 - rel), quantum)
    full_end = end = width
    if window is not None:
        full_end = _floor_to(lo - rel + window, quantum)
        end = -_floor_to(-(lo + n - 1 - rel + window), quantum)
    return clamp(start), clamp(full_start), clamp(full_end), clamp(end)


def _mask(s, lo, hi, d, k_axis, out=lax.gt):
    """``s`` with -inf where (k index) - (q index) > d (the causal mask;
    with ``out=lax.lt`` where it is < d, the window's far edge), for
    columns [lo, hi) only: the rest of a chunk's columns its rows see
    whole. The k index runs along ``k_axis`` of ``s`` and d counts from
    column lo."""
    if lo >= hi:
        return s
    part = lax.slice_in_dim(s, lo, hi, axis=1)
    kk = lax.broadcasted_iota(jnp.int32, part.shape, k_axis)
    qq = lax.broadcasted_iota(jnp.int32, part.shape, 1 - k_axis)
    part = lax.select(out(lax.sub(kk, qq), lax.full_like(kk, d)),
                      lax.full_like(part, -jnp.inf), part)
    pieces = [lax.slice_in_dim(s, 0, lo, axis=1), part,
              lax.slice_in_dim(s, hi, s.shape[1], axis=1)]
    pieces = [x for x in pieces if x.shape[1]]
    return pieces[0] if len(pieces) == 1 else lax.concatenate(pieces, 1)


def _diag_rels(block_q, block_k):
    """The values rel takes on tiles the diagonal crosses: tiles wholly
    above it have rel <= -block_q, tiles wholly below rel >= block_k - 1."""
    g = math.gcd(block_q, block_k)
    return list(range(-block_q + g, block_k - 1, g))


def _edge_rels(block_q, block_k, window):
    """The values rel takes on tiles the window's far edge crosses: tiles
    with rel <= window - block_q lie wholly inside it, tiles with
    rel >= window + block_k - 1 wholly outside (no grid steps)."""
    g = math.gcd(block_q, block_k)
    first = (window - block_q) // g * g + g
    return list(range(first, window + block_k - 1, g))


def _masked_rels(block_q, block_k, window):
    """Every rel whose tile needs a mask, or None where they are too many
    for a body each."""
    rels = _diag_rels(block_q, block_k)
    if window is not None:
        rels = sorted(set(rels) | set(_edge_rels(block_q, block_k, window)))
    return rels if len(rels) <= _MAX_DIAG_BODIES else None


def _causal_bodies(rel, block_q, block_k, body, window=None):
    """Run ``body`` for one causal tile of ``_steps`` (none lies above
    the diagonal or wholly outside the window): ``body(None)``, no mask,
    wholly below the diagonal and inside the window, and on either edge
    ``body`` with the tile's static rel, so each chunk's visible range is
    a constant. Shared by the three kernels so the classification cannot
    drift."""
    import jax.experimental.pallas as pl

    if window is not None:
        inside = (rel >= block_k - 1) & (rel <= window - block_q)
        rels = _masked_rels(block_q, block_k, window)
        if any(block_k - 1 <= r <= window - block_q
               for r in range(0, window, math.gcd(block_q, block_k))):
            pl.when(inside)(lambda: body(None))
        if rels is None:
            pl.when(jnp.logical_not(inside))(lambda: body(rel))
            return
        for r in rels:
            pl.when(rel == r)(functools.partial(body, r))
        return

    @pl.when(rel >= block_k - 1)
    def _():
        body(None)

    rels = _diag_rels(block_q, block_k)
    if len(rels) > _MAX_DIAG_BODIES:
        @pl.when(rel < block_k - 1)
        def _():
            body(rel)
        return
    for r in rels:
        @pl.when(rel == r)
        def _(r=r):
            body(r)


def _fwd_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
                l_scr, o_scr, *, block_q, block_k, chunk, causal, scale2,
                n_kblocks, window=None):
    """One (batch*head, step) grid cell; a step is one (q-block, k-block)
    tile of ``_steps``. The TPU grid runs sequentially with a q-block's
    k-blocks in a row, so VMEM scratch carries the m/l/o online-softmax
    state across them — only one (block_k, D) K/V tile is resident at a
    time, keeping VMEM O(block) instead of O(seq). m and l are
    (block_q, 128), every lane of a row the same."""
    import jax.experimental.pallas as pl

    qi = qi_ref[pl.program_id(1)]
    ki = ki_ref[pl.program_id(1)]
    d = o_scr.shape[-1]

    @pl.when(ki == _first_kblock(qi, block_q, block_k, window))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        o_scr[:] = jnp.zeros_like(o_scr)

    def tile(rel):
        # dots run on the input dtype (bf16 hits the MXU at full rate;
        # f32 would be 8x slower) and accumulate in f32.
        # No isneginf guards: every q row's FIRST processed block (ki=0)
        # starts at its valid col 0, so m is finite from the first step
        # on, exp2(-inf - finite) is exactly 0 for masked scores and for
        # the m_prev=-inf init, and no exp2(-inf+inf) NaN can form.
        # (Fully-masked rows cannot occur: causal row r sees cols 0..r.)
        # With a window a q-block's first tiles may hold nothing yet for
        # its later rows: there the maximum stays -inf and is replaced by
        # 0 in the exponents, so corr and p come out 0 and not NaN.
        def rows_at(lo):
            start, full_start, full_end, end = _span(
                rel, lo, chunk, block_k, False, window)
            if end <= start:
                return
            rows = pl.ds(lo, chunk)
            s = lax.mul(_dot(q_ref[0, rows], k_ref[0, start:end], _NT),
                        scale2)
            if full_end < end:
                s = _mask(s, full_end - start, end - start,
                          rel + lo - full_end, 1)
            if start < full_start:
                s = _mask(s, 0, full_start - start,
                          rel + lo - start - window + 1, 1, lax.lt)
            m_prev = m_scr[rows]
            m_new = lax.max(m_prev, _row_stat(lax.reduce_max, s))
            m_ref = m_new
            if start < full_start:
                m_ref = lax.select(
                    lax.eq(m_new, lax.full_like(m_new, -jnp.inf)),
                    lax.full_like(m_new, 0.0), m_new)
            corr = lax.exp2(lax.sub(m_prev, m_ref))
            p = lax.exp2(lax.sub(s, _lanes(m_ref, end - start)))
            v = v_ref[0, start:end]
            m_scr[rows] = m_new
            l_scr[rows] = lax.add(lax.mul(corr, l_scr[rows]),
                                  _row_stat(lax.reduce_sum, p))
            o_scr[rows] = lax.add(
                lax.mul(_lanes(corr, d), o_scr[rows]),
                _dot(lax.convert_element_type(p, v.dtype), v, _NN))

        _for_chunks(rel, block_q, chunk, _GROUP["fwd"], rows_at)

    if causal:
        _causal_bodies(qi * block_q - ki * block_k, block_q, block_k, tile,
                       window)
    else:
        tile(None)

    @pl.when(ki == _last_kblock(qi, block_q, block_k, n_kblocks, causal))
    def _finalize():
        # INVARIANT: no row is ever fully masked (causal row r sees cols
        # 0..r; non-causal sees everything; ring x flash skips
        # fully-masked hops before calling the kernel), so l > 0 and
        # lse is finite — the backward recompute relies on this.
        l = l_scr[:]
        lse_ref[0] = _row((m_scr[:] + jnp.log2(l)) * _LN2)
        o_ref[0] = (o_scr[:] / _lanes(l, d)).astype(o_ref.dtype)


def _steps(n_qblocks, n_kblocks, block_q, block_k, causal, kv_major,
           window=None, group=1):
    """The (q-block, k-block) pairs that compute, in grid order (k
    innermost; q innermost for dk/dv), as two int32 tables the kernels
    and their index maps read from SMEM. Tiles wholly above the diagonal
    or wholly outside the window are not grid steps at all: no step
    overhead, no DMA. ``group`` (dk/dv with grouped query heads): a
    k-block's steps go through its q-blocks once for each of the group's
    query heads, and the q table holds head * n_qblocks + q-block: the
    block's index in the group's heads laid end to end."""
    pairs = [(i, j) for i in range(n_qblocks) for j in range(n_kblocks)
             if not causal or j * block_k <= i * block_q + block_q - 1
             and (window is None
                  or i * block_q - j * block_k < window + block_k - 1)]
    if kv_major:
        pairs.sort(key=lambda p: (p[1], p[0]))
    if group > 1:
        pairs = [(h * n_qblocks + i, j) for j in range(n_kblocks)
                 for h in range(group) for i, j2 in pairs if j2 == j]
    # numpy, not jnp: a jnp array made while tracing is an eager device
    # computation, a program of its own to compile or fetch from the cache
    qi, ki = np.asarray(pairs, np.int32).T
    return qi, ki


# Index maps of a (batch*head, step) grid: the step's q-block or k-block
# from the prefetched tables, as rows of a (bh, S, D) operand or as lanes
# of a (bh, 1, S) statistics row.
def _q_rows(bh, t, qi, ki):
    return bh, qi[t], 0


def _k_rows(bh, t, qi, ki):
    return bh, ki[t], 0


def _q_lanes(bh, t, qi, ki):
    return bh, 0, qi[t]


def _kv_rows(group):
    """``_k_rows`` where ``group`` query heads read one key-value head: the
    grid's first axis counts query heads, K and V hold a row of blocks a
    key-value head, and no copy of either is made."""
    if group == 1:
        return _k_rows
    return lambda bh, t, qi, ki: (bh // group, ki[t], 0)


def _first_kblock(qi, block_q, block_k, window):
    """The first k-block a q-block's steps visit."""
    if window is None:
        return 0
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _last_kblock(qi, block_q, block_k, n_kblocks, causal):
    """The last k-block a q-block's steps visit."""
    if not causal:
        return n_kblocks - 1
    return jnp.minimum(n_kblocks - 1, ((qi + 1) * block_q - 1) // block_k)


def _compiler_params(interpret, vmem_bytes):
    """The step axis carries the accumulators; heads are independent.
    ``vmem_bytes`` is the launcher's count of double-buffered blocks,
    scratch and one chunk's score-sized temporaries; the scoped-VMEM
    limit (16 MiB by default) is set half as much again above it, for
    what the compiler spills."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(1.5 * vmem_bytes + (4 << 20), 16 << 20),
                                 100 << 20)))


def _fit(block_q, block_k, sq, sk):
    """Both axes ride lanes somewhere (k in the score tile, q in the
    statistics rows and in dk/dv's tile): whole lane tiles or the whole
    axis."""
    return _fit_block(block_q, sq, _LANES), _fit_block(block_k, sk, _LANES)


def _pallas_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                    window=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    g, sk = k.shape[1], k.shape[2]
    block_q, block_k = _fit(block_q, block_k, sq, sk)
    chunk = _chunk(block_q)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * g, sk, d)
    vf = v.reshape(b * g, sk, d)
    n_kblocks = sk // block_k
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, chunk=chunk,
        causal=causal, scale2=scale * _LOG2E, n_kblocks=n_kblocks,
        **({} if window is None else {"window": window}))
    steps = _steps(sq // block_q, n_kblocks, block_q, block_k, causal, False,
                   window)
    kv_rows = _kv_rows(h // g)
    item = q.dtype.itemsize
    vmem = (4 * (block_q + block_k) * d * item
            + block_q * (2 * _LANES + d) * 4 + 5 * chunk * block_k * 4)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, steps[0].size),
            in_specs=[
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, block_k, d), kv_rows),
                pl.BlockSpec((1, block_k, d), kv_rows),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, 1, block_q), _q_lanes),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # denominator l
                pltpu.VMEM((block_q, d), jnp.float32),   # unnormalized out
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret, vmem),
        interpret=interpret,
        name="mx_flash_fwd",
    )(*steps, qf, kf, vf)
    # callers (ring_flash) take lse as (bh, sq, 128) and keep one lane:
    # a broadcast XLA folds into that slice, never 128x in HBM
    return out.reshape(b, h, sq, d), jnp.broadcast_to(
        lse.reshape(b * h, sq, 1), (b * h, sq, _LANES))


def _dq_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, lse_scr, delta_scr, *, block_q,
               block_k, chunk, causal, scale, n_kblocks, window=None):
    """dQ, one (q-block, k-block) tile of ``_steps`` a grid step. Flash-2
    recompute per chunk of q rows: P = 2^(S2 - lse2) from Q/K and the
    saved row logsumexp, dS = P * (dP - delta), dQ += dS @ K; the score
    scale is applied once, to the finished accumulator. Masked scores
    give p = 2^(-inf - lse2) = 0 exactly (causal rows always have a
    finite lse — see _fwd_kernel). lse2 (= lse * log2 e) and delta come
    as (1, block_q) rows and are laid out lane-replicated, (block_q,
    128), once a q-block."""
    import jax.experimental.pallas as pl

    qi = qi_ref[pl.program_id(1)]
    ki = ki_ref[pl.program_id(1)]

    @pl.when(ki == _first_kblock(qi, block_q, block_k, window))
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lse_scr[:] = _replicated(lse_ref[0])
        delta_scr[:] = _replicated(delta_ref[0])

    def tile(rel):
        def rows_at(lo):
            start, full_start, full_end, end = _span(
                rel, lo, chunk, block_k, False, window)
            if end <= start:
                return
            rows = pl.ds(lo, chunk)
            k = k_ref[0, start:end]
            s = lax.mul(_dot(q_ref[0, rows], k, _NT), scale * _LOG2E)
            if full_end < end:
                s = _mask(s, full_end - start, end - start,
                          rel + lo - full_end, 1)
            if start < full_start:
                s = _mask(s, 0, full_start - start,
                          rel + lo - start - window + 1, 1, lax.lt)
            p = lax.exp2(lax.sub(s, _lanes(lse_scr[rows], end - start)))
            dp = _dot(do_ref[0, rows], v_ref[0, start:end], _NT)
            ds = lax.mul(p, lax.sub(dp, _lanes(delta_scr[rows],
                                               end - start)))
            dq_scr[rows] = lax.add(dq_scr[rows], _dot(
                lax.convert_element_type(ds, k.dtype), k, _NN))

        _for_chunks(rel, block_q, chunk, _GROUP["dq"], rows_at)

    if causal:
        _causal_bodies(qi * block_q - ki * block_k, block_q, block_k, tile,
                       window)
    else:
        tile(None)

    @pl.when(ki == _last_kblock(qi, block_q, block_k, n_kblocks, causal))
    def _write():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, block_q,
                block_k, chunk, causal, scale, n_qblocks, window=None,
                group=1):
    """dK/dV, one tile of ``_steps`` a grid step, a k-block's q-blocks in
    a row from the first one the diagonal lets it see. The tile is
    computed kv-major, S^T = K @ Q^T and dP^T = V @ dO^T, so that
    dV += P^T @ dO and dK += dS^T @ Q are plain products: no operand is
    contracted over its major dim, no tile is transposed. lse2 and delta
    ride lanes as (1, block_q) rows. With ``group`` query heads to a
    key-value head the q table counts blocks through the group's heads
    laid end to end (``_steps``), and the sums run over all of them."""
    import jax.experimental.pallas as pl

    qh = qi = qi_ref[pl.program_id(1)]
    ki = ki_ref[pl.program_id(1)]
    if group > 1:
        qi = qh % n_qblocks
    last = n_qblocks - 1
    if window is not None:
        last = jnp.minimum(last,
                           (ki * block_k + block_k + window - 2) // block_q)

    @pl.when(qh == ((ki * block_k) // block_q if causal else 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(rel):
        def rows_at(lo):
            start, full_start, full_end, end = _span(
                rel, lo, chunk, block_q, True, window)
            if end <= start:
                return
            rows = pl.ds(lo, chunk)
            q = q_ref[0, start:end]                       # (n, D)
            do = do_ref[0, start:end]
            s = lax.mul(_dot(k_ref[0, rows], q, _NT), scale * _LOG2E)
            if start < full_start:
                s = _mask(s, 0, full_start - start, rel - lo + start, 0)
            if full_end < end:
                s = _mask(s, full_end - start, end - start,
                          rel - lo + full_end - window + 1, 0, lax.lt)
            across = lambda row: lax.broadcast_in_dim(  # noqa: E731
                row, s.shape, (0, 1))                     # (chunk, n)
            p = lax.exp2(lax.sub(s, across(lse_ref[0, :, start:end])))
            dv_scr[rows] = lax.add(dv_scr[rows], _dot(
                lax.convert_element_type(p, do.dtype), do, _NN))
            ds = lax.mul(p, lax.sub(_dot(v_ref[0, rows], do, _NT),
                                    across(delta_ref[0, :, start:end])))
            dk_scr[rows] = lax.add(dk_scr[rows], _dot(
                lax.convert_element_type(ds, q.dtype), q, _NN))

        _for_chunks(rel, block_k, chunk, _GROUP["dkv"], rows_at)

    if causal:
        _causal_bodies(qi * block_q - ki * block_k, block_q, block_k, tile,
                       window)
    else:
        tile(None)

    @pl.when(qh == (group - 1) * n_qblocks + last)
    def _write():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_operands(q, k, v, o, lse, g):
    """Flattened operands the two backward kernels share. delta_i =
    sum_d dO_i * O_i is the rowwise correction in dS; O(S*D)."""
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    dof = g.reshape(b * h, sq, d)
    delta = jnp.sum(dof.astype(jnp.float32)
                    * o.reshape(b * h, sq, d).astype(jnp.float32), axis=-1)
    # the O(S) per-row vectors cross HBM as (bh, 1, sq) rows
    return (q.reshape(b * h, sq, d), k.reshape(b * kv, sk, d),
            v.reshape(b * kv, sk, d), dof, (lse * _LOG2E)[:, None, :],
            delta[:, None, :])


def _pallas_dq(qf, kf, vf, dof, lse2, delta, causal, scale, block_q,
               block_k, interpret, window=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = qf.shape
    sk = kf.shape[1]
    block_q, block_k = _fit(block_q, block_k, sq, sk)
    chunk = _chunk(block_q)
    n_kblocks = sk // block_k
    steps = _steps(sq // block_q, n_kblocks, block_q, block_k, causal, False,
                   window)
    kv_rows = _kv_rows(bh // kf.shape[0])
    item = qf.dtype.itemsize
    vmem = ((6 * block_q + 4 * block_k) * d * item
            + block_q * (d + 2 * _LANES) * 4 + 6 * chunk * block_k * 4)
    return pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          chunk=chunk, causal=causal, scale=scale,
                          n_kblocks=n_kblocks,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, steps[0].size),
            in_specs=[
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, block_k, d), kv_rows),
                pl.BlockSpec((1, block_k, d), kv_rows),
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, 1, block_q), _q_lanes),
                pl.BlockSpec((1, 1, block_q), _q_lanes),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), _q_rows),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
        compiler_params=_compiler_params(interpret, vmem),
        interpret=interpret,
        name="mx_flash_dq",
    )(*steps, qf, kf, vf, dof, lse2, delta)


def _pallas_dkv(qf, kf, vf, dof, lse2, delta, causal, scale, block_q,
                block_k, interpret, window=None):
    """dK and dV of each key-value head, summed inside the kernel over
    the query heads that read it: a group's heads are laid end to end, as
    one operand of group * sq rows a key-value head (a reshape, no copy),
    and a k-block's accumulators stay in VMEM through all of them."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, sq, d), bg = qf.shape, kf.shape[0]
    sk = kf.shape[1]
    group = bh // bg
    block_q, block_k = _fit(block_q, block_k, sq, sk)
    chunk = _chunk(block_k)
    n_qblocks = sq // block_q
    steps = _steps(n_qblocks, sk // block_k, block_q, block_k, causal, True,
                   window, group)
    if group > 1:
        qf, dof = qf.reshape(bg, group * sq, d), dof.reshape(bg, group * sq, d)
        lse2 = lse2.reshape(bg, 1, group * sq)
        delta = delta.reshape(bg, 1, group * sq)
    extra = {} if window is None else {"window": window}
    if group > 1:
        extra["group"] = group
    item = qf.dtype.itemsize
    vmem = ((4 * block_q + 8 * block_k) * d * item
            + 2 * block_k * d * 4 + 6 * chunk * block_q * 4)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                          chunk=chunk, causal=causal, scale=scale,
                          n_qblocks=n_qblocks, **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bg, steps[0].size),
            in_specs=[
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, block_k, d), _k_rows),
                pl.BlockSpec((1, block_k, d), _k_rows),
                pl.BlockSpec((1, block_q, d), _q_rows),
                pl.BlockSpec((1, 1, block_q), _q_lanes),
                pl.BlockSpec((1, 1, block_q), _q_lanes),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), _k_rows),
                pl.BlockSpec((1, block_k, d), _k_rows),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bg, sk, d), kf.dtype),
            jax.ShapeDtypeStruct((bg, sk, d), vf.dtype),
        ],
        compiler_params=_compiler_params(interpret, vmem),
        interpret=interpret,
        name="mx_flash_dkv",
    )(*steps, qf, kf, vf, dof, lse2, delta)


def _backward(q, k, v, o, lse, g, causal, scale, dq_blocks, dkv_blocks,
              interpret, window=None):
    """dq, dk, dv from the saved output and row logsumexp ``lse``
    (bh, sq), each kernel on its own (block_q, block_k)."""
    ops = _bwd_operands(q, k, v, o, lse, g)
    w = () if window is None else (window,)
    dq = _pallas_dq(*ops, causal, scale, *dq_blocks, interpret, *w)
    dk, dv = _pallas_dkv(*ops, causal, scale, *dkv_blocks, interpret, *w)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _pallas_backward(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                     interpret):
    """``_backward`` with one (block_q, block_k) for both kernels: what
    parallel/ring_flash.py calls per hop."""
    return _backward(q, k, v, o, lse, g, causal, scale, (block_q, block_k),
                     (block_q, block_k), interpret)


def _use_pallas():
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, blocks, interpret, window=None):
    """``blocks``: the (block_q, block_k) of forward, dq and dk/dv."""
    if interpret or _use_pallas():
        return _pallas_forward(q, k, v, causal, scale, *blocks[0],
                               interpret, window)[0]
    return attention_reference(q, k, v, causal=causal, scale=scale,
                               window=window)


def _flash_fwd(q, k, v, causal, scale, blocks, interpret, window):
    if interpret or _use_pallas():
        out, lse = _pallas_forward(q, k, v, causal, scale, *blocks[0],
                                   interpret, window)
        # keep one lane of the (bh, sq, 128) kernel output — the lane dim
        # exists only for Mosaic's block constraint, not worth 128x HBM
        # across the fwd->bwd interval. The two residuals only this kernel
        # can make are named, so that a caller's jax.checkpoint may keep
        # them (parallel/transformer.py _remat_rows) and its backward not
        # run the kernel again; outside a checkpoint a name is the identity
        out = checkpoint_name(out, "flash_out")
        return out, (q, k, v, out, checkpoint_name(lse[:, :, 0], "flash_lse"))
    out = attention_reference(q, k, v, causal=causal, scale=scale,
                              window=window)
    return out, (q, k, v, None, None)


def _flash_bwd(causal, scale, blocks, interpret, window, res, g):
    q, k, v, o, lse = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=causal, scale=scale, window=window),
            q, k, v)
        return vjp(g)
    return _backward(q, k, v, o, lse, g, causal, scale, blocks[1], blocks[2],
                     interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _default_blocks(sq, sk, d=128, dtype=jnp.bfloat16):
    """((block_q, block_k) of forward, of dq, of dk/dv): each kernel's
    fastest tile on one v5e chip (PERF.md, PR 27, has the sweeps at
    [8, 32, 2048, 128] and [1, 32, 16384, 128] bf16). Forward wants a
    tall tile (K/V stream once per 2048 q rows, the accumulator rescale
    is shared by 1024 columns); dq and dk/dv, which carry no running
    statistics, the largest square that leaves VMEM room. Wider heads
    and 4-byte operands halve the tile to keep that room. Blocks clamp
    to the sequence."""
    big = 2048 if d <= 128 and jnp.dtype(dtype).itemsize <= 2 else 1024
    return tuple(_fit(bq, bk, sq, sk)
                 for bq, bk in ((big, 1024), (big, big), (big, big)))


def _computed_pairs(sq, sk, block_q, block_k, chunk, causal, kv_major,
                    window=None):
    """Score pairs one kernel computes for one query head, from the same
    classification its body uses."""
    if not causal:
        return sq * sk
    static = _masked_rels(block_q, block_k, window) is not None
    chunked, other = (block_k, block_q) if kv_major else (block_q, block_k)
    inside = float("inf") if window is None else window - block_q
    total = 0
    for qi in range(sq // block_q):
        for ki in range(sk // block_k):
            rel = qi * block_q - ki * block_k
            if rel <= -block_q or (window is not None
                                   and rel >= window + block_k - 1):
                continue
            if block_k - 1 <= rel <= inside or not static:
                total += block_q * block_k
                continue
            for lo in range(0, chunked, chunk):
                start, _, _, end = _span(rel, lo, chunk, other, kv_major,
                                         window)
                total += chunk * max(end - start, 0)
    return total


def _kept_pairs(sq, sk, causal, window=None):
    """Score pairs the mask keeps, for one query head."""
    if not causal:
        return sq * sk
    if window is None or window >= sq:
        return sq * (sq + 1) // 2
    return window * (window + 1) // 2 + (sq - window) * window


# metrics()["flash"] / dumps(): one entry per distinct call shape, made
# when the call is traced — the tile shape each kernel took and the
# score pairs it computes over the pairs the mask keeps (1.0 = no work
# above the diagonal). Costs nothing a step.
_CALLS = {}  # mxlint: disable=MX003 (GIL-atomic trace-time record, one string per call shape; a racing duplicate writes the same value)


def _record_call(q, k, causal, blocks, window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    kept = _kept_pairs(sq, sk, causal, window)
    parts = []
    for name, (bq, bk) in zip(("fwd", "dq", "dkv"), blocks):
        kv_major = name == "dkv"
        chunk = _chunk(bk if kv_major else bq)
        pairs = _computed_pairs(sq, sk, bq, bk, chunk, causal, kv_major,
                                window)
        parts.append("%s=%dx%d/%.4f" % (name, bq, bk, pairs / kept))
    # grouped heads and a window are named only where the call has them
    key = "%dx%dx%dx%dx%d.%s.%s" % (
        b, h, sq, sk, d, jnp.dtype(q.dtype).name,
        "causal" if causal else "full")
    if k.shape[1] != h:
        key += ".kv%d" % k.shape[1]
    if window is not None:
        key += ".window%d" % window
    _CALLS[key] = " ".join(parts)


_profiler.register_stats_provider("flash", lambda: dict(_CALLS),
                                  _CALLS.clear)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False, window=None):
    """Tiled attention. q: [B, H, S, D]; k, v: [B, G, S, D] with H a
    multiple of G: query head h reads key-value head h // (H/G) from its
    own rows, and dk/dv come summed over the group. ``window`` (needs
    ``causal``): a query sees the last ``window`` keys only, itself among
    them; tiles wholly outside are no grid steps. On TPU runs the Pallas
    kernels; elsewhere the jnp reference (or the kernels under
    ``interpret=True`` for testing). By default each of the three kernels
    takes its own measured tile shape (``_default_blocks``); an explicit
    block_q/block_k applies to all three. Blocks clamp to the sequence
    length."""
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            "flash_attention: %d query heads cannot share %d key and %d "
            "value heads" % (q.shape[1], k.shape[1], v.shape[1]))
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: a window needs causal=True")
        window = int(window)
        if window >= q.shape[2]:
            window = None       # it cuts nothing: the plain causal call
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
    sq, sk = q.shape[2], k.shape[2]
    blocks = tuple(
        _fit(bq if block_q is None else int(block_q),
             bk if block_k is None else int(block_k), sq, sk)
        for bq, bk in _default_blocks(sq, sk, q.shape[-1], q.dtype))
    if interpret or _use_pallas():
        _record_call(q, k, causal, blocks, window)
    return _flash(q, k, v, bool(causal), float(scale), blocks,
                  bool(interpret), window)
