"""Moving rows between token order and slot order, live rows only.

What an expert share (``parallel/expert.py moe_share``) does around its
grouped products: a token's row goes to the buffer rows of its slots
(dispatch, and the combine's backward), and a token takes the weighted sum of
its slots' buffer rows (combine, and the dispatch's backward). The buffer has
a row for every slot there is; a step's routing gives the experts held a
prefix of it (``used`` tiles, ``grouped_matmul``'s layout) and gives a token
only some of its k slots. These kernels do work for those alone.

A row moves as ONE DMA of whole (8, 128) tiles: Mosaic slices a ref in HBM by
whole tiles only, and a row of a ``[N, D]`` array is an eighth (a sixteenth,
in 16 bits) of each of D / 128 tiles. So the source of a gather is first
*packed*: ``[N * P8, L]`` uint32, row n the P = words / L sublanes from
``n * P8``, contiguous in HBM (L = 128 lanes; 4-byte elements one a word,
2-byte elements two a word: column c beside column c + D / 2, so both halves
come apart again with a shift and a mask and no lane moves). The stride P8
is P rounded up to whole tiles of 8 sublanes where L is 128 (P itself
below 128 lanes, which only the interpreter runs): a row of 28 sublanes
(hidden 7168 in 16 bits) moves as 32, whose last four nothing writes and
no arithmetic reads.

- ``mx_moe_pack``:   [N, D] -> packed, tiles < ``used`` only
- ``mx_moe_gather``: out[r] = row idx[r] of a packed source (x scale[r]),
                     tiles < ``used`` only; with ``other``, also
                     dots[r] = sum_d other[r, d] * row[d] in float32
- ``mx_moe_sum``:    out[t] = sum over j = 0 .. k-1, in that order and in
                     float32, of weight[t, j] * row rows[t, j] of a packed
                     source; only the rows a tile's list names are fetched

Tiles behind ``used`` are grid steps that do nothing: their index maps point
at the last tile in use, they move no data, and their rows of the output are
never written (``grouped_matmul``'s contract). The caller keeps the plain
``jnp.take`` forms (``*_reference`` here) off the TPU and for widths whose
rows are not whole words of 128 lanes (``fits``); under ``interpret`` any
width that packs into whole words runs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .grouped_matmul import _params, _tile_in_use

__all__ = ["fits", "pack_rows", "gather_rows", "sum_rows", "tile_lists",
           "tokens_of", "gather_rows_reference", "sum_rows_reference"]

_ISSUE = 8      # row copies started (and awaited) a loop step
_CHUNK = 32     # rows of a tile worked at once: what the registers hold
_SMEM_WORDS = 1024    # a 1-D int32 block in SMEM is a multiple of this
_U32 = jnp.uint32


def _words(width, dtype):
    """(uint32 words a row, lanes L, live sublanes P, stride P8) of a
    packed row: row n lies at sublanes [n * P8, n * P8 + P)."""
    per = 4 // jnp.dtype(dtype).itemsize
    words = width // per
    lanes = min(128, words)
    sublanes = words // lanes
    stride = -(-sublanes // 8) * 8 if lanes == 128 else sublanes
    return words, lanes, sublanes, stride


def fits(width, dtype, on_tpu):
    """Whether rows of ``width`` elements of ``dtype`` pack into whole
    words, and on the TPU into whole words of 128 lanes."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    words, lanes, _, _ = _words(width, dtype)
    if width % (4 // dtype.itemsize) or words % lanes:
        return False
    return not on_tpu or lanes == 128


def gather_rows_reference(x, idx, scale=None, other=None):
    """``gather_rows`` of ``pack_rows(x)`` in plain XLA, every row of it:
    out[r] = x[idx[r]] (times scale[r]); with ``other`` also their dots."""
    rows = jnp.take(x, idx, axis=0)
    out = rows if scale is None else (rows * scale[:, None]).astype(x.dtype)
    if other is None:
        return out
    return out, jnp.sum(rows.astype(jnp.float32) * other.astype(jnp.float32),
                        axis=-1)


def sum_rows_reference(table, rows, held, weights, dtype):
    """``sum_rows`` of ``pack_rows(table)`` in plain XLA: out[t] = sum over
    j = 0 .. k-1 of weights[t, j] * table[rows[t, j]] where ``held``, in
    float32. k gathers of T rows, added as they go: one gather of T * k
    rows would lay a [T, k, D] array down first (PERF.md, PR 30)."""
    def slot(j):
        got = jnp.take(table, rows[:, j], axis=0)
        return jnp.where(held[:, j, None], got, 0).astype(jnp.float32)
    return sum(slot(j) * weights[:, j, None]
               for j in range(rows.shape[1])).astype(dtype)


def _to_words(lo, hi):
    """Two [n, L] blocks of a 2-byte float type (``hi`` None: one block of
    a 4-byte type) as [n, L] uint32."""
    if hi is None:
        return lax.bitcast_convert_type(lo, _U32)
    lo = lax.bitcast_convert_type(lo.astype(jnp.float32), _U32)
    hi = lax.bitcast_convert_type(hi.astype(jnp.float32), _U32)
    return (lo >> 16) | (hi & _U32(0xffff0000))


def _from_words(w, halves):
    """[n, L] uint32 -> (low, high) float32 [n, L] (``halves`` false: the
    word itself, and None)."""
    if not halves:
        return lax.bitcast_convert_type(w, jnp.float32), None
    return (lax.bitcast_convert_type(w << 16, jnp.float32),
            lax.bitcast_convert_type(w & _U32(0xffff0000), jnp.float32))


# -- mx_moe_pack --------------------------------------------------------------

def _pack_kernel(used, x_ref, o_ref, *, tile, lanes, sublanes, stride,
                 halves):
    import jax.experimental.pallas as pl
    half = lanes * sublanes

    @pl.when(pl.program_id(0) < used[0])
    def _():
        # a loop, not Python's: the kernels are traced once a call (a
        # dozen a layer), and set-up pays for every equation traced
        def sublane(s, c):
            col = pl.multiple_of(s * lanes, lanes)
            lo = x_ref[:, pl.ds(col, lanes)]
            hi = x_ref[:, pl.ds(half + col, lanes)] if halves else None
            o_ref[pl.ds(s, tile, stride=stride), :] = _to_words(lo, hi)
            return c
        lax.fori_loop(0, sublanes, sublane, 0)


def pack_rows(x, used, tile, interpret=False):
    """x [N, D] -> [N * P8, L] uint32 for tiles of ``tile`` rows < ``used``
    [1] (the rest, and each row's sublanes behind its P, is never
    written)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, width = x.shape
    words, lanes, sublanes, stride = _words(width, x.dtype)
    in_use = lambda i, used: (_tile_in_use(i, used), 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_pack_kernel, tile=tile, lanes=lanes,
                          sublanes=sublanes, stride=stride,
                          halves=x.dtype.itemsize == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(-(-n // tile),),
            in_specs=[pl.BlockSpec((tile, width), in_use)],
            out_specs=pl.BlockSpec((tile * stride, lanes), in_use)),
        out_shape=jax.ShapeDtypeStruct((n * stride, lanes), _U32),
        compiler_params=_params(interpret,
                                2 * tile * (words + stride * lanes) * 4),
        interpret=interpret, name="mx_moe_pack",
    )(used, x)


# -- mx_moe_gather ------------------------------------------------------------

def _row_copy(src, buf, sem, row, place, stride):
    """One packed row, all ``stride`` sublanes of it: whole tiles."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.make_async_copy(
        src.at[pl.ds(pl.multiple_of(row * stride, stride), stride)],
        buf.at[pl.ds(pl.multiple_of(place * stride, stride), stride)],
        sem)


def _await_rows(src, buf, sem, n, stride):
    """Wait for ``n`` (a multiple of ``_ISSUE``) row copies into ``buf``."""
    def wait(_, c):
        for _ in range(_ISSUE):
            _row_copy(src, buf, sem, 0, 0, stride).wait()
        return c
    lax.fori_loop(0, n // _ISSUE, wait, 0)


def _gather_kernel(used, idx, src, *rest, tile, lanes, sublanes, stride,
                   halves, scaled, dotted):
    import jax.experimental.pallas as pl
    rest = list(rest)
    scale = rest.pop(0) if scaled else None
    other = rest.pop(0) if dotted else None
    out = rest.pop(0)
    dots = rest.pop(0) if dotted else None
    buf, sem = rest
    half = lanes * sublanes
    chunk = min(_CHUNK, tile)

    # (the interpreter knows program_id at the kernel's top level only)
    first = (pl.program_id(0) * tile) % idx.shape[0]

    @pl.when(pl.program_id(0) < used[0])
    def _():
        def issue(b, c):
            for u in range(_ISSUE):
                r = b * _ISSUE + u
                _row_copy(src, buf, sem, idx[first + r], r, stride).start()
            return c
        lax.fori_loop(0, tile // _ISSUE, issue, 0)
        _await_rows(src, buf, sem, tile, stride)

        def rows(c, carry):
            at = pl.multiple_of(c * chunk, chunk)
            span = pl.ds(at, chunk)
            by = scale[span, :] if scaled else None

            def sublane(s, acc):
                col = pl.multiple_of(s * lanes, lanes)
                parts = _from_words(
                    buf[pl.ds(at * stride + s, chunk, stride=stride), :],
                    halves)
                for part, at_col in zip(parts, (col, half + col)):
                    if part is None:
                        continue
                    cols = pl.ds(at_col, lanes)
                    if dotted:
                        acc += part * other[span, cols].astype(jnp.float32)
                    out[span, cols] = (part * by if scaled else part
                                       ).astype(out.dtype)
                return acc
            # traced once, unrolled when lowered: 0.3 ms a call faster on
            # the chip than the loop kept (PERF.md, PR 31)
            acc = lax.fori_loop(0, sublanes, sublane,
                                jnp.zeros((chunk, lanes), jnp.float32),
                                unroll=True)
            if dotted:
                dots[span, :] = jnp.sum(acc, axis=1, keepdims=True)
            return carry
        lax.fori_loop(0, tile // chunk, rows, 0)


def gather_rows(packed, idx, used, tile, width, dtype, scale=None,
                other=None, interpret=False):
    """out[r] = row ``idx[r]`` of ``packed`` (``pack_rows`` of a [N, width]
    array of ``dtype``), times ``scale[r]`` (float32 [rows]) if given, for
    the tiles of ``tile`` rows < ``used`` [1]; rows behind are never
    written. With ``other`` [rows, width] also dots [rows] float32 =
    sum_d other[r, d] * (the row before scaling)[d]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows = idx.shape[0]
    words, lanes, sublanes, stride = _words(width, dtype)
    in_use = lambda i, used: (_tile_in_use(i, used), 0)  # noqa: E731
    # a 1-D block in SMEM is whole tiles of 1024 words: several row tiles
    listed = max(tile, _SMEM_WORDS)
    assert listed % tile == 0, tile
    in_specs = [pl.BlockSpec(
        (listed,), lambda i, used: (_tile_in_use(i, used) * tile // listed,),
        memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [idx.astype(jnp.int32), packed]
    out_specs = [pl.BlockSpec((tile, width), in_use)]
    out_shape = [jax.ShapeDtypeStruct((rows, width), dtype)]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tile, 1), in_use))
        operands.append(scale.astype(jnp.float32).reshape(rows, 1))
    if other is not None:
        in_specs.append(pl.BlockSpec((tile, width), in_use))
        operands.append(other)
        out_specs.append(pl.BlockSpec((tile, 1), in_use))
        out_shape.append(jax.ShapeDtypeStruct((rows, 1), jnp.float32))
    item = jnp.dtype(dtype).itemsize
    vmem = tile * stride * lanes * 4 + 2 * tile * width * item * (
        2 if other is not None else 1) + 4 * tile * 512
    got = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, lanes=lanes,
                          sublanes=sublanes, stride=stride, halves=item == 2,
                          scaled=scale is not None, dotted=other is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile * stride, lanes), _U32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape, compiler_params=_params(interpret, vmem),
        interpret=interpret, name="mx_moe_gather",
    )(used, *operands)
    return got[0] if other is None else (got[0], got[1].reshape(rows))


# -- mx_moe_sum ---------------------------------------------------------------

def _row_bits(buffer_rows):
    return max(int(buffer_rows - 1).bit_length(), 1)


def tokens_of(k):
    """Tokens of a grid step of ``mx_moe_sum``: the fewest whose k slots
    fill whole 1-D blocks in SMEM (128 at k = 8: 4 MB of rows in flight)."""
    return math.lcm(_SMEM_WORDS, k) // k


def tile_lists(rows, held, buffer_rows):
    """For ``mx_moe_sum``: each tile of ``tokens_of(k)`` tokens' list of the
    rows it fetches. rows, held: [T, k]. -> (lists [tiles * tokens * k] int32:
    a tile's held slots first, each ``place << bits | row`` with place =
    j * tokens + t the slot's block in the kernel's buffer; the others name
    the spare block behind it and row 0; counts [tiles]: a tile's held
    slots, rounded up to whole loop steps)."""
    T, k = rows.shape
    tokens = tokens_of(k)
    bits = _row_bits(buffer_rows)
    assert bits + int(tokens * k).bit_length() <= 31, (buffer_rows, tokens, k)
    tiles = -(-T // tokens)
    pad = ((0, tiles * tokens - T), (0, 0))
    rows, held = jnp.pad(rows, pad), jnp.pad(held, pad)
    place = (jnp.arange(k, dtype=jnp.int32)[None, :] * tokens
             + (jnp.arange(tiles * tokens, dtype=jnp.int32) % tokens)[:, None])
    key = jnp.where(held, (place << bits) | rows.astype(jnp.int32),
                    jnp.int32((tokens * k) << bits))
    lists = jnp.sort(key.reshape(tiles, tokens * k), axis=-1)
    counts = jnp.sum(held.reshape(tiles, tokens * k), axis=-1,
                     dtype=jnp.int32)
    return lists.reshape(-1), -(-counts // _ISSUE) * _ISSUE


def _sum_kernel(counts, lists, src, w_ref, out, buf, sem, *, tokens, k, bits,
                lanes, sublanes, stride, halves):
    import jax.experimental.pallas as pl
    i = pl.program_id(0)
    half = lanes * sublanes
    chunk = min(_CHUNK, tokens)

    @pl.when(i == 0)
    def _():
        # what no copy of this step fills is multiplied by a weight of
        # nought below: it has to be finite, so nothing is left as found
        buf[...] = jnp.zeros_like(buf)

    n = counts[i]

    def issue(b, c):
        for u in range(_ISSUE):
            v = lists[b * _ISSUE + u]
            _row_copy(src, buf, sem, v & ((1 << bits) - 1), v >> bits,
                      stride).start()
        return c
    lax.fori_loop(0, n // _ISSUE, issue, 0)
    _await_rows(src, buf, sem, n, stride)

    def rows(c, carry):
        at = pl.multiple_of(c * chunk, chunk)
        span = pl.ds(at, chunk)
        by = [w_ref[span, j:j + 1] for j in range(k)]

        def sublane(s, c2):
            col = pl.multiple_of(s * lanes, lanes)
            lo = jnp.zeros((chunk, lanes), jnp.float32)
            hi = lo if halves else None
            for j in range(k):
                a, b = _from_words(
                    buf[pl.ds((j * tokens + at) * stride + s, chunk,
                              stride=stride), :], halves)
                lo = lo + a * by[j]
                if halves:
                    hi = hi + b * by[j]
            out[span, pl.ds(col, lanes)] = lo.astype(out.dtype)
            if halves:
                out[span, pl.ds(half + col, lanes)] = hi.astype(out.dtype)
            return c2
        lax.fori_loop(0, sublanes, sublane, 0, unroll=True)
        return carry
    lax.fori_loop(0, tokens // chunk, rows, 0)


def sum_rows(packed, lists, counts, weights, width, dtype, interpret=False):
    """out[t] = sum_j weights[t, j] * row rows[t, j] of ``packed``, j = 0
    .. k-1 in that order, in float32, rounded to ``dtype`` once. ``lists``,
    ``counts``: ``tile_lists`` of (rows, held); ``weights``
    [T, k] float32 with NOUGHT where a slot is not held: such a slot's row
    is not fetched, and what the buffer holds in its place (an older row,
    finite) is multiplied by that nought."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, k = weights.shape
    tokens = tokens_of(k)
    words, lanes, sublanes, stride = _words(width, dtype)
    item = jnp.dtype(dtype).itemsize
    bits = _row_bits(packed.shape[0] // stride)
    block = (tokens * k + 1) * stride          # and the spare block
    vmem = block * lanes * 4 + 2 * tokens * width * item + 4 * tokens * 512
    return pl.pallas_call(
        functools.partial(_sum_kernel, tokens=tokens, k=k, bits=bits,
                          lanes=lanes, sublanes=sublanes, stride=stride,
                          halves=item == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(-(-T // tokens),),
            in_specs=[pl.BlockSpec((tokens * k,), lambda i, counts: (i,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tokens, k), lambda i, counts: (i, 0))],
            out_specs=pl.BlockSpec((tokens, width), lambda i, counts: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block, lanes), _U32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((T, width), dtype),
        compiler_params=_params(interpret, vmem), interpret=interpret,
        name="mx_moe_sum",
    )(counts, lists, packed, weights.astype(jnp.float32))
