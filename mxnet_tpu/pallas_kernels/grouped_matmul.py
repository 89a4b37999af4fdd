"""Grouped matrix products over rows sorted by group: out[r] = x[r] @ w[g(r)].

What an expert layer runs once its token-slots are sorted by expert
(``parallel/expert.py moe_share``). The rows of one group lie together and
every group starts on a tile boundary (its rows padded to whole tiles of
``tile`` rows), so a row tile belongs to exactly one group and the kernels
are plain tiled products whose weight block is picked by a prefetched table:

- ``mx_gmm_fwd``: out[tile] = x[tile] @ w[group of tile]
- ``mx_gmm_dx``:  dx[tile] = dy[tile] @ w[group of tile]^T
- ``mx_gmm_dw``:  dw[g] = sum over the tiles of g of x[tile]^T @ dy[tile]

and the SiLU-gated pair of an expert's feed-forward (``grouped_glu``), so
that the gate runs over the tiles in use and never crosses HBM on its own:

- ``mx_gmm_glu_fwd``: a = x @ w_gate[g], b = x @ w_up[g], each rounded to
  x's type, h = silu(a) * b in float32, rounded once (a and b kept for the
  backward)
- ``mx_gmm_glu_dx``: da = dh * b * silu'(a), db = dh * silu(a) in float32,
  written in x's type for ``mx_gmm_dw``; dx = da @ w_gate[g]^T + db @
  w_up[g]^T in one float32 sum, rounded once

A grid step is one row tile; the whole K and N of a group's matrix sit in
VMEM and stay there through the group's tiles (the block index does not
change, so nothing is fetched again). Tiles behind the last one in use are
grid steps that do nothing: their index maps point at the last tile in use,
so they move no data, and their rows of the output are never written. A
caller reads only the rows it laid out.

The invariant a caller and these kernels share (``parallel/expert.py _plan``
lays the rows out, ``pallas_kernels/moe_rows.py`` fills and reads them): the
tiles in use are a prefix, ``used`` = sum(sizes) / TILE of them. Rows behind
it hold nothing: no kernel here reads or writes them, and whatever they hold
(it need not be finite) reaches no row in use. The padding rows of a tile in
use ARE read (``mx_gmm_dw`` multiplies them by a ``dy`` of nought) and so
must be finite.

XLA's own lowering of ``jax.lax.ragged_dot`` on this chip is a kernel of the
same family with tiles of 512 x 512 x 512; it runs at 46% of the grouped
product's roofline at the Trinity cell's shapes and drops the ``mx.*`` scope
of the instruction (its ``op_name`` becomes "ragged-dot-none"), so the trace
can attribute it to no layer and to no phase (PERF.md, PR 30). Off the TPU
``grouped_matmul`` is ``ragged_dot`` over the padded group sizes, and
``grouped_glu`` the gate over two of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_matmul", "grouped_matmul_reference", "grouped_glu",
           "grouped_glu_reference", "glu_fits", "TILE"]

TILE = 256      # rows of a tile: a group's rows are padded to whole tiles
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_VMEM_CAP = 100 << 20               # the most a kernel here asks for
_VMEM_SLACK = 8 << 20               # asked for beyond a kernel's estimate


def grouped_matmul_reference(x, w, sizes):
    """The same product in plain XLA: ``ragged_dot`` over the padded groups
    (rows behind the last group come out nought)."""
    return lax.ragged_dot(x, w, sizes.astype(jnp.int32))


def grouped_glu_reference(x, w_gate, w_up, sizes):
    """``grouped_glu`` in plain XLA: the gate over two ``ragged_dot``."""
    return jax.nn.silu(grouped_matmul_reference(x, w_gate, sizes)) \
        * grouped_matmul_reference(x, w_up, sizes)


def _use_pallas():
    return jax.default_backend() == "tpu"


def _params(interpret, vmem_bytes):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(vmem_bytes + _VMEM_SLACK, _VMEM_CAP)))


def _used(sizes):
    """[1]: the tiles in use, the prefix the groups fill."""
    return (jnp.sum(sizes, dtype=jnp.int32) // TILE).reshape(1)


def _tile_in_use(i, used):
    """The tile a grid step works on: its own, or the last one in use."""
    return jnp.minimum(i, used[0] - 1)


def _row_tile(i, group_of, used):
    return _tile_in_use(i, used), 0


def _group_block(i, group_of, used):
    return group_of[_tile_in_use(i, used)], 0, 0


def _product_kernel(group_of, used, a_ref, w_ref, o_ref, *, dims):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < used[0])
    def _():
        o_ref[...] = lax.dot_general(
            a_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _product(a, w, group_of, used, transposed, interpret):
    """a [rows, K] @ w[group] [K, N] -> [rows, N]; ``transposed``: a [rows,
    N] @ w[group]^T -> [rows, K]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, width = a.shape
    _, k, n = w.shape
    out = k if transposed else n
    item = a.dtype.itemsize
    vmem = 2 * item * (TILE * width + k * n + TILE * out) + 4 * TILE * out
    return pl.pallas_call(
        functools.partial(_product_kernel, dims=_NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // TILE,),
            in_specs=[pl.BlockSpec((TILE, width), _row_tile),
                      pl.BlockSpec((1, k, n), _group_block)],
            out_specs=pl.BlockSpec((TILE, out), _row_tile)),
        out_shape=jax.ShapeDtypeStruct((rows, out), a.dtype),
        compiler_params=_params(interpret, vmem), interpret=interpret,
        name="mx_gmm_dx" if transposed else "mx_gmm_fwd",
    )(group_of, used, a, w)


def _dw_kernel(group_of, used, x_ref, dy_ref, dw_ref, acc, *, n_tiles):
    import jax.experimental.pallas as pl
    i = pl.program_id(0)
    mine = group_of[_tile_in_use(i, used)]
    before = group_of[jnp.maximum(i - 1, 0)]
    after = group_of[jnp.minimum(i + 1, n_tiles - 1)]
    live = i < used[0]

    @pl.when(live & ((i == 0) | (before != mine)))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live)
    def _():
        acc[...] += lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                    preferred_element_type=jnp.float32)

    @pl.when(live & ((i == used[0] - 1) | (after != mine)))
    def _():
        dw_ref[0] = acc[...].astype(dw_ref.dtype)


def _dw(x, dy, group_of, used, n_groups, interpret):
    """dw[g] = sum over the tiles of group g of x[tile]^T @ dy[tile]. Every
    group has a tile (a group of no rows has one of padding, whose dy is
    nought), so every block of the result is written."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, k = x.shape
    n = dy.shape[1]
    item = x.dtype.itemsize
    vmem = 2 * item * (TILE * (k + n) + k * n) + 2 * 4 * k * n
    return pl.pallas_call(
        functools.partial(_dw_kernel, n_tiles=rows // TILE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // TILE,),
            in_specs=[pl.BlockSpec((TILE, k), _row_tile),
                      pl.BlockSpec((TILE, n), _row_tile)],
            out_specs=pl.BlockSpec((1, k, n), _group_block),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), x.dtype),
        compiler_params=_params(interpret, vmem), interpret=interpret,
        name="mx_gmm_dw",
    )(group_of, used, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(x, w, group_of, used, interpret):
    return _product(x, w, group_of, used, False, interpret)


def _gmm_fwd(x, w, group_of, used, interpret):
    return _gmm(x, w, group_of, used, interpret), (x, w, group_of, used)


def _gmm_bwd(interpret, res, dy):
    x, w, group_of, used = res
    return (_product(dy, w, group_of, used, True, interpret),
            _dw(x, dy, group_of, used, w.shape[0], interpret), None, None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, sizes, group_of, interpret=False):
    """x: [rows, K], rows a multiple of ``TILE``; w: [G, K, N]; ``sizes``
    [G]: each group's rows, every one a multiple of ``TILE`` and at least
    one tile, the groups laid one after the other from row 0;
    ``group_of`` [rows // TILE]: each tile's group (any group for tiles
    behind the last in use). -> [rows, N], out[r] = x[r] @ w[group of r];
    rows behind the last group hold nothing a caller may read. The
    gradients of x and w are kernels of the same kind."""
    if interpret or _use_pallas():
        return _gmm(x, w, group_of.astype(jnp.int32), _used(sizes),
                    bool(interpret))
    return grouped_matmul_reference(x, w, sizes)


# -- the gated pair: both products from one x tile, the gate in the epilogue --

def _silu_parts(a):
    """(sigmoid(a), silu(a)) of a float32 tile."""
    s = jax.nn.sigmoid(a)
    return s, a * s


def _glu_fwd_kernel(group_of, used, x_ref, wg_ref, wu_ref, h_ref, *kept):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < used[0])
    def _():
        x = x_ref[...]
        a, b = (lax.dot_general(x, w_ref[0], _NN,
                                preferred_element_type=jnp.float32
                                ).astype(h_ref.dtype)
                for w_ref in (wg_ref, wu_ref))
        for ref, v in zip(kept, (a, b)):
            ref[...] = v
        h_ref[...] = (_silu_parts(a.astype(jnp.float32))[1]
                      * b.astype(jnp.float32)).astype(h_ref.dtype)


def _glu_dx_kernel(group_of, used, dh_ref, a_ref, b_ref, wg_ref, wu_ref,
                   dx_ref, da_ref, db_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < used[0])
    def _():
        dh, a, b = (r[...].astype(jnp.float32) for r in (dh_ref, a_ref,
                                                          b_ref))
        s, silu = _silu_parts(a)
        da = (dh * b * (s + silu * (1.0 - s))).astype(da_ref.dtype)
        db = (dh * silu).astype(db_ref.dtype)
        da_ref[...] = da
        db_ref[...] = db
        dx_ref[...] = (
            lax.dot_general(da, wg_ref[0], _NT,
                            preferred_element_type=jnp.float32)
            + lax.dot_general(db, wu_ref[0], _NT,
                              preferred_element_type=jnp.float32)
        ).astype(dx_ref.dtype)


def _glu_vmem(k, n, item):
    """VMEM the larger of the two gated kernels asks for at x [TILE, k],
    weights [k, n]: both weight blocks and every row tile double-buffered,
    and the float32 tiles of the epilogue (the forward: x, h, a, b and two
    accumulators; dx: dh, a, b, da, db, dx and the float32 dx and gate)."""
    fwd = 2 * item * (TILE * k + 2 * k * n + 3 * TILE * n) + 4 * 4 * TILE * n
    dx = 2 * item * (TILE * k + 2 * k * n + 5 * TILE * n) \
        + 4 * (TILE * k + 4 * TILE * n)
    return max(fwd, dx)


def glu_fits(k, n, dtype):
    """Whether ``grouped_glu``'s kernels fit VMEM at x [rows, k] against
    weights [G, k, n] of ``dtype``."""
    return _glu_vmem(k, n, jnp.dtype(dtype).itemsize) + _VMEM_SLACK \
        <= _VMEM_CAP


def _glu_call(x, w_gate, w_up, group_of, used, keep, interpret):
    """-> h [rows, N], and with ``keep`` also a and b."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, k = x.shape
    n = w_gate.shape[2]
    tile = pl.BlockSpec((TILE, n), _row_tile)
    weight = pl.BlockSpec((1, k, n), _group_block)
    out = jax.ShapeDtypeStruct((rows, n), x.dtype)
    return pl.pallas_call(
        _glu_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // TILE,),
            in_specs=[pl.BlockSpec((TILE, k), _row_tile), weight, weight],
            out_specs=[tile] * (3 if keep else 1)),
        out_shape=[out] * (3 if keep else 1),
        compiler_params=_params(interpret,
                                _glu_vmem(k, n, x.dtype.itemsize)),
        interpret=interpret, name="mx_gmm_glu_fwd",
    )(group_of, used, x, w_gate, w_up)


def _glu_dx(dh, a, b, w_gate, w_up, group_of, used, interpret):
    """-> (dx [rows, K], da, db [rows, N])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, n = dh.shape
    k = w_gate.shape[1]
    tile = pl.BlockSpec((TILE, n), _row_tile)
    weight = pl.BlockSpec((1, k, n), _group_block)
    return pl.pallas_call(
        _glu_dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // TILE,),
            in_specs=[tile, tile, tile, weight, weight],
            out_specs=[pl.BlockSpec((TILE, k), _row_tile), tile, tile]),
        out_shape=[jax.ShapeDtypeStruct((rows, k), dh.dtype),
                   jax.ShapeDtypeStruct((rows, n), dh.dtype),
                   jax.ShapeDtypeStruct((rows, n), dh.dtype)],
        compiler_params=_params(interpret,
                                _glu_vmem(k, n, dh.dtype.itemsize)),
        interpret=interpret, name="mx_gmm_glu_dx",
    )(group_of, used, dh, a, b, w_gate, w_up)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _glu(x, w_gate, w_up, group_of, used, interpret):
    h, = _glu_call(x, w_gate, w_up, group_of, used, False, interpret)
    return h


def _glu_fwd(x, w_gate, w_up, group_of, used, interpret):
    h, a, b = _glu_call(x, w_gate, w_up, group_of, used, True, interpret)
    return h, (x, w_gate, w_up, a, b, group_of, used)


def _glu_bwd(interpret, res, dh):
    x, w_gate, w_up, a, b, group_of, used = res
    dx, da, db = _glu_dx(dh, a, b, w_gate, w_up, group_of, used, interpret)
    groups = w_gate.shape[0]
    return (dx, _dw(x, da, group_of, used, groups, interpret),
            _dw(x, db, group_of, used, groups, interpret), None, None)


_glu.defvjp(_glu_fwd, _glu_bwd)


def grouped_glu(x, w_gate, w_up, sizes, group_of, interpret=False):
    """h[r] = silu(x[r] @ w_gate[g(r)]) * (x[r] @ w_up[g(r)]): the two
    products of an expert's SiLU-gated feed-forward and the gate between
    them in one kernel, over the tiles in use. Arguments and layout as
    ``grouped_matmul``'s, w_gate and w_up [G, K, N]; -> [rows, N], rows
    behind the last group holding nothing a caller may read. Its gradient
    is ``mx_gmm_glu_dx`` and ``mx_gmm_dw`` twice. Where the kernels run,
    the caller checks ``glu_fits`` first."""
    if interpret or _use_pallas():
        return _glu(x, w_gate, w_up, group_of.astype(jnp.int32),
                    _used(sizes), bool(interpret))
    return grouped_glu_reference(x, w_gate, w_up, sizes)
