"""Grouped matrix products over rows sorted by group: out[r] = x[r] @ w[g(r)].

What an expert layer runs once its token-slots are sorted by expert
(``parallel/expert.py moe_share``). The rows of one group lie together and
every group starts on a tile boundary (its rows padded to whole tiles of
``tile`` rows), so a row tile belongs to exactly one group and the kernels
are plain tiled products whose weight block is picked by a prefetched table:

- ``mx_gmm_fwd``: out[tile] = x[tile] @ w[group of tile]
- ``mx_gmm_dx``:  dx[tile] = dy[tile] @ w[group of tile]^T
- ``mx_gmm_dw``:  dw[g] = sum over the tiles of g of x[tile]^T @ dy[tile]

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
``grouped_matmul`` is ``ragged_dot`` over the padded group sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_matmul", "grouped_matmul_reference", "TILE"]

TILE = 256      # rows of a tile: a group's rows are padded to whole tiles
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def grouped_matmul_reference(x, w, sizes):
    """The same product in plain XLA: ``ragged_dot`` over the padded groups
    (rows behind the last group come out nought)."""
    return lax.ragged_dot(x, w, sizes.astype(jnp.int32))


def _use_pallas():
    return jax.default_backend() == "tpu"


def _params(interpret, vmem_bytes):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(vmem_bytes + (8 << 20), 100 << 20)))


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
        used = (jnp.sum(sizes, dtype=jnp.int32) // TILE).reshape(1)
        return _gmm(x, w, group_of.astype(jnp.int32), used, bool(interpret))
    return grouped_matmul_reference(x, w, sizes)
