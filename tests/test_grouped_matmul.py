"""``pallas_kernels.grouped_matmul``: the three kernels in interpret mode
against ``jax.lax.ragged_dot`` over the same padded groups: an empty group
(one tile of padding), tiles behind the last in use, and the gradients of
both operands."""
import importlib

import jax
import jax.numpy as jnp
import jax.random as jr
import pytest
from jax import lax

G = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")


@pytest.fixture()
def small_tiles(monkeypatch):
    monkeypatch.setattr(G, "TILE", 32)
    return 32


def _layout(counts, rows, tile):
    counts = jnp.asarray(counts, jnp.int32)
    sizes = jnp.maximum(-(-counts // tile), 1) * tile
    group_of = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(rows), side="right"),
        len(counts) - 1)[::tile].astype(jnp.int32)
    return sizes, group_of


LAYOUTS = pytest.mark.parametrize("counts,tiles", [
    ([40, 0, 70, 5], 11),       # an empty group, four tiles behind the last
    ([32, 32, 32, 32], 4),      # every tile in use, no padding
    ([1, 1, 1, 200], 12),       # one heavy group
])


@LAYOUTS
def test_the_kernels_are_ragged_dot_over_the_padded_groups(small_tiles,
                                                           counts, tiles):
    tile, (k, n) = small_tiles, (64, 48)
    rows = tiles * tile
    sizes, group_of = _layout(counts, rows, tile)
    assert int(sizes.sum()) <= rows and sizes.tolist() == [
        max(-(-c // tile), 1) * tile for c in counts]
    x = jr.normal(jr.PRNGKey(0), (rows, k))
    w = jr.normal(jr.PRNGKey(1), (len(counts), k, n))
    dy = jr.normal(jr.PRNGKey(2), (rows, n))
    laid = (jnp.arange(rows) < int(sizes.sum()))[:, None]

    def loss(product):
        return lambda x, w: jnp.sum(jnp.where(laid, product(x, w), 0) * dy)

    kernel = lambda x, w: G.grouped_matmul(x, w, sizes, group_of,  # noqa: E731
                                           interpret=True)
    plain = lambda x, w: lax.ragged_dot(x, w, sizes)  # noqa: E731
    assert float(jnp.max(jnp.abs(jnp.where(
        laid, kernel(x, w) - plain(x, w), 0)))) < 1e-5
    got = jax.grad(loss(kernel), argnums=(0, 1))(x, w)
    want = jax.grad(loss(plain), argnums=(0, 1))(x, w)
    assert float(jnp.max(jnp.abs(jnp.where(laid, got[0] - want[0], 0)))) \
        < 1e-5
    assert float(jnp.max(jnp.abs(got[1] - want[1]))) < 1e-4
    # off the TPU and not interpreted, it IS ragged_dot
    assert bool(jnp.all(G.grouped_matmul(x, w, sizes, group_of)
                        == plain(x, w)))


def _glu_case(counts, tiles, tile, k=64, n=48):
    """-> (sizes, group_of, x, w_gate, w_up, dh, the rows in use [rows, 1])."""
    rows = tiles * tile
    sizes, group_of = _layout(counts, rows, tile)
    ks = jr.split(jr.PRNGKey(3), 4)
    x = jr.normal(ks[0], (rows, k))
    wg, wu = (jr.normal(kk, (len(counts), k, n)) * k ** -0.5
              for kk in ks[1:3])
    dh = jr.normal(ks[3], (rows, n))
    return (sizes, group_of, x, wg, wu, dh,
            (jnp.arange(rows) < int(sizes.sum()))[:, None])


def _glu_and_grads(glu, x, wg, wu, dh):
    h, back = jax.vjp(glu, x, wg, wu)
    return (h,) + back(dh)


@LAYOUTS
def test_the_gated_pair_is_the_gate_over_two_ragged_dots(small_tiles, counts,
                                                         tiles):
    """``grouped_glu`` in interpret mode: h, dx, dw_gate and dw_up against
    silu(ragged_dot(x, w_gate)) * ragged_dot(x, w_up) over the padded
    groups, on the rows in use; a cotangent of nought on the padding rows
    of the tiles in use (as the down product's backward gives them) and
    behind them."""
    sizes, group_of, x, wg, wu, dh, laid = _glu_case(counts, tiles,
                                                     small_tiles)
    dh = jnp.where(laid, dh, 0)
    got = _glu_and_grads(lambda x, wg, wu: G.grouped_glu(
        x, wg, wu, sizes, group_of, interpret=True), x, wg, wu, dh)
    want = _glu_and_grads(lambda x, wg, wu: jax.nn.silu(
        lax.ragged_dot(x, wg, sizes)) * lax.ragged_dot(x, wu, sizes),
        x, wg, wu, dh)
    for name, g, w in zip(("h", "dx", "dw_gate", "dw_up"), got, want):
        if name in ("h", "dx"):
            g, w = jnp.where(laid, g, 0), jnp.where(laid, w, 0)
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * (
            1.0 + float(jnp.max(jnp.abs(w)))), name
    # off the TPU and not interpreted, it IS the gate over ragged_dot
    assert bool(jnp.all(G.grouped_glu(x, wg, wu, sizes, group_of)
                        == G.grouped_glu_reference(x, wg, wu, sizes)))


@LAYOUTS
def test_what_lies_behind_the_tiles_in_use_reaches_no_row_in_use(
        small_tiles, counts, tiles):
    """NaN in x and in the cotangent on every row behind the tiles in use:
    h and dx on the rows in use, and both weights' gradients, are the same
    as with finite rows there."""
    sizes, group_of, x, wg, wu, dh, laid = _glu_case(counts, tiles,
                                                     small_tiles)
    dh = jnp.where(laid, dh, 0)

    def run(x, dh):
        return _glu_and_grads(lambda x, wg, wu: G.grouped_glu(
            x, wg, wu, sizes, group_of, interpret=True), x, wg, wu, dh)

    clean = run(x, dh)
    dirty = run(jnp.where(laid, x, jnp.nan), jnp.where(laid, dh, jnp.nan))
    for name, c, d in zip(("h", "dx", "dw_gate", "dw_up"), clean, dirty):
        if name in ("h", "dx"):
            c, d = c[:int(sizes.sum())], d[:int(sizes.sum())]
        assert bool(jnp.all(jnp.isfinite(d))), name
        assert bool(jnp.all(c == d)), name


def test_the_kernels_keep_their_names():
    """The trace finds them by ``mx_gmm_``: forward, dx, dw, and the gated
    pair's forward and dx."""
    sizes, group_of = _layout([256, 256], 512, 256)
    x = jnp.ones((512, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(G.grouped_matmul(
            x, w, sizes, group_of, interpret=True).astype(jnp.float32)),
        argnums=(0, 1)))(x, w))
    for name in ("mx_gmm_fwd", "mx_gmm_dx", "mx_gmm_dw"):
        assert name in text
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, wg, wu: jnp.sum(G.grouped_glu(
            x, wg, wu, sizes, group_of, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, w, w))
    for name in ("mx_gmm_glu_fwd", "mx_gmm_glu_dx", "mx_gmm_dw"):
        assert name in text


def test_a_dw_block_too_large_for_vmem_is_taken_in_column_blocks(
        small_tiles, monkeypatch):
    """Where a group's whole dw block, its float32 sum and the tiles miss
    the VMEM cap, ``mx_gmm_dw`` takes the block's columns in passes (here
    two of 128 under a cap set low); the gradients are ragged_dot's all the
    same. At the benchmark's widths only the 7168-wide hidden state splits:
    every other expert cell keeps its one pass."""
    import json
    import os
    monkeypatch.setattr(G, "_VMEM_CAP", G._VMEM_SLACK + 200000)
    assert G._dw_cols(64, 256, 4) == 128
    tile, (k, n), counts = small_tiles, (64, 256), [40, 0, 70, 5]
    rows = 11 * tile
    sizes, group_of = _layout(counts, rows, tile)
    x = jr.normal(jr.PRNGKey(0), (rows, k))
    w = jr.normal(jr.PRNGKey(1), (len(counts), k, n))
    dy = jr.normal(jr.PRNGKey(2), (rows, n))
    laid = (jnp.arange(rows) < int(sizes.sum()))[:, None]

    def loss(product):
        return lambda x, w: jnp.sum(jnp.where(laid, product(x, w), 0) * dy)

    got = jax.grad(loss(lambda x, w: G.grouped_matmul(
        x, w, sizes, group_of, interpret=True)), argnums=1)(x, w)
    want = jax.grad(loss(lambda x, w: lax.ragged_dot(x, w, sizes)),
                    argnums=1)(x, w)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    monkeypatch.undo()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    configs = os.path.join(root, "chipbench", "configs")
    for name in sorted(os.listdir(configs)):
        with open(os.path.join(configs, name)) as f:
            m = json.load(f)
        if "num_experts_held" not in m:
            continue
        d = m["hidden_size"]
        f = m.get("moe_intermediate_size") or m["intermediate_size"]
        split = (d == 7168)
        assert (G._dw_cols(d, f, 2) < f, G._dw_cols(f, d, 2) < d) == (
            split, split), name
    assert (G._dw_cols(7168, 2048, 2), G._dw_cols(2048, 7168, 2)) == (
        1024, 3584)
