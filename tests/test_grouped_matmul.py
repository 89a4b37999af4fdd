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


@pytest.mark.parametrize("counts,tiles", [
    ([40, 0, 70, 5], 11),       # an empty group, four tiles behind the last
    ([32, 32, 32, 32], 4),      # every tile in use, no padding
    ([1, 1, 1, 200], 12),       # one heavy group
])
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


def test_the_kernels_keep_their_names():
    """The trace finds them by ``mx_gmm_``: forward, dx, dw."""
    sizes, group_of = _layout([256, 256], 512, 256)
    x = jnp.ones((512, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(G.grouped_matmul(
            x, w, sizes, group_of, interpret=True).astype(jnp.float32)),
        argnums=(0, 1)))(x, w))
    for name in ("mx_gmm_fwd", "mx_gmm_dx", "mx_gmm_dw"):
        assert name in text
