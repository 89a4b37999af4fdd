"""``parallel.expert.moe_share``: one chip's share of an expert layer. It
routes over all experts, computes the terms of the experts it holds with
grouped products over sorted slots, and drops nothing whatever the
imbalance; the four shares' routed parts plus the shared expert once are
the whole layer."""
import jax
import jax.numpy as jnp
import jax.random as jr
import pytest

from mxnet_tpu.parallel import expert as X

B, S, D, E, F, K = 2, 64, 32, 16, 24, 4
SCALE = 2.5


@pytest.fixture(scope="module")
def weights():
    ks = jr.split(jr.PRNGKey(0), 9)
    n = lambda k, shape, fan: jr.normal(k, shape) * fan ** -0.5  # noqa: E731
    return {"x": jr.normal(ks[0], (B, S, D)), "router": n(ks[1], (D, E), D),
            "bias": jr.normal(ks[2], (E,)) * 0.01,
            "w_gate": n(ks[3], (E, D, F), D), "w_up": n(ks[4], (E, D, F), D),
            "w_down": n(ks[5], (E, F, D), F),
            "shared": (n(ks[6], (D, F), D), n(ks[7], (D, F), D),
                       n(ks[8], (F, D), F))}


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _loop(w, first, held, shared=True, bias=None):
    """The layer by its equations: a loop over the experts held, a mask an
    expert; scores, choice and weights over ALL experts."""
    xt = w["x"].reshape(-1, D)
    scores = jax.nn.sigmoid(jnp.dot(xt, w["router"], precision="highest"))
    _, chosen = jax.lax.top_k(scores + (w["bias"] if bias is None else bias),
                              K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) * SCALE
    y = _gated(xt, *w["shared"]) if shared else jnp.zeros_like(xt)
    for e in range(first, first + held):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * _gated(xt, w["w_gate"][e], w["w_up"][e],
                                      w["w_down"][e])
    return y.reshape(w["x"].shape)


def _share(w, first, held, shared=True, bias=None):
    sl = slice(first, first + held)
    return X.moe_share(w["x"], w["router"], w["bias"] if bias is None
                       else bias, w["w_gate"][sl], w["w_up"][sl],
                       w["w_down"][sl], w["shared"] if shared else None, k=K,
                       first=first, route_scale=SCALE)


@pytest.mark.parametrize("first,held", [(4, 4), (0, 8), (12, 4), (0, 16)])
def test_a_share_is_the_loop_over_the_experts_it_holds(weights, first, held):
    y, stats = _share(weights, first, held)
    assert float(jnp.max(jnp.abs(y - _loop(weights, first, held)))) < 1e-5
    layers, slots, dropped, most = (int(v) for v in stats)
    assert (layers, dropped) == (1, 0) and most <= slots <= B * S * K
    if held == E:
        assert slots == B * S * K          # every slot is of an expert held


def test_its_gradients_are_the_loops(weights):
    names = ("x", "router", "w_gate", "w_up", "w_down", "shared")

    def of(fn):
        def loss(*leaves):
            return jnp.sum(jnp.sin(fn(dict(weights, **dict(zip(names,
                                                               leaves))))))
        return jax.grad(loss, argnums=tuple(range(len(names))))(
            *(weights[n] for n in names))

    got = of(lambda w: _share(w, 4, 4)[0])
    want = of(lambda w: _loop(w, 4, 4))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) < 5e-5
    # experts it does not hold get no gradient from it, the router does
    assert float(jnp.max(jnp.abs(got[2][:4]))) == 0.0
    assert float(jnp.max(jnp.abs(got[1]))) > 0.0


def test_routing_as_uneven_as_it_can_be_drops_nothing(weights):
    """The bias sends every token to one held expert (and its other three
    slots to experts held elsewhere): that expert computes all B*S slots."""
    bias = jnp.zeros(E).at[5].set(10.0).at[jnp.array([0, 1, 2])].set(5.0)
    y, stats = _share(weights, 4, 4, shared=False, bias=bias)
    assert [int(v) for v in stats] == [1, B * S, 0, B * S]
    want = _loop(weights, 4, 4, shared=False, bias=bias)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    # and every slot of every token to the four held: 4 x B*S slots
    bias = jnp.zeros(E).at[jnp.arange(4, 8)].set(10.0)
    y, stats = _share(weights, 4, 4, shared=False, bias=bias)
    assert [int(v) for v in stats] == [1, 4 * B * S, 0, B * S]
    assert float(jnp.max(jnp.abs(
        y - _loop(weights, 4, 4, shared=False, bias=bias)))) < 1e-5


def test_the_shares_sum_to_the_model(weights):
    """The four shares' routed parts plus the shared expert ONCE are the
    uncut layer: what the exchange between the four chips would add up."""
    whole = _loop(weights, 0, E)
    parts = sum(_share(weights, first, 4, shared=False)[0]
                for first in range(0, E, 4))
    parts = parts + _gated(weights["x"].reshape(-1, D),
                           *weights["shared"]).reshape(whole.shape)
    assert float(jnp.max(jnp.abs(parts - whole))) < 1e-5
    slots = sum(int(_share(weights, first, 4)[1][1])
                for first in range(0, E, 4))
    assert slots == B * S * K


def test_counters_merge_by_sum_and_by_largest_load():
    a, b = jnp.array([1, 10, 0, 7]), jnp.array([2, 5, 1, 9])
    assert X.merge_stats(a, b).tolist() == [3, 15, 1, 9]
    assert X.MOE_STATS == ("layers", "slots_held", "slots_dropped",
                           "max_load")


def test_through_the_grouped_product_kernels_it_is_the_loop_too(weights,
                                                                monkeypatch):
    """The same share with the Pallas kernels (interpret mode, tiles of 32
    rows): every group padded to whole tiles, the heavy expert's rows over
    several of them, the tiles behind the last one never read."""
    import importlib
    gmm = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(gmm, "TILE", 32)
    bias = jnp.zeros(E).at[5].set(10.0)        # every token to expert 5
    sl = slice(4, 8)

    def share(x):
        return X.moe_share(x, weights["router"], bias, weights["w_gate"][sl],
                           weights["w_up"][sl], weights["w_down"][sl], None,
                           k=K, first=4, route_scale=SCALE, interpret=True)

    y, stats = share(weights["x"])
    assert int(stats[2]) == 0 and int(stats[3]) == B * S
    want = _loop(weights, 4, 4, shared=False, bias=bias)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    got = jax.grad(lambda x: jnp.sum(jnp.sin(share(x)[0])))(weights["x"])
    ref = jax.grad(lambda x: jnp.sum(jnp.sin(_loop(
        dict(weights, x=x), 4, 4, shared=False, bias=bias))))(weights["x"])
    assert float(jnp.max(jnp.abs(got - ref))) < 5e-5
