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
R = 16                              # the MLP router's width


@pytest.fixture(scope="module")
def weights():
    ks = jr.split(jr.PRNGKey(0), 15)
    n = lambda k, shape, fan: jr.normal(k, shape) * fan ** -0.5  # noqa: E731
    return {"x": jr.normal(ks[0], (B, S, D)), "router": n(ks[1], (D, E), D),
            "bias": jr.normal(ks[2], (E,)) * 0.01,
            "w_gate": n(ks[3], (E, D, F), D), "w_up": n(ks[4], (E, D, F), D),
            "w_down": n(ks[5], (E, F, D), F),
            "shared": (n(ks[6], (D, F), D), n(ks[7], (D, F), D),
                       n(ks[8], (F, D), F)),
            "mlp": {"down": n(ks[9], (D, R), D), "gamma": jnp.float32(0.5),
                    "norm": jnp.ones(R), "w1": n(ks[10], (R, R), R),
                    "w2": n(ks[11], (R, R), R), "out": n(ks[12], (R, E), R)},
            "state": jr.normal(ks[13], (B, S, R))}


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _choice(w, bias, route):
    """-> (the K experts [T, K] over ALL experts, their weights [T, K])."""
    xt = w["x"].reshape(-1, D)
    if route == "mlp_softmax":      # held to its reference in
        chosen, weight, _ = X.route_mlp_softmax(
            xt, w["mlp"], bias, K, w["state"].reshape(-1, R), 1e-5)
        return chosen, weight       # test_transformer_zaya.py
    logits = jnp.dot(xt, w["router"], precision="highest")
    if route == "topk_softmax":
        top, chosen = jax.lax.top_k(logits, K)
        return chosen, jax.nn.softmax(top, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * SCALE


def _loop(w, first, held, shared=True, bias=None, route="sigmoid"):
    """The layer by its equations: a loop over the experts held, a mask an
    expert; scores, choice and weights over ALL experts."""
    xt = w["x"].reshape(-1, D)
    chosen, weight = _choice(w, w["bias"] if bias is None else bias, route)
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
    layers, slots, dropped, most, live, inside, to_held, moved = (
        int(v) for v in stats)
    assert (layers, dropped, inside, moved) == (1, 0, 0, 0)  # off the kernels
    assert to_held == 0                              # routed without groups
    assert most <= slots <= B * S * K
    assert slots <= live <= slots + held * 256 and live % 256 == 0
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
    assert [int(v) for v in stats] == [1, B * S, 0, B * S, 4 * 256, 0, 0,
                                       0]
    want = _loop(weights, 4, 4, shared=False, bias=bias)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    # and every slot of every token to the four held: 4 x B*S slots
    bias = jnp.zeros(E).at[jnp.arange(4, 8)].set(10.0)
    y, stats = _share(weights, 4, 4, shared=False, bias=bias)
    assert [int(v) for v in stats] == [1, 4 * B * S, 0, B * S, 4 * 256, 0,
                                       0, 0]
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
    a = jnp.array([1, 10, 0, 7, 512, 1, 0, 1])
    b = jnp.array([2, 5, 1, 9, 768, 2, 30, 0])
    assert X.merge_stats(a, b).tolist() == [3, 15, 1, 9, 1280, 3, 30, 1]
    assert X.sum_stats(jnp.stack([a, b, b])).tolist() == [
        5, 20, 2, 9, 2048, 5, 60, 1]
    assert X.MOE_STATS == ("layers", "slots_held", "slots_dropped",
                           "max_load", "rows_live", "gate_in_kernel",
                           "tokens_to_held_groups", "rows_in_kernel")


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


@pytest.mark.parametrize("route", ["sigmoid", "topk_softmax", "mlp_softmax"])
def test_with_the_gate_inside_the_kernels_it_is_the_loop_for_each_router(
        weights, monkeypatch, route):
    """The share through ``grouped_glu`` (interpret mode, tiles of 32 rows,
    most of the buffer's rows behind the tiles in use) against the loop:
    the output and the gradients of x and of the three experts' matrices.
    The call counts in ``gate_in_kernel``, the same call off the kernels in
    ``gate_apart``."""
    import importlib
    import mxnet_tpu as mx
    gmm = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(gmm, "TILE", 32)
    sl, names = slice(4, 8), ("x", "w_gate", "w_up", "w_down")
    router = weights["mlp"] if route == "mlp_softmax" else weights["router"]

    def share(x, wg, wu, wd, interpret=True):
        return X.moe_share(x, router, weights["bias"], wg, wu, wd, None, k=K,
                           first=4, route_scale=SCALE, route=route,
                           state=weights["state"], interpret=interpret)

    def loop(x, wg, wu, wd):
        w = dict(weights, x=x, w_gate=weights["w_gate"].at[sl].set(wg),
                 w_up=weights["w_up"].at[sl].set(wu),
                 w_down=weights["w_down"].at[sl].set(wd))
        return _loop(w, 4, 4, shared=False, route=route)

    args = (weights["x"],) + tuple(weights[n][sl] for n in names[1:])
    y, stats = share(*args)[:2]
    rows = X.buffer_rows(B * S, K, 4, 32)
    assert int(stats[4]) < 0.5 * rows
    assert float(jnp.max(jnp.abs(y - loop(*args)))) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(share(*a)[0])),
                   argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(loop(*a))),
                    argnums=(0, 1, 2, 3))(*args)
    for name, g, w in zip(names, got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 5e-5, name
    apart = share(*args, interpret=False)[1]
    assert (int(stats[5]), int(apart[5])) == (1, 0)
    before = mx.profiler.metrics()["moe"]
    holder = _Holder(X.merge_stats(stats, stats), 4, rows)
    X.track(holder)
    after = mx.profiler.metrics()["moe"]
    assert after["gate_in_kernel"] - before["gate_in_kernel"] == 2
    assert after["gate_apart"] == before["gate_apart"]
    holder.moe_counters = apart
    assert mx.profiler.metrics()["moe"]["gate_apart"] \
        - before["gate_apart"] == 1
    holder.moe_counters = None


# -- the row movements: the mx_moe_* kernels against the plain gathers -------

TILE_T, DM = 32, 64                 # rows of a buffer tile; the rows' width
N_E, N_HELD, FIRST = 16, 4, 4


def _routing(case, T):
    """[T, K] experts over N_E, and (first, experts held)."""
    spread = jnp.argsort(jr.uniform(jr.PRNGKey(7), (T, N_E)), axis=-1)[:, :K]
    if case == "one_expert":        # every slot there is
        return jnp.full((T, K), FIRST + 1, jnp.int32), FIRST, N_HELD
    if case == "none_held":         # a tile of padding an expert, no more
        return spread % FIRST, FIRST, N_HELD
    if case == "all_experts":
        return spread, 0, N_E
    return spread, FIRST, N_HELD    # "even", "ragged_tokens"


@pytest.fixture
def small_tiles(monkeypatch):
    """Token tiles of 32 (128 words of slots at K = 4), so that a test's
    tokens are several tiles of ``mx_moe_sum``; the interpreter has no
    block granule in SMEM to keep."""
    from mxnet_tpu.pallas_kernels import moe_rows
    monkeypatch.setattr(moe_rows, "_SMEM_WORDS", 128)
    assert moe_rows.tokens_of(K) == 32 and moe_rows.tokens_of(8) == 16


def _planned(case, T, kernels):
    experts, first, n_held = _routing(case, T)
    plan = X._plan(experts.astype(jnp.int32), first, n_held, TILE_T)
    if kernels:
        from mxnet_tpu.pallas_kernels import moe_rows
        lists, fetched = moe_rows.tile_lists(plan.row_of, plan.held,
                                             plan.slot_of.shape[0])
        plan = plan._replace(lists=lists, fetched=fetched)
    return plan, ((TILE_T, True) if kernels else None)


def _operands(plan, T, dtype, width=DM):
    """The movements' operands. Past ``DM`` columns the weights are eighths:
    a bfloat16 element times such a weight is exact in float32, so a sum is
    the same to the bit whether or not XLA's CPU fuses a multiply into the
    add after it (it does in the interpreted kernels, not in ``jnp.take``'s
    sums, and at 768 columns one lane of 98,304 then rounds otherwise)."""
    ks = jr.split(jr.PRNGKey(11), 5)
    rows = plan.slot_of.shape[0]
    w = jr.uniform(ks[2], (T, K)) + 0.1
    return {"x": jr.normal(ks[0], (T, width)).astype(dtype),
            "ys": jr.normal(ks[1], (rows, width)).astype(dtype),
            "w": w if width == DM else jnp.round(w * 8) / 8,
            "g_rows": jr.normal(ks[3], (rows, width)).astype(dtype),
            "g_tokens": jr.normal(ks[4], (T, width)).astype(dtype)}


def _movements(plan, how, o, dtype):
    """Both movements and their transposes -> (xs, dx, y, dys, dw); rows
    behind the tiles in use are cut off: nothing is promised of them."""
    in_use = int(plan.used[0]) * TILE_T
    xs, back = jax.vjp(lambda x: X._dispatch(x, plan, how), o["x"])
    dx, = back(o["g_rows"])
    y, back = jax.vjp(lambda ys, w: X._combine(ys, w, plan, how, dtype),
                      o["ys"], o["w"])
    dys, dw = back(o["g_tokens"])
    return xs[:in_use], dx, y, dys[:in_use], dw


MOVES = [("even", 128), ("one_expert", 128), ("none_held", 128),
         ("all_experts", 128), ("ragged_tokens", 80)]
# rows of 64 columns pack into fewer than 128 lanes; bfloat16 rows of 768
# and 1792 columns into 128 lanes and 3 and 7 sublanes, laid at a stride
# of 8: rows that are not whole (8, 128) tiles, as hidden 7168's 28
WIDTHS = [("bfloat16", DM), ("float32", DM), ("bfloat16", 768),
          ("bfloat16", 1792)]


@pytest.mark.parametrize("dtype,width", WIDTHS, ids=[
    d if w == DM else "%s-%d" % (d, w) for d, w in WIDTHS])
@pytest.mark.parametrize("case,T", MOVES, ids=[c for c, _ in MOVES])
def test_the_kernels_move_the_rows_the_gathers_move(small_tiles, case, T,
                                                     dtype, width):
    from mxnet_tpu.pallas_kernels import moe_rows
    dtype = jnp.dtype(dtype)
    plan, how = _planned(case, T, kernels=True)
    plain, _ = _planned(case, T, kernels=False)
    o = _operands(plan, T, dtype, width)
    _, lanes, sublanes, stride = moe_rows._words(width, dtype)
    assert stride == (8 if width > DM else sublanes)
    used, rows = int(plan.used[0]), plan.slot_of.shape[0]
    assert used * TILE_T == int(jnp.sum(plan.sizes))
    if case == "one_expert":        # all but that expert's spare tile
        assert (used + 1) * TILE_T == rows
    if case == "none_held":
        assert used == N_HELD and not bool(jnp.any(plan.held))
    got = _movements(plan, how, o, dtype)
    want = _movements(plain, None, o, dtype)
    exact = dtype == jnp.bfloat16   # float32: XLA's CPU fuses a multiply-add
    for name, g, w in zip(("xs", "dx", "y", "dys", "dw"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        if name == "xs" or (exact and name in ("dx", "y", "dys")):
            assert bool(jnp.all(g == w)), name          # to the bit
        else:
            assert float(jnp.max(jnp.abs(g - w))) <= 2e-5 * (
                1.0 + float(jnp.max(jnp.abs(w)))), name


def _nothing_not_finite(width):
    """NaN behind the tiles in use, in the rows of tokens none of whose
    slots is held, and in the cotangents behind the tiles in use: every
    output is finite, the padding rows of the tiles in use included."""
    T, dtype = 128, jnp.bfloat16
    plan, how = _planned("even", T, kernels=True)
    o = _operands(plan, T, dtype, width)
    in_use = int(plan.used[0]) * TILE_T
    assert in_use < plan.slot_of.shape[0] - TILE_T
    behind = (jnp.arange(plan.slot_of.shape[0]) >= in_use)[:, None]
    unused = ~jnp.any(plan.held, axis=-1).at[0].set(True)   # token 0 pads
    o = dict(o, x=jnp.where(unused[:, None], jnp.nan, o["x"]),
             ys=jnp.where(behind, jnp.nan, o["ys"]),
             g_rows=jnp.where(behind, jnp.nan, o["g_rows"]))
    for name, v in zip(("xs", "dx", "y", "dys", "dw"),
                       _movements(plan, how, o, dtype)):
        assert bool(jnp.all(jnp.isfinite(v.astype(jnp.float32)))), name
    padding = ~plan.live[:in_use]
    assert bool(jnp.any(padding))
    xs, _, _, dys, _ = _movements(plan, how, o, dtype)
    assert bool(jnp.all(xs[padding] == o["x"][0]))
    assert bool(jnp.all(dys[padding] == 0))


def test_nothing_not_finite_comes_in_from_the_rows_no_slot_uses(small_tiles):
    _nothing_not_finite(DM)


def test_nothing_not_finite_comes_in_from_a_packed_rows_spare_sublanes(
        small_tiles, monkeypatch):
    """The same at 1792 columns, 7 sublanes a row at a stride of 8: what
    the pack leaves in a row's eighth sublane (NaN here, as the packs are
    made) reaches no output."""
    from mxnet_tpu.pallas_kernels import moe_rows
    pack = moe_rows.pack_rows

    def spoilt(x, used, tile, interpret=False):
        packed = pack(x, used, tile, interpret)
        spare = jnp.arange(packed.shape[0]) % 8 == 7
        return jnp.where(spare[:, None], jnp.uint32(0x7fc07fc0), packed)

    monkeypatch.setattr(moe_rows, "pack_rows", spoilt)
    assert moe_rows._words(1792, jnp.bfloat16)[2:] == (7, 8)
    _nothing_not_finite(1792)


def test_rows_pack_into_128_lanes_at_a_stride_of_whole_tiles():
    """On the TPU a row fits where it packs into whole words of 128 lanes:
    hidden 7168 in bfloat16 (28 sublanes) now does, at a stride of 32. At
    hidden 2048 and 4096 (cells with experts at 8 and 16 sublanes) the
    stride is the row itself: their packs are the layout there was."""
    from mxnet_tpu.pallas_kernels import moe_rows
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert moe_rows.fits(7168, bf16, True)
    assert moe_rows._words(7168, bf16) == (3584, 128, 28, 32)
    for width, sublanes in ((2048, 8), (4096, 16)):
        assert moe_rows._words(width, bf16)[2:] == (sublanes, sublanes)
        assert moe_rows.fits(width, bf16, True)
    assert moe_rows._words(4096, f32)[2:] == (32, 32)
    # fewer than 128 lanes, or words that are not whole: the interpreter's
    assert not moe_rows.fits(64, bf16, True) and moe_rows.fits(64, bf16, False)
    assert not moe_rows.fits(7169, bf16, False)
    assert not moe_rows.fits(1000, bf16, True)
    assert not moe_rows.fits(2048, jnp.float16, False)


def test_rows_in_kernel_counts_the_calls_whose_rows_the_kernels_moved(
        weights, monkeypatch):
    """``rows_in_kernel``, the eighth counter: 1 for a share whose rows
    move through the ``mx_moe_*`` kernels (interpret mode), 0 on the plain
    gathers; ``metrics()["moe"]["rows_apart"]`` is ``layers`` less it."""
    import importlib
    import mxnet_tpu as mx
    gmm = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(gmm, "TILE", 32)
    sl = slice(4, 8)

    def stats(interpret):
        return X.moe_share(weights["x"], weights["router"], weights["bias"],
                           weights["w_gate"][sl], weights["w_up"][sl],
                           weights["w_down"][sl], None, k=K, first=4,
                           route_scale=SCALE, interpret=interpret)[1]

    moved, apart = stats(True), stats(False)
    assert (int(moved[7]), int(apart[7])) == (1, 0)
    rows = X.buffer_rows(B * S, K, 4, 32)
    before = mx.profiler.metrics()["moe"]
    holder = _Holder(X.sum_stats(jnp.stack([moved, moved, apart])), 4, rows)
    X.track(holder)
    after = mx.profiler.metrics()["moe"]
    assert after["layers"] - before["layers"] == 3
    assert after["rows_in_kernel"] - before["rows_in_kernel"] == 2
    assert after["rows_apart"] - before["rows_apart"] == 1
    assert after["rows_apart"] == after["layers"] - after["rows_in_kernel"]
    holder.moe_counters = None


def test_the_share_through_the_kernels_is_the_share_through_the_gathers(
        weights, monkeypatch):
    """``moe_share`` in bfloat16 with every kernel in interpret mode against
    the same call on the plain forms: the output and the gradient of x."""
    import importlib
    gmm = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(gmm, "TILE", 32)
    w16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), weights)
    sl = slice(4, 8)

    def share(x, interpret):
        return X.moe_share(x, w16["router"], w16["bias"], w16["w_gate"][sl],
                           w16["w_up"][sl], w16["w_down"][sl], None, k=K,
                           first=4, route_scale=SCALE, interpret=interpret)

    (y, stats), (want, _) = share(w16["x"], True), share(w16["x"], False)
    assert float(jnp.max(jnp.abs((y - want).astype(jnp.float32)))) < 0.05
    assert int(stats[4]) % 32 == 0 and int(stats[1]) <= int(stats[4])
    grad = lambda i: jax.grad(lambda x: jnp.sum(  # noqa: E731
        share(x, i)[0].astype(jnp.float32)))(w16["x"])
    assert float(jnp.max(jnp.abs((grad(True) - grad(False)).astype(
        jnp.float32)))) < 0.1


class _Holder:
    """What ``expert.track`` asks of a train step."""

    def __init__(self, counters, held, rows):
        self.moe_counters, self.moe_held, self.moe_rows = counters, held, rows


@pytest.mark.parametrize("first,held,share", [(4, 4, (0.15, 0.6)),
                                              (0, 16, (0.9, 1.0))])
def test_metrics_say_how_much_of_the_buffer_the_steps_used(
        weights, monkeypatch, first, held, share):
    """``metrics()["moe"]``: ``rows_live`` summed over layers and steps, and
    ``live_share`` of the buffers' rows: a quarter of the experts use about
    a quarter of the buffer and a tile an expert, all of them all of it but
    the spare padding."""
    import importlib
    import mxnet_tpu as mx
    gmm = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(gmm, "TILE", 4)
    _, stats = _share(weights, first, held)
    rows = X.buffer_rows(B * S, K, held, 4)
    before = mx.profiler.metrics()["moe"]
    holder = _Holder(X.merge_stats(stats, stats), held, rows)
    X.track(holder)
    after = mx.profiler.metrics()["moe"]
    assert after["layers"] - before["layers"] == 2
    assert after["rows_live"] - before["rows_live"] == 2 * int(stats[4])
    if before["layers"] == 0:
        assert share[0] < after["live_share"] <= share[1]
    assert share[0] < int(stats[4]) / rows <= share[1]
    holder.moe_counters = None
