"""Expert parallelism: Mixture-of-Experts FFN sharded over the 'ep' axis.

Not present in the reference (2019-era, SURVEY.md §2.4 item 7) but part of
the required capability surface. Design: experts live on the 'ep' mesh axis
(weights [E, ...] sharded P('ep', ...)); routing is computed densely and
tokens reach their experts via einsum dispatch/combine (Shazeer et al.
arXiv:1701.06538, GShard arXiv:2006.16668). GSPMD turns the dispatch einsum
into an all-to-all over ICI. Dense dispatch keeps shapes static — the XLA
requirement — with capacity_factor bounding per-expert load.

``moe_share`` is the other kind of expert layer: one chip's share of an
expert-parallel layer. It is told which experts it holds (a contiguous
range), routes over all of them (sigmoid scores, top-k of score + bias,
inside each token's best groups of experts where the routing is limited to
groups, weights normalised over the k chosen; or the k largest logits and a
softmax over those; or an MLP with a state carried from layer to layer, a
softmax over all experts, the chosen probabilities as they are), sorts the
token-slots by expert, runs
grouped products over the slots of the experts held
(``pallas_kernels/grouped_matmul.py``) and gathers the weighted results
back. No capacity and no drops: the slot buffer holds every slot there is,
and slots of absent experts get no row and cost no product. What the absent
experts would add is left out: the exchange between chips is not this
layer's.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler as _profiler

__all__ = ["top_k_routing", "moe_ffn", "MoELayer", "route_sigmoid",
           "route_topk_softmax", "route_mlp_softmax", "moe_share",
           "MOE_STATS", "ROUTER_MLP"]


def top_k_routing(logits, k=2, capacity=None):
    """Token->expert assignment with capacity. logits: [T, E].

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights, aux_loss).
    aux_loss is the load-balancing loss (mean_prob * mean_assignment * E).
    """
    T, E = logits.shape
    if capacity is None:
        capacity = max(1, (k * T + E - 1) // E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)            # [T, k]
    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)     # [T, k, E]
    # cumulative count per expert across (token, choice) in order
    flat = onehot.reshape(T * k, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat          # [T*k, E]
    pos = (pos_in_expert * flat).sum(-1).reshape(T, k)       # [T, k]
    keep = pos < capacity
    gates = gates * keep
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates / denom
    disp = jnp.zeros((T, E, capacity), jnp.float32)
    comb = jnp.zeros((T, E, capacity), jnp.float32)
    t_idx = jnp.arange(T)[:, None].repeat(k, 1)
    disp = disp.at[t_idx, experts, jnp.clip(pos, 0, capacity - 1)].add(
        keep.astype(jnp.float32))
    comb = comb.at[t_idx, experts, jnp.clip(pos, 0, capacity - 1)].add(
        gates * keep)
    # load-balance aux loss
    me = probs.mean(0)                                   # [E]
    ce = flat.reshape(T, k, E).sum(1).astype(jnp.float32).mean(0)
    aux = (me * ce).sum() * E
    return disp, comb, aux


def moe_ffn(x, router_w, w1, w2, k=2, capacity_factor=1.25,
            activation=jax.nn.gelu):
    """MoE FFN. x: [B, S, D]; router_w: [D, E]; w1: [E, D, F]; w2: [E, F, D].
    Shard w1/w2 P('ep', None, 'tp')/P('ep', 'tp', None) for ep x tp."""
    B, S, D = x.shape
    E = router_w.shape[1]
    T = B * S
    xt = x.reshape(T, D)
    logits = xt @ router_w                               # [T, E]
    capacity = max(1, int(capacity_factor * k * T / E))
    disp, comb, aux = top_k_routing(logits, k=k, capacity=capacity)
    # dispatch: [E, C, D] expert inputs (GSPMD: all-to-all over 'ep')
    xe = jnp.einsum("td,tec->ecd", xt, disp)
    h = activation(jnp.einsum("ecd,edf->ecf", xe, w1))
    ye = jnp.einsum("ecf,efd->ecd", h, w2)
    yt = jnp.einsum("ecd,tec->td", ye, comb)
    return yt.reshape(B, S, D), aux


class MoELayer:
    """Functional MoE layer bundle (params created via init())."""

    def __init__(self, dim, hidden, num_experts, k=2, capacity_factor=1.25):
        self.dim, self.hidden = dim, hidden
        self.num_experts, self.k = num_experts, k
        self.capacity_factor = capacity_factor

    def init(self, key):
        import jax.random as jr
        k1, k2, k3 = jr.split(key, 3)
        scale = self.dim ** -0.5
        return {
            "router": jr.normal(k1, (self.dim, self.num_experts)) * scale,
            "w1": jr.normal(k2, (self.num_experts, self.dim,
                                 self.hidden)) * scale,
            "w2": jr.normal(k3, (self.num_experts, self.hidden,
                                 self.dim)) * (self.hidden ** -0.5),
        }

    def __call__(self, params, x):
        return moe_ffn(x, params["router"], params["w1"], params["w2"],
                       k=self.k, capacity_factor=self.capacity_factor)


# -- one chip's share of an expert-parallel layer ----------------------------

MOE_STATS = ("layers", "slots_held", "slots_dropped", "max_load",
             "rows_live", "gate_in_kernel", "tokens_to_held_groups",
             "rows_in_kernel")
_LARGEST = tuple(n == "max_load" for n in MOE_STATS)   # the rest are sums


def kept_groups(biased, groups, kept):
    """[T, groups] bool: each token's ``kept`` groups of its experts
    [T, E] biased scores, the best by the sum of a group's two largest."""
    T, E = biased.shape
    top2 = lax.top_k(biased.reshape(T, groups, E // groups), 2)[0]
    _, best = lax.top_k(jnp.sum(top2, axis=-1), kept)
    return jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)


def route_sigmoid(h, router_w, bias, k, route_scale=1.0, groups=(1, 1)):
    """Scores over ALL experts in float32, the k chosen by score + bias
    (``bias`` is a buffer: it selects and carries no gradient), weights the
    chosen scores normalised over the k and scaled. h: [T, D]; router_w:
    [D, E]; bias: [E]. ``groups`` (n, kept): the E experts in n groups, the
    k chosen inside each token's ``kept_groups`` only (an expert outside
    them is never chosen); (1, 1) is every expert. -> (experts [T, k]
    int32, weights [T, k] float32, the kept groups [T, n] bool or None
    where n is 1)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased = scores + lax.stop_gradient(bias.astype(jnp.float32))
    kept = None
    if groups[0] > 1:
        kept = kept_groups(biased, *groups)
        biased = jnp.where(jnp.repeat(kept, biased.shape[1] // groups[0],
                                      axis=1), biased, -jnp.inf)
    _, experts = lax.top_k(biased, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * route_scale, kept


def route_topk_softmax(h, router_w, k):
    """Logits over ALL experts in float32, the k largest chosen, weights a
    softmax over those k logits; no bias. h: [T, D]; router_w: [D, E].
    -> (experts [T, k] int32, weights [T, k] float32)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    chosen, experts = lax.top_k(logits, k)
    return experts, jax.nn.softmax(chosen, axis=-1)


# the leaves of ``route_mlp_softmax``'s router, as ``moe_share`` takes them
ROUTER_MLP = ("down", "gamma", "norm", "w1", "w2", "out")


def route_mlp_softmax(h, router, bias, k, state, eps):
    """A router that is an MLP with a state: r = h W_down + gamma * (the r
    of the layer before), in the activations' product and float32 from
    there on; p = softmax(gelu(gelu(rmsnorm(r) * norm W1) W2) W_out) over
    ALL experts; the k chosen by p + bias (``bias`` is a buffer: it selects
    and carries no gradient); weights the chosen probabilities AS THEY ARE:
    normalised over the k they would read 1 at k = 1 and the router would
    get no gradient. h: [T, D]; router: {``ROUTER_MLP``: down [D, R], gamma
    [], norm [R], w1, w2 [R, R], out [R, E]}; state: [T, R] float32.
    -> (experts [T, k] int32, weights [T, k] float32, r [T, R] float32)."""
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
    r = jnp.dot(h, router["down"], preferred_element_type=f32) \
        + router["gamma"].astype(f32) * state
    a = r * lax.rsqrt(jnp.mean(jnp.square(r), -1, keepdims=True) + eps) \
        * router["norm"].astype(f32)
    for name in ("w1", "w2"):
        a = jax.nn.gelu(dot(a, router[name].astype(f32)), approximate=False)
    p = jax.nn.softmax(dot(a, router["out"].astype(f32)), axis=-1)
    _, experts = lax.top_k(p + lax.stop_gradient(bias.astype(f32)), k)
    return experts, jnp.take_along_axis(p, experts, axis=-1), r


def buffer_rows(tokens, k, n_held, tile):
    """Rows of a share's slot buffer: every slot there is, and a tile of
    padding an expert held."""
    return -(-tokens * k // tile) * tile + n_held * tile


class _Plan(NamedTuple):
    """Where each token-slot goes (``_plan``). held, row_of: [T, k];
    slot_of, live: [rows]; sizes: [n_held]; group_of: [rows / tile]; used:
    [1]; lists, fetched: ``moe_rows.tile_lists`` (None off the kernels)."""
    held: jax.Array
    row_of: jax.Array
    slot_of: jax.Array
    live: jax.Array
    counts: jax.Array
    sizes: jax.Array
    group_of: jax.Array
    used: jax.Array
    lists: Optional[jax.Array] = None
    fetched: Optional[jax.Array] = None


def _plan(experts, first, n_held, tile):
    """Where each token-slot goes. experts: [T, k] over all E. The slots of
    the experts held ([first, first + n_held)) are sorted by expert and laid
    into a buffer an expert's group after the other, each group padded to
    whole tiles of ``tile`` rows (and at least one: ``grouped_matmul``'s
    layout). The buffer has a row for every slot there is, so nothing is
    dropped whatever the imbalance; the slots of absent experts get no row.

    What a step's routing fills is a PREFIX of the buffer: ``used`` =
    sum(sizes) / tile tiles. That is all a step touches. Rows behind it
    hold nothing and are neither read nor written, by the row movements
    here as by the grouped products; the padding rows of the tiles in use
    are read (a group's product runs over whole tiles) and are finite: they
    hold token 0's row on the way in and nought on the way back.
    -> ``_Plan``: held [T, k] bool, row_of [T, k]: a held slot's row (0 for
        the others), slot_of [rows]: the slot in each row (0 where ``live``
        [rows] is false: padding, or behind the last group), counts and
        sizes [n_held]: each group's slots and rows, group_of [rows / tile]:
        each tile's expert, used [1]: the tiles in use."""
    T, k = experts.shape
    whole = T * k
    rows = buffer_rows(T, k, n_held, tile)
    local = experts - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).reshape(whole).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    sizes = jnp.maximum(-(-counts // tile), 1) * tile
    sorted_from = jnp.cumsum(counts) - counts     # a group's first sorted slot
    row_from = jnp.cumsum(sizes) - sizes          # ... and its first row
    mine = jnp.minimum(key, n_held - 1)
    row_of = jnp.where(held.reshape(whole),
                       row_from[mine] + place - sorted_from[mine], 0)
    r = jnp.arange(rows, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(jnp.cumsum(sizes), r, side="right",
                                         method="compare_all"),
                        n_held - 1).astype(jnp.int32)
    rank = r - row_from[group]
    live = rank < counts[group]
    slot_of = jnp.where(live, order[jnp.clip(sorted_from[group] + rank, 0,
                                             whole - 1)], 0)
    return _Plan(held, row_of.reshape(T, k), slot_of, live, counts, sizes,
                 group[::tile],
                 (jnp.sum(sizes, dtype=jnp.int32) // tile).reshape(1))


# The row movements. ``how`` is None for the plain ``jnp.take`` forms (off
# the TPU, and what the tests hold the kernels to) or (tile, interpret) for
# the ``mx_moe_*`` kernels (``pallas_kernels/moe_rows.py``), which move and
# sum the rows of the slots held and no others.

def _slot_sums(table, weights, plan, how, dtype):
    """[T, D]: sum over a token's held slots j = 0 .. k-1, in that order and
    in float32, of weights[t, j] * table[row_of[t, j]]."""
    from ..pallas_kernels import moe_rows as _rows
    if how is None:
        return _rows.sum_rows_reference(table, plan.row_of, plan.held,
                                        weights, dtype)
    tile, interpret = how
    packed = _rows.pack_rows(table, plan.used, tile, interpret)
    return _rows.sum_rows(packed, plan.lists, plan.fetched,
                          jnp.where(plan.held, weights, 0), table.shape[1],
                          dtype, interpret=interpret)


def _token_rows(x, plan, how, scale=None, other=None):
    """[rows, D]: each buffer row's token's row of ``x`` [T, D] (times
    ``scale`` [rows]); with ``other`` [rows, D] also its dot with that
    row, [rows] float32."""
    from ..pallas_kernels import moe_rows as _rows
    token = plan.slot_of // plan.row_of.shape[1]
    if how is None:
        return _rows.gather_rows_reference(x, token, scale, other)
    tile, interpret = how
    every = jnp.full((1,), -(-x.shape[0] // tile), jnp.int32)
    packed = _rows.pack_rows(x, every, tile, interpret)
    return _rows.gather_rows(packed, token, plan.used, tile, x.shape[1],
                             x.dtype, scale, other, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, plan, how):
    """Rows of ``x`` [T, D] in slot order [rows, D]: a gather. Its transpose
    is a gather too: a token's gradient is the sum over its own slots'
    rows, so no scatter runs in either direction."""
    return _token_rows(x, plan, how)


def _dispatch_fwd(x, plan, how):
    return _dispatch(x, plan, how), plan


def _dispatch_bwd(how, plan, g):
    ones = jnp.ones(plan.held.shape, jnp.float32)
    return _slot_sums(g, ones, plan, how, g.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(ys, weights, plan, how, dtype):
    """y[t] = sum over the token's held slots of weight * ys[row]: a gather
    by ``row_of``. Rows that are not ``live`` (padding and what lies behind
    the last group, where no product ran) are never read."""
    return _slot_sums(ys, weights, plan, how, dtype)


def _combine_fwd(ys, weights, plan, how, dtype):
    return _combine(ys, weights, plan, how, dtype), (ys, weights, plan)


def _combine_bwd(how, dtype, res, g):
    """dys[row] = weight of the row's slot * g[its token] (nought on
    padding); dw[t, j] = ys[row] . g[t], taken where g[t] already lies
    beside ys[row], in slot order, and carried back as a gather of
    scalars."""
    ys, weights, plan = res
    w_row = jnp.where(plan.live, jnp.take(weights.reshape(-1), plan.slot_of),
                      0)
    dys, dots = _token_rows(g, plan, how, w_row, ys)
    dw = jnp.where(plan.held, jnp.take(dots, plan.row_of), 0)
    return dys, dw.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gated(x, w_gate, w_up, w_down, product):
    """A three-matrix SiLU-gated feed-forward under ``product(a, w)``."""
    return product(jax.nn.silu(product(x, w_gate)) * product(x, w_up),
                   w_down)


def moe_share(x, router_w, bias, w_gate, w_up, w_down, shared=None, *, k,
              first=0, route_scale=1.0, route="sigmoid", state=None,
              eps=1e-5, groups=(1, 1), interpret=False):
    """The share of an expert layer that holds experts ``first`` to
    ``first + w_gate.shape[0]`` of the ``router_w.shape[1]`` routed over.

    x: [B, S, D]; router_w: [D, E]; bias: [E] (``route`` "sigmoid":
    ``route_sigmoid``; "topk_softmax": ``route_topk_softmax``, which has
    none and takes no ``route_scale``; "mlp_softmax":
    ``route_mlp_softmax``, for which ``router_w`` is that router's leaves,
    ``state`` [B, S, R] the layer before's r, ``eps`` its norm's, and the
    layer's own r [B, S, R] is returned as a third result); ``groups`` (n,
    kept): "sigmoid"'s limit to groups, refused with another ``route``;
    w_gate, w_up: [held, D, F];
    w_down: [held, F, D]; ``shared``: (w_gate [D, Fs], w_up, w_down
    [Fs, D]) of the shared expert, computed for every token.
    -> (y [B, S, D]: shared(x) + sum over the chosen experts HELD of weight
    * expert(x), int32 [8] as ``MOE_STATS`` names them: 1, slots routed to
    experts held, those of them that no product covered (0: the buffer
    holds every slot), the largest load of an expert held, the buffer rows
    in use (the slots held and their groups' padding to whole tiles), 1
    where the gate ran inside the grouped-product kernels and 0 where it
    ran in XLA, the tokens whose kept groups hold an expert held (0 where
    the routing is not limited to groups), 1 where the rows moved through
    the ``mx_moe_*`` kernels and 0 where through ``jnp.take``).

    The buffer is sized for every slot there is; a step touches the prefix
    its routing fills (``_plan``) and a token's held slots only, so the
    time around the products follows the slots held, as theirs does. On the
    TPU (and under ``interpret``) the rows move through the ``mx_moe_*``
    kernels; elsewhere, or where a row is not whole words of 128 lanes
    (``moe_rows.fits``), through ``jnp.take``. There too the gate between
    the experts' first two products runs inside them
    (``grouped_matmul.grouped_glu``) where its kernels fit VMEM
    (``glu_fits``); elsewhere in XLA between separate products, over every
    row of the buffer."""
    from ..pallas_kernels import grouped_matmul as _gmm
    from ..pallas_kernels import moe_rows as _rows
    if groups[0] > 1 and route != "sigmoid":
        raise ValueError("groups=%r: routing limited to groups is the "
                         "sigmoid router's only, not %r's" % (groups, route))
    B, S, D = x.shape
    T, n_held = B * S, w_gate.shape[0]
    xt = x.reshape(T, D)
    on_tpu = jax.default_backend() == "tpu"
    how = (_gmm.TILE, bool(interpret)) if (interpret or on_tpu) and \
        _rows.fits(D, x.dtype, on_tpu and not interpret) else None
    in_kernel = (interpret or on_tpu) and _gmm.glu_fits(
        D, w_gate.shape[2], x.dtype)
    with jax.named_scope("mx.moe_route"):
        if route == "mlp_softmax":
            experts, weights, r = route_mlp_softmax(
                xt, router_w, bias, k, state.reshape(T, -1), eps)
            kept = None
        elif route == "topk_softmax":
            experts, weights = route_topk_softmax(xt, router_w, k)
            kept = None
        else:
            experts, weights, kept = route_sigmoid(xt, router_w, bias, k,
                                                   route_scale, groups)
    with jax.named_scope("mx.moe_dispatch"):
        plan = _plan(experts, first, n_held, _gmm.TILE)
        if how is not None:
            lists, fetched = _rows.tile_lists(plan.row_of, plan.held,
                                              plan.slot_of.shape[0])
            plan = plan._replace(lists=lists, fetched=fetched)
        xs = _dispatch(xt, plan, how)
    with jax.named_scope("mx.moe_experts"):
        def product(a, w):
            return _gmm.grouped_matmul(a, w, plan.sizes, plan.group_of,
                                       interpret)
        if in_kernel:
            ys = product(_gmm.grouped_glu(xs, w_gate, w_up, plan.sizes,
                                          plan.group_of, interpret), w_down)
        else:
            ys = _gated(xs, w_gate, w_up, w_down, product)
    with jax.named_scope("mx.moe_combine"):
        y = _combine(ys, weights, plan, how, x.dtype)
    if shared is not None:
        with jax.named_scope("mx.moe_shared"):
            y = y + _gated(xt, *shared, jnp.dot)
    n_held_slots = jnp.sum(plan.held, dtype=jnp.int32)
    to_held = jnp.int32(0)
    if kept is not None:
        size = router_w.shape[1] // groups[0]
        mine = kept[:, first // size:(first + n_held - 1) // size + 1]
        to_held = jnp.sum(jnp.any(mine, axis=1), dtype=jnp.int32)
    stats = jnp.stack([jnp.int32(1), n_held_slots,
                       n_held_slots - jnp.sum(plan.live, dtype=jnp.int32),
                       jnp.max(plan.counts),
                       jnp.sum(plan.sizes, dtype=jnp.int32),
                       jnp.int32(in_kernel), to_held,
                       jnp.int32(how is not None)])
    if route == "mlp_softmax":
        return y.reshape(B, S, D), stats, r.reshape(B, S, -1)
    return y.reshape(B, S, D), stats


def merge_stats(a, b):
    """Two layers' (or steps') ``MOE_STATS``: sums, and the larger load."""
    return jnp.where(jnp.array(_LARGEST), jnp.maximum(a, b), a + b)


def sum_stats(stats):
    """[n, 8] ``MOE_STATS`` of n calls as one: sums, and the largest load."""
    return jnp.where(jnp.array(_LARGEST), jnp.max(stats, axis=0),
                     jnp.sum(stats, axis=0))


# metrics()["moe"]: the counters of the train steps that carry them, kept
# on the device as the steps left them and fetched only when asked for
_LIVE_COUNTERS = []  # mxlint: disable=MX003 (weak references appended when a step is built; read as a snapshot)


def track(holder):
    """``holder.moe_counters`` (a device array or None) is summed into
    ``metrics()['moe']`` for as long as ``holder`` lives;
    ``holder.moe_held`` is the experts it holds and ``holder.moe_rows`` the
    rows of its layers' buffer."""
    import weakref
    _LIVE_COUNTERS.append(weakref.ref(holder))


def moe_stats():
    """``metrics()['moe']``: over every live train step with an expert share:
    ``layers`` (expert-layer calls), ``slots_held`` (token-slots routed to
    experts held), ``slots_dropped`` (must read 0), ``max_load`` (the most
    slots one held expert got in one call), ``rows_live`` (the buffer rows
    the steps' routing put in use: all that the row movements and the
    products touch), ``mean_load`` and ``live_share`` (``rows_live`` over
    the rows the layers' buffers have), ``gate_in_kernel`` and
    ``gate_apart`` (the calls whose gate ran inside the grouped-product
    kernels, and the rest: in XLA between separate products),
    ``tokens_to_held_groups`` (where the routing is limited to groups: the
    tokens, a layer call each, whose kept groups hold an expert held; 0
    where no step's routing is), ``rows_in_kernel`` and ``rows_apart``
    (the calls whose rows moved through the ``mx_moe_*`` kernels, and the
    rest: through ``jnp.take`` over every buffer row)."""
    import numpy as np
    total = np.zeros(len(MOE_STATS), np.int64)
    experts = rows = 0
    for ref in list(_LIVE_COUNTERS):
        holder = ref()
        if holder is None:
            _LIVE_COUNTERS.remove(ref)
        elif holder.moe_counters is not None:
            got = np.asarray(jax.device_get(holder.moe_counters), np.int64)
            total = np.where(_LARGEST, np.maximum(total, got), total + got)
            experts = max(experts, holder.moe_held)
            rows += int(got[0]) * holder.moe_rows
    out = {n: int(v) for n, v in zip(MOE_STATS, total)}
    out["mean_load"] = (out["slots_held"] / (out["layers"] * experts)
                        if out["layers"] and experts else 0.0)
    out["live_share"] = out["rows_live"] / rows if rows else 0.0
    out["gate_apart"] = out["layers"] - out["gate_in_kernel"]
    out["rows_apart"] = out["layers"] - out["rows_in_kernel"]
    return out


def _reset_moe_stats():
    for ref in list(_LIVE_COUNTERS):
        holder = ref()
        if holder is not None and holder.moe_counters is not None:
            holder.moe_counters = jnp.zeros_like(holder.moe_counters)


_profiler.register_stats_provider("moe", moe_stats, _reset_moe_stats)
