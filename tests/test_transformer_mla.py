"""Latent attention (MLA) over a share of the heads, YaRN positions and
group-limited sigmoid routing in the mesh transformer, held to the plain
reference (``chipbench/reference/deepseek_decoder.py``) and to hand
computations, at toy widths on the CPU."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as onp
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models import deepseek_decoder as adapter  # noqa: E402
from chipbench.reference import afmoe_decoder as afmoe_ref  # noqa: E402
from chipbench.reference import deepseek_decoder as R  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import expert as X  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_deepseek", "configs",
                    "tiny_deepseek.json")
# the published row's rope_scaling (GigaChat3.1-702B-A36B, rope_theta 1e5)
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"}


def _tiny(**changes):
    with open(TINY) as f:
        m = json.load(f)
    m.update(changes)
    return m


def _weights(m, seed, dtype):
    """The adapter's seeded weights, made in one program."""
    return jax.jit(lambda w: adapter.make_weights(m, w, dtype))(
        adapter.seed_words(seed))


def test_the_yarn_frequencies_and_scale_are_the_hand_computed_ones():
    """By hand, 64 rope dims at base 1e5: the correction dims are
    floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e5)) = floor(8.378) = 8 and
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e5)) = ceil(18.01) = 19; frequency j
    is 1e5^(-j/32) up to j = 8, that over 64 from j = 19, and the linear
    blend between. The scores: 192^-0.5 (1 + 0.1 ln 64)^2 = 192^-0.5 x
    2.00474; cos and sin times 1."""
    assert math.floor(64 * math.log(4096 / (64 * math.pi))
                      / (2 * math.log(1e5))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e5))) == 19
    hand = []
    for j in range(32):
        ramp = min(max((j - 8) / 11, 0.0), 1.0)
        base = 1e5 ** (-j / 32)
        hand.append(base * (1 - ramp) + base / 64 * ramp)
    got = T.yarn_freqs(64, 1e5, YARN)
    assert got.dtype == onp.float32 and got.shape == (32,)
    onp.testing.assert_allclose(got, hand, rtol=1e-6)
    assert got[8] == pytest.approx(1e5 ** -0.25, rel=1e-6)
    assert got[19] == pytest.approx(1e5 ** (-19 / 32) / 64, rel=1e-6)
    freqs, mult, factor = R.yarn(64, 1e5, (64, 4096, 32, 1, 1, 1))
    onp.testing.assert_allclose(onp.asarray(freqs), hand, rtol=1e-6)
    assert mult == 1.0 and factor == pytest.approx(2.00474, abs=1e-5)
    cfg = adapter.transformer_config(
        _tiny(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=192,
              rope_scaling=YARN), {"dtype": "float32", "remat": True,
                                   "loss_chunks": 1}, 128)
    assert cfg.head_dim == 192
    assert cfg.scores_scale == pytest.approx(
        192 ** -0.5 * (1 + 0.1 * math.log(64)) ** 2, rel=1e-12)
    # no rope_scaling, or no mscale_all_dim: the kernel's own head ** -0.5
    assert T.TransformerConfig().scores_scale is None
    assert T.TransformerConfig(rope_scaling=dict(
        YARN, mscale_all_dim=0)).scores_scale is None


def _route(h, bias, groups, k=4):
    """``route_sigmoid`` with the identity as router: the logits are h."""
    return X.route_sigmoid(h, jnp.eye(h.shape[1]), bias, k, 2.5, groups)


def test_group_limited_routing_is_a_top_k_over_the_kept_groups():
    """16 experts in 4 groups of 4, 2 groups kept, top 4: token 0's best
    expert (logit 5, group 0) stands alone in its group, whose score (its
    two largest summed) loses to groups 1 and 2; it is never chosen. Every
    token is checked against the plain top k over its kept groups."""
    E, G = 16, 4
    h = jr.normal(jr.PRNGKey(0), (64, E)) * 2
    row = jnp.full((E,), -5.0).at[0].set(5.0).at[4:6].set(2.0) \
        .at[8:10].set(2.0)
    h = h.at[0].set(row)
    bias = jr.normal(jr.PRNGKey(1), (E,)) * 0.01
    experts, weights, kept = _route(h, bias, (G, 2))
    assert sorted(onp.asarray(experts[0]).tolist()) == [4, 5, 8, 9]
    assert onp.asarray(kept[0]).tolist() == [False, True, True, False]
    s = onp.asarray(jax.nn.sigmoid(h), onp.float64)
    b = s + onp.asarray(bias, onp.float64)
    for t in range(h.shape[0]):
        tops = [sorted(b[t, g * 4:(g + 1) * 4])[-2:] for g in range(G)]
        best = onp.argsort([-sum(v) for v in tops], kind="stable")[:2]
        pool = [e for e in range(E) if e // 4 in best]
        chosen = sorted(pool, key=lambda e: -b[t, e])[:4]
        assert sorted(onp.asarray(experts[t]).tolist()) == sorted(chosen)
        assert sorted(onp.asarray(kept[t]).nonzero()[0].tolist()) \
            == sorted(best.tolist())
        w = s[t, chosen] / s[t, chosen].sum() * 2.5
        got = dict(zip(onp.asarray(experts[t]).tolist(),
                       onp.asarray(weights[t]).tolist()))
        onp.testing.assert_allclose([got[e] for e in chosen], w, rtol=1e-5)


def test_one_group_is_the_sigmoid_route_there_was():
    """``route_groups`` 1: the experts and weights of the function as it
    stood before groups (copied here), bit for bit, and no kept groups."""
    def before(h, router_w, bias, k, route_scale=1.0):
        logits = jnp.dot(h.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, experts = lax.top_k(scores + lax.stop_gradient(
            bias.astype(jnp.float32)), k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        return experts, weights * route_scale

    h = jr.normal(jr.PRNGKey(2), (48, 32), jnp.bfloat16)
    w = jr.normal(jr.PRNGKey(3), (32, 16), jnp.bfloat16)
    bias = jr.normal(jr.PRNGKey(4), (16,)) * 0.01
    experts, weights, kept = X.route_sigmoid(h, w, bias, 4, 2.826)
    want_e, want_w = before(h, w, bias, 4, 2.826)
    assert kept is None
    assert (experts == want_e).all() and (weights == want_w).all()


def test_a_head_share_is_refused_outside_latent_attention():
    """Projections straight to q, k and v run every head: a share of them
    there would be a count that nothing reads."""
    with pytest.raises(ValueError, match="latent attention's only"):
        T.TransformerConfig(n_heads=8, heads_held=4)
    cfg = T.TransformerConfig(n_heads=8, attention="mla", heads_held=4)
    assert (cfg.heads_here, T.TransformerConfig(n_heads=8).heads_here) \
        == (4, 8)


@pytest.mark.parametrize("route,moe_hidden", [
    ("topk_softmax", 32), ("mlp_softmax", 32), ("sigmoid", None)])
def test_group_limits_are_refused_outside_the_sigmoid_share(route,
                                                            moe_hidden):
    """The top-k-softmax and MLP routers, and the GShard layer, have no
    groups: a limit to groups there is refused where it is set."""
    with pytest.raises(ValueError, match="route_groups=4"):
        T.TransformerConfig(num_experts=16, moe_k=2, moe_hidden=moe_hidden,
                            route=route, route_groups=4, route_groups_kept=2)
    if moe_hidden is not None:
        h = jnp.zeros((1, 8, 16))
        w = jnp.zeros((4, 16, 8))
        with pytest.raises(ValueError, match="sigmoid router's only"):
            X.moe_share(h, jnp.zeros((16, 16)), None, w, w,
                        jnp.zeros((4, 8, 16)), k=2, route=route,
                        groups=(4, 2))


def _share_case():
    """A tiny layer whose every weight is whole (8 heads, 16 experts),
    float32, and the configuration of a share of it."""
    m = _tiny(num_hidden_layers=2, first_k_dense_replace=1, heads_held=8,
              num_experts_held=16, first_expert_held=0)
    w = _weights(m, 3000000019, jnp.float32)
    lp = {n: a[0] for n, a in w["layers"].items()}
    # a larger bias, so that the groups and their limit decide something
    lp["moe_bias"] = jr.normal(jr.PRNGKey(5), (16,)) * 0.3
    x = jr.normal(jr.PRNGKey(6), (1, 16, 64))
    return m, lp, x


def test_the_shares_of_heads_and_experts_add_up_to_the_uncut_layer():
    """Two head shares of 4 (the latent down-projections and their norms
    computed alike in both) and two expert shares of 8 (the shared expert
    once): attention's parts summed, then the experts' parts summed on the
    summed input, are the reference's whole layer."""
    m, lp, x = _share_case()
    a = {"dtype": "float32", "remat": False, "loss_chunks": 1}
    positions = jnp.arange(x.shape[1])

    cfg = adapter.transformer_config(dict(m, heads_held=4), a, x.shape[1])

    @jax.jit
    def shares(lp, x):
        attn = 0.0
        for first in (0, 4):
            part = dict(lp, wq_b=lp["wq_b"][:, first:first + 4],
                        wkv_b=lp["wkv_b"][:, first:first + 4],
                        wo=lp["wo"][first:first + 4])
            attn = attn + T._attend(cfg, None, positions, x, part, False) - x
        x1 = x + attn
        h = T._rms_norm(x1, lp["ln2"], m["rms_norm_eps"])
        shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        parts, counts = 0.0, []
        for first in (0, 8):
            y, stats = X.moe_share(
                h, lp["moe_router"], lp["moe_bias"],
                *(lp[n][first:first + 8] for n in (
                    "moe_w_gate", "moe_w_up", "moe_w_down")),
                shared, k=4, first=first, route_scale=2.5, groups=(4, 2))
            parts, counts = parts + y, counts + [stats]
        return (x1[0] + parts[0] - afmoe_ref._gated(h[0], *shared, "exact"),
                jnp.stack(counts))

    got, counts = shares(lp, x)
    want = jax.jit(lambda lp, x: R.layer(lp, x, adapter.model_of(m), 16,
                                         "exact"))(lp, x[0])
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                atol=2e-4, rtol=2e-4)
    # every slot lands in one share or the other; every token's kept groups
    # (2 of 4) hold experts of one share or of both
    assert int(counts[:, 1].sum()) == 16 * 4
    assert 16 <= int(counts[:, 6].sum()) <= 32


def test_a_step_through_make_train_step_is_the_references():
    """The tiny share in float32 (4 of 8 heads, 4 of 16 experts, 2 of 4
    groups kept, YaRN x4), one step of make_train_step at learning rate
    0.1: its loss, its momentum (the first gradient) and its weights are
    the reference's loss, gradient and SGD step."""
    m = _tiny()
    a = {"dtype": "float32", "remat": False, "loss_chunks": 2}
    cfg = adapter.transformer_config(m, a, 128)
    w = _weights(m, 3000000019, jnp.float32)
    (tokens, targets), = jax.jit(lambda words: adapter.make_batches(
        m, {"n_batches": 1, "batch": 1, "seq_len": 128}, words))(
            adapter.seed_words(3000000019))
    model = adapter.model_of(m)

    def ref_loss(w):
        x = jnp.take(w["embed"], tokens[0], axis=0)
        for _, lp in R.split_layers(w):
            x = R.layer(lp, x, model, 64, "exact")
        return afmoe_ref.head_nll(w["ln_f"], w["w_out"], x, targets[0],
                                  model.eps, "exact") / tokens.size

    loss, grads = jax.jit(jax.value_and_grad(ref_loss))(w)
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=0.1)
    with mesh.mesh:
        (params, mom), got = step(
            (jax.tree_util.tree_map(jnp.copy, w),
             jax.tree_util.tree_map(jnp.zeros_like, w)), tokens, targets)
    assert float(got) == pytest.approx(float(loss), rel=1e-5)
    grads, params, mom, w = jax.device_get((grads, params, mom, w))
    for (path, g), p, mo, p0 in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(mom),
            jax.tree_util.tree_leaves(w)):
        if "moe_bias" in jax.tree_util.keystr(path):
            assert (p == p0).all() and not mo.any()   # a buffer: never moves
            continue
        assert onp.abs(mo - g).max() <= 1e-4 * (onp.abs(g).max() + 1e-12), \
            path
        onp.testing.assert_allclose(p, p0 - onp.float32(0.1) * mo,
                                    rtol=1e-6, atol=1e-7)
    # what the step traced holds, and the counters it kept
    mla = profiler.metrics()["mla"]
    assert mla == {"layers": 3, "heads_held": 4, "q_latent": 32,
                   "kv_latent": 16,
                   "latent_bytes": 4 * 3 * (128 * (3 * 64 + 2 * 4 * (
                       2 * 32 + 32)) + 3 * (64 * 32 + 32 + 32 * 4 * 32
                                            + 64 * 32 + 16 + 16 * 4 * 48))}
    assert profiler.metrics()["moe"]["tokens_to_held_groups"] > 0


def test_the_latent_stage_has_its_scope_in_the_program():
    m = _tiny()
    cfg = adapter.transformer_config(m, m["assumed"], 128)
    w = _weights(m, 7, jnp.bfloat16)
    tokens = jnp.zeros((1, 128), jnp.int32)
    text = jax.jit(lambda w: T.loss_fn(w, tokens, tokens, cfg)).lower(
        w).as_text(debug_info=True)
    for scope in ("mx.mla_latent", "mx.attn_proj", "mx.flash", "mx.attn_out",
                  "mx.moe_route", "mx.moe_experts"):
        assert scope in text, scope
