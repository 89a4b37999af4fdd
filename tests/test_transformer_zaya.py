"""The trunk with compressed convolutional attention, a router that is an
MLP with a state carried from layer to layer, a whole top-1 expert layer and
learned residual scaling. The program's loss and every gradient against the
plain reference (``chipbench/reference/zaya_decoder.py``) on seeded weights;
the state's way through the scan and the layer remat; the router's gradient
at k = 1; the shares tied to the whole layer; the other configurations'
programs left as they were."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import expert  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_zaya", "configs",
                    "tiny_zaya.json")
ROUTER = tuple("moe_router_" + n for n in expert.ROUTER_MLP)


def _model(m, first=None):
    from chipbench.models import zaya_decoder as adapter
    from chipbench.reference import zaya_decoder as R
    theta, dims = adapter.rope_of(m)
    return R.Model(eps=m["rms_norm_eps"], k=m["num_experts_per_tok"],
                   first=m["first_expert_held"] if first is None else first,
                   theta=theta, rope_dims=dims)


@pytest.fixture(scope="module")
def tiny():
    """The tiny stage in float32: its file, configuration, seeded weights,
    one batch."""
    from chipbench.models import zaya_decoder as adapter
    with open(TINY) as f:
        m = json.load(f)
    a = dict(m["assumed"], dtype="float32")
    cfg = adapter.transformer_config(m, a, 128)
    words = adapter.seed_words(3000000019)
    weights = adapter.make_weights(m, words, jnp.float32)
    (tokens, targets), = adapter.make_batches(
        m, {"n_batches": 1, "batch": 2, "seq_len": 128}, words)
    return m, cfg, weights, tokens, targets


@pytest.fixture(scope="module")
def sound(tiny):
    """The reference's loss on the tiny batch."""
    m, _, weights, tokens, targets = tiny
    return float(jax.jit(lambda w: _reference_loss(
        m, w, tokens, targets))(weights))


def _reference_loss(m, weights, tokens, targets, variant="exact"):
    """Mean token NLL by the reference's own layer and head."""
    from chipbench.reference import zaya_decoder as R
    model = _model(m)
    total = 0.0
    for b in range(tokens.shape[0]):
        x = jnp.take(weights["embed"], tokens[b], axis=0)
        r = jnp.zeros((tokens.shape[1], m["router_hidden_size"]))
        for lp in R.split_layers(weights):
            x, r = R.layer(lp, x, r, model, 64, variant)
        total = total + R.head_nll(weights["ln_f"], weights["embed"], x,
                                   targets[b], model, variant)
    return total / tokens.size


def test_the_adapters_configuration_and_the_tables_leaves(tiny):
    from chipbench.models import zaya_decoder as adapter
    m, cfg, weights, _, _ = tiny
    assert cfg.layer_pattern == () and cfg.n_layers == 3
    assert (cfg.qk_mix, cfg.mix_taps, cfg.v_shift) == ("cca", (2, 2), True)
    assert (cfg.rope_theta, cfg.rope_dims) == (5000000.0, 8)
    assert cfg.route == "mlp_softmax" and cfg.router_state
    assert cfg.expert_share == (0, 16) and cfg.moe_k == 1
    assert cfg.tied_head and cfg.residual_scaling and not cfg.embed_scale
    layer, _ = adapter.weight_shapes(m)
    table = T._layer_leaves(cfg)
    assert {n: s for n, (s, _) in layer.items()} == {
        n: s for n, (s, _, _) in table.items()}
    assert "w_out" not in weights and "moe_router" not in table
    # no state where no router has one: the carry is x alone
    assert not dataclasses.replace(cfg, route="sigmoid").router_state
    assert not T.TransformerConfig().router_state


def test_loss_and_every_gradient_against_the_plain_reference(tiny):
    m, cfg, weights, tokens, targets = tiny
    loss, grads = jax.value_and_grad(T.loss_fn)(weights, tokens, targets,
                                                cfg)
    want, want_grads = jax.value_and_grad(
        lambda w: _reference_loss(m, w, tokens, targets))(weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    flat = dict(grads["layers"], embed=grads["embed"], ln_f=grads["ln_f"])
    ref = dict(want_grads["layers"], embed=want_grads["embed"],
               ln_f=want_grads["ln_f"])
    assert set(flat) == set(ref)
    for name, g in flat.items():
        if name == "moe_bias":      # a buffer: it selects, no gradient
            assert not onp.any(g) and not onp.any(ref[name])
            continue
        gap = float(jnp.linalg.norm(g - ref[name])
                    / jnp.linalg.norm(ref[name]))
        assert gap < 2e-5, (name, gap)
        assert float(jnp.linalg.norm(g)) > 0, name


@pytest.mark.parametrize("variant", ["mix_dropped", "shift_dropped",
                                     "state_dropped", "weight_normalised",
                                     "fp8", "half_batch"])
def test_the_references_planted_faults_move_the_loss(tiny, sound, variant):
    m, _, weights, tokens, targets = tiny
    if variant == "half_batch":
        tokens, targets = tokens[:1], targets[:1]
        variant = "exact"
    got = float(jax.jit(lambda w: _reference_loss(
        m, w, tokens, targets, variant))(weights))
    assert abs(got - sound) / sound > 5e-5, (variant, got, sound)


def test_the_routers_leaves_get_a_gradient_at_one_expert_a_token(tiny):
    """The slot's weight is p[e], not p[e] / sum over the one chosen: the
    router learns at k = 1. Normalised, its every leaf's gradient is
    nought."""
    m, cfg, weights, tokens, targets = tiny
    grads = jax.grad(T.loss_fn)(weights, tokens, targets, cfg)["layers"]
    for name in ROUTER:
        assert float(jnp.linalg.norm(grads[name])) > 1e-6, name
    from chipbench.reference import zaya_decoder as R
    lp = R.split_layers(weights)[0]
    h = jax.random.normal(jax.random.PRNGKey(1), (128, m["hidden_size"]))
    r0 = jax.random.normal(jax.random.PRNGKey(2),
                           (128, m["router_hidden_size"]))

    def summed(lp, variant):
        chosen, w, _ = R.route(lp, h, r0, _model(m), variant)
        return jnp.sum(R.experts(lp, h, chosen, w, _model(m), 64, variant))

    sound = jax.grad(summed)(lp, "exact")
    flat = jax.grad(summed)(lp, "weight_normalised")
    for name in ROUTER:
        whole = float(jnp.linalg.norm(sound[name]))
        assert whole > 0, name
        # p / p: nought but for the division's rounding
        assert float(jnp.linalg.norm(flat[name])) < 1e-4 * whole, name


def test_layer_twos_routing_follows_layer_ones_state(tiny):
    """r travels: through the scan's carry and the layer remat. Perturb the
    first layer's down-projection, which moves r_1 and nothing of x but
    through the routing, and the second layer's state moves with it."""
    m, cfg, weights, tokens, targets = tiny
    lps = [jax.tree_util.tree_map(lambda a: a[l], weights["layers"])
           for l in range(3)]
    x0 = jnp.take(weights["embed"], tokens, axis=0)
    r0 = jnp.zeros(tokens.shape + (m["router_hidden_size"],))
    body = jax.checkpoint(lambda c, lp: T._layer_body(
        cfg, None, jnp.arange(tokens.shape[1]), c, lp)[0])
    (x1, r1) = body((x0, r0), lps[0])
    (_, r2) = body((x1, r1), lps[1])
    # the same second layer from the same x but a fresh state: other r, and
    # other experts for some tokens
    (_, r2_fresh) = body((x1, r0), lps[1])
    gamma = lps[1]["moe_router_gamma"]
    onp.testing.assert_allclose(r2 - r2_fresh, gamma * r1, rtol=1e-4,
                                atol=1e-5)

    def chosen(r_prev):
        h = T._rms_norm(x1, lps[1]["ln2"], cfg.norm_eps)
        # the expert share adds to x1's attention output first: take the
        # routing alone, on the layer's own input
        router = {n: lps[1]["moe_router_" + n] for n in expert.ROUTER_MLP}
        e, _, _ = expert.route_mlp_softmax(
            h.reshape(-1, h.shape[-1]), router, lps[1]["moe_bias"], 1,
            r_prev.reshape(-1, r_prev.shape[-1]), cfg.norm_eps)
        return e

    moved = jnp.mean(chosen(r1) != chosen(r1 + 3.0 * jnp.std(r1)
                                          * jnp.sign(r1)))
    assert float(moved) > 0.05, float(moved)
    # and the gradient of the loss reaches layer one's router through it
    # even where layer one's own slot weights are held still
    g = jax.grad(lambda w: jnp.sum(body((x1, (x0 @ w)), lps[1])[1]))(
        lps[0]["moe_router_down"])
    assert float(jnp.linalg.norm(g)) > 0


def test_the_two_shares_add_up_to_the_whole_layer(tiny):
    """Experts 0-7 and experts 8-15, each told which it holds, routing over
    all sixteen: their parts add up to what the uncut layer gives, program
    and reference alike."""
    from chipbench.reference import zaya_decoder as R
    m, cfg, weights, tokens, _ = tiny
    lp = jax.tree_util.tree_map(lambda a: a[0], weights["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, m["hidden_size"]))
    r0 = jnp.zeros((2, 128, m["router_hidden_size"]))
    router = {n: lp["moe_router_" + n] for n in expert.ROUTER_MLP}

    def share(first, count):
        sl = slice(first, first + count)
        return expert.moe_share(
            h, router, lp["moe_bias"], lp["moe_w_gate"][sl],
            lp["moe_w_up"][sl], lp["moe_w_down"][sl], k=1, first=first,
            route="mlp_softmax", state=r0, eps=cfg.norm_eps)

    whole, stats, r = share(0, 16)
    low, s_low, r_low = share(0, 8)
    high, s_high, r_high = share(8, 8)
    onp.testing.assert_allclose(low + high, whole, rtol=1e-5, atol=1e-6)
    onp.testing.assert_array_equal(r, r_low)
    onp.testing.assert_array_equal(r, r_high)
    names = dict(zip(expert.MOE_STATS, zip(stats, s_low, s_high)))
    assert int(names["slots_held"][0]) == 256 == int(
        names["slots_held"][1] + names["slots_held"][2])
    assert [int(v) for v in names["slots_dropped"]] == [0, 0, 0]
    # the reference, given the same shares
    for b in range(2):
        chosen, w, _ = R.route(lp, h[b], r0[b], _model(m), "exact")
        part = lambda first, count: R.experts(  # noqa: E731
            dict(lp, **{n: lp[n][first:first + count] for n in
                        ("moe_w_gate", "moe_w_up", "moe_w_down")}),
            h[b], chosen, w, _model(m, first), 64, "exact")
        onp.testing.assert_allclose(part(0, 8) + part(8, 8), whole[b],
                                    rtol=2e-5, atol=2e-6)
        onp.testing.assert_allclose(part(0, 8), low[b], rtol=2e-5, atol=2e-6)


def test_three_steps_count_every_slot_and_note_the_stage(tiny):
    from chipbench.models import zaya_decoder as adapter
    from mxnet_tpu import profiler
    m, _, _, tokens, targets = tiny
    cfg = adapter.transformer_config(m, m["assumed"], 128)
    weights = adapter.make_weights(m, adapter.seed_words(3000000019),
                                   jnp.bfloat16)
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=1.0)
    state = (weights, jax.tree_util.tree_map(jnp.zeros_like, weights))
    before = profiler.metrics()["moe"]
    with mesh.mesh:
        losses = []
        for _ in range(3):
            state, loss = step(state, tokens, targets)
            losses.append(float(loss))
        text = step.lower(state, tokens, targets).as_text(debug_info=True)
    assert all(onp.isfinite(losses)) and losses[2] < losses[0]
    moe = profiler.metrics()["moe"]
    # three layers a step, every token exactly one slot, nothing dropped
    assert moe["layers"] - before["layers"] == 9
    assert moe["slots_held"] - before["slots_held"] == 9 * 256
    assert moe["slots_dropped"] == before["slots_dropped"]
    # off the kernels every expert layer's gate runs in XLA
    assert moe["gate_apart"] - before["gate_apart"] == 9
    assert moe["gate_in_kernel"] == before["gate_in_kernel"]
    assert profiler.metrics()["cca"] == {
        "layers": 3, "q_latent": 64, "kv_latent": 32, "taps": [2, 2],
        "mix_bytes": 2 * 256 * (5 * 96 + 4 * 16)}
    for scope in ("mx.attn_proj", "mx.cca_mix", "mx.flash", "mx.attn_out",
                  "mx.moe_route", "mx.moe_experts"):
        assert scope in text, scope
    assert "convolution" not in text    # multiply-adds and products, no conv


def test_the_moved_scopes_are_in_both_tables():
    """``_debug/devicetable.py`` and ``chipbench/scopes.py`` say the same
    for every scope the program carries, the new one among them."""
    from chipbench import scopes
    from mxnet_tpu._debug import devicetable
    base = "jit(step_fn)/jit(main)/mx.layer/while/body/%smx.attn_proj/" \
           "mx.cca_mix/mul"
    for inner, phase in (("", "forward"),
                         ("transpose(jvp(checkpoint))/", "backward"),
                         ("checkpoint/rematted_computation/", "recompute")):
        assert scopes.classify(base % inner) == ("mx.cca_mix", phase) \
            == devicetable.classify(base % inner)
    for name in ("mx.embed", "mx.layer", "mx.attn_proj", "mx.cca_mix",
                 "mx.mla_latent", "mx.flash", "mx.attn_out", "mx.ffn",
                 "mx.head_ce",
                 "mx.optimizer", "mx.moe_route", "mx.moe_dispatch",
                 "mx.moe_experts", "mx.moe_combine", "mx.moe_shared",
                 "mx.ssm_proj", "mx.ssm_conv", "mx.ssm_scan", "mx.ssm_gate"):
        op = "jit(step_fn)/mx.layer/%s/dot_general" % name
        assert scopes.classify(op) == devicetable.classify(op) \
            == (name, "forward")
    text = '  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, ' \
           'metadata={op_name="jit(f)/mx.cca_mix/add"}\n'
    assert scopes.scope_map(text) == devicetable.scope_map(text) \
        == {"fusion.1": "jit(f)/mx.cca_mix/add"}


# sha256 of the lowered step's text at the parent commit (f7a3481, CPU, one
# device): the plain decoder and the two tiny shares in their own
# ``assumed`` type. The fields this model added default to those programs.
# Re-taken at PR 38 (parent 3a185ac): its head takes the gradient in its
# forward rule, which every one of these steps runs. The two shares' again
# when their counters gained ``gate_in_kernel``, a sixth int32 carried, and
# ``tokens_to_held_groups``, a seventh (0 where routing has no groups).
PARENT_LOWERED = {
    "dense": "0e5c5ceeaee5c46fed63c03b26df1cf0ce512645ed09a75e5ff765163345363a",
    "afmoe": "3f33b97f39a18694743f93ae173cfbd5eca67289d27a40d53393bc4594408367",
    "granite": "9ef25c21d6a52c72c7ddd89e098689281ad909e42d39e58483277862488159f5",
}


def _other_case(kind):
    import importlib
    import jax.random as jr
    if kind == "dense":
        cfg = T.TransformerConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, ffn_hidden=128,
            max_seq_len=128, dtype="bfloat16", attn_mode="local", remat=True,
            loss_chunks=4)
        ids = jr.randint(jr.PRNGKey(8), (2, 129), 0, 256, jnp.int32)
        return cfg, T.init_params(jr.PRNGKey(7), cfg), ids[:, :-1], \
            ids[:, 1:], 1.0
    adapter = importlib.import_module(
        "chipbench.models." + {"afmoe": "afmoe_decoder",
                               "granite": "granite_hybrid"}[kind])
    with open(os.path.join(ROOT, "tests", "chipbench", "tiny_" + kind,
                           "configs", "tiny_%s.json" % kind)) as f:
        m = json.load(f)
    words = adapter.seed_words(3000000019)
    (tokens, targets), = adapter.make_batches(
        m, {"n_batches": 1, "batch": 2, "seq_len": 128}, words)
    return (adapter.transformer_config(m, m["assumed"], 128),
            adapter.make_weights(m, words, jnp.bfloat16), tokens, targets,
            m["assumed"]["learning_rate"])


@pytest.mark.parametrize("kind", sorted(PARENT_LOWERED))
def test_the_other_configurations_lower_to_the_parents_program(kind):
    import hashlib
    cfg, weights, tokens, targets, lr = _other_case(kind)
    assert not cfg.router_state and cfg.qk_mix == "none"
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=lr)
    state = (weights, jax.tree_util.tree_map(jnp.zeros_like, weights))
    with mesh.mesh:
        text = step.lower(state, tokens, targets).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_LOWERED[kind]
