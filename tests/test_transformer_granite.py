"""The trunk with a state-space kind: mixers and an attention layer in one
period (a stack a run, a run of mixers as an inner scan), no positions, a
scale of the scores, three multipliers, a top-k-softmax router, a tied
head. The program's loss and every gradient against the plain reference
(``chipbench/reference/granite_hybrid.py``) on seeded weights; the share
tied to the model; the tied head's gradient as the sum of both uses."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import ssm  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_granite", "configs",
                    "tiny_granite.json")


def _model(m):
    from chipbench.reference import granite_hybrid as R
    return R.Model(
        eps=m["rms_norm_eps"], k=m["num_experts_per_tok"],
        first=m["first_expert_held"], residual=m["residual_multiplier"],
        embedding=float(m["embedding_multiplier"]),
        logits=1.0 / m["logits_scaling"], attention=m["attention_multiplier"],
        heads=m["mamba_n_heads"], head_size=m["mamba_d_head"],
        state=m["mamba_d_state"], chunk=m["mamba_chunk_size"])


@pytest.fixture(scope="module")
def tiny():
    """The tiny share in float32: its file, configuration, seeded weights,
    one batch."""
    from chipbench.models import granite_hybrid as adapter
    with open(TINY) as f:
        m = json.load(f)
    a = dict(m["assumed"], dtype="float32")
    cfg = adapter.transformer_config(m, a, 128)
    words = adapter.seed_words(3000000019)
    weights = adapter.make_weights(m, words, jnp.float32)
    (tokens, targets), = adapter.make_batches(
        m, {"n_batches": 1, "batch": 2, "seq_len": 128}, words)
    return m, cfg, weights, tokens, targets


def _reference_loss(m, weights, tokens, targets, variant="exact"):
    """Mean token NLL by the reference's own layer and head."""
    from chipbench.models import granite_hybrid as adapter
    from chipbench.reference import granite_hybrid as R
    model = _model(m)
    total = 0.0
    for b in range(tokens.shape[0]):
        x = jnp.take(weights["embed"], tokens[b], axis=0) * model.embedding
        for _, kind, lp in R.split_layers(weights, adapter.runs_of(m)):
            x = R.layer(lp, x, kind, model, 64, variant)
        total = total + R.head_nll(weights["ln_f"], weights["embed"], x,
                                   targets[b], model, variant)
    return total / tokens.size


def test_a_stack_a_run_and_a_run_of_mixers_scanned(tiny):
    m, cfg, weights, tokens, targets = tiny
    assert cfg.layer_pattern == ("mamba", "mamba", "full", "mamba")
    assert T._runs(cfg) == [("mamba", ["mamba", "mamba"]),
                            ("layers", ["full"]), ("mamba_1", ["mamba"])]
    assert {n: (lead, kind) for n, (lead, _, _, kind)
            in T._stacks(cfg).items()} == {
        "mamba": ((1, 2), "mamba"), "layers": ((1, 1), "full"),
        "mamba_1": ((1, 1), "mamba")}
    assert cfg.attn_layers == 1 and cfg.periods == 1
    # an attention-only period is one run, one stack, as it was
    plain = dataclasses.replace(cfg, layer_pattern=("sliding", "full"),
                                n_layers=2, window=32)
    assert T._runs(plain) == [("layers", ["sliding", "full"])]
    assert plain.attn_layers == 2
    assert "w_out" not in weights and "w_out" not in T.param_specs(cfg)
    # the inner scan: the step's text holds a while inside the layers' while
    text = jax.jit(lambda w: T.loss_fn(w, tokens, targets, cfg)).lower(
        weights).as_text(debug_info=True)
    for scope in ("mx.ssm_proj", "mx.ssm_conv", "mx.ssm_scan", "mx.ssm_gate",
                  "mx.flash", "mx.moe_route", "mx.moe_shared"):
        assert scope in text, scope
    assert "convolution" not in text          # four multiply-adds, no conv op


def test_loss_and_every_gradient_against_the_plain_reference(tiny):
    m, cfg, weights, tokens, targets = tiny
    loss, grads = jax.value_and_grad(T.loss_fn)(weights, tokens, targets,
                                                cfg)
    want, want_grads = jax.value_and_grad(
        lambda w: _reference_loss(m, w, tokens, targets))(weights)
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert len(flat) == len(ref) == 2 + 13 + 2 * 17
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        top = float(jnp.max(jnp.abs(ref[path])))
        assert top > 0.0, name
        assert float(jnp.max(jnp.abs(g - ref[path]))) < 5e-4 * top, name


def test_the_mixer_layer_against_the_references(tiny):
    m, cfg, weights, tokens, _ = tiny
    from chipbench.reference import granite_hybrid as R
    lp = jax.tree_util.tree_map(lambda a: a[0, 1], weights["mamba"])
    x = jnp.take(weights["embed"], tokens, axis=0) * 12.0

    def mine(lp, x):
        return T._layer_body(cfg, None, None, x, lp, kind="mamba")[0]

    def theirs(lp, x):
        return jnp.stack([R.layer(lp, x[b], "mamba", _model(m), 64, "exact")
                          for b in range(x.shape[0])])

    got, want = mine(lp, x), theirs(lp, x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    g = jax.grad(lambda p: jnp.sum(jnp.sin(mine(p, x))))(lp)
    w = jax.grad(lambda p: jnp.sum(jnp.sin(theirs(p, x))))(lp)
    for n in lp:
        assert float(jnp.max(jnp.abs(g[n] - w[n]))) < 5e-4 * float(
            jnp.max(jnp.abs(w[n]))), n


@pytest.mark.parametrize("variant", ["state_dropped", "expert_missing"])
def test_the_references_planted_faults_move_the_loss(tiny, variant):
    m, cfg, weights, tokens, targets = tiny
    sound = float(_reference_loss(m, weights, tokens, targets))
    broken = float(_reference_loss(m, weights, tokens, targets, variant))
    assert abs(broken - sound) > 1e-6 * sound


# The tiny step's first three losses at the parent commit (40fff6e, before
# ``ssm.conv_silu`` and the barrier before the gate; this machine's CPU, JAX
# 0.9.0, learning rate 1.0, seed 3000000019, batch 2 x 128): the forward is
# the parent's arithmetic in the parent's order, and at this size the
# backward's other order of sums does not reach a loss's last bit either.
PARENT_LOSSES = {
    "float32": ["0x1.62e1880000000p+2", "0x1.62ba300000000p+2",
                "0x1.626f820000000p+2"],
    "bfloat16": ["0x1.62e0f80000000p+2", "0x1.62c03e0000000p+2",
                 "0x1.627a9c0000000p+2"]}


def _three_steps(cfg, weights, tokens, targets):
    """-> (the first three losses of the step at learning rate 1, the step:
    ``metrics()["moe"]`` counts the steps alive)."""
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=1.0)
    state = (jax.tree_util.tree_map(jnp.copy, weights),     # the step donates
             jax.tree_util.tree_map(jnp.zeros_like, weights))
    losses = []
    with mesh.mesh:
        for _ in range(3):
            state, loss = step(state, tokens, targets)
            losses.append(float(loss))
    return losses, step


def test_in_bfloat16_the_first_three_losses_are_the_parents(tiny):
    from chipbench.models import granite_hybrid as adapter
    m, _, _, tokens, targets = tiny
    cfg = adapter.transformer_config(m, dict(m["assumed"], dtype="bfloat16"),
                                     128)
    weights = adapter.make_weights(m, adapter.seed_words(3000000019),
                                   jnp.bfloat16)
    losses, _ = _three_steps(cfg, weights, tokens, targets)
    assert [x.hex() for x in losses] == PARENT_LOSSES["bfloat16"]


def test_three_steps_losses_and_the_scans_facts(tiny):
    import mxnet_tpu as mx
    from chipbench.models import granite_hybrid as adapter
    from chipbench.reference import granite_hybrid as R
    m, cfg, weights, tokens, targets = tiny
    before = mx.profiler.metrics()["moe"]
    losses, step = _three_steps(cfg, weights, tokens, targets)
    assert [x.hex() for x in losses] == PARENT_LOSSES["float32"]
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, weights)  # noqa: E731
    want = R.train(fresh, [(tokens, targets)] * 3, 1.0, 3,
                   adapter.runs_of(m), _model(m), block=64)
    for got, ref in zip(losses, want["loss"]):
        assert abs(got - ref) < 2e-5 * ref
    assert losses[2] < losses[0]
    # the scan's facts, set while the step was traced
    assert mx.profiler.metrics()["ssm"] == {
        "layers": 3, "chunk": 32, "heads_at_once": 8,
        "scan_temp_bytes": 4 * 2 * 128 * 32 * 8,
        # float32, 2 x 128 tokens, 8 heads of 16 and a state of 16
        "stage_bytes": 4 * 2 * 128 * (5 * (128 + 2 * 16) + 8 * 128)}
    after = mx.profiler.metrics()["moe"]
    assert after["layers"] - before["layers"] == 3 * 4   # every layer routes
    assert after["slots_dropped"] == 0
    # off the kernels every expert layer's gate runs in XLA, the scanned
    # run of mixers' among them
    assert after["gate_apart"] - before["gate_apart"] == 3 * 4
    assert after["gate_in_kernel"] == before["gate_in_kernel"]


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Each share's routed part, with the shared expert counted once, is
    the uncut reference layer's feed-forward: 16 experts as four shares of
    four, the program's ``moe_share`` against the reference holding all."""
    from mxnet_tpu.parallel import expert
    from chipbench.reference import granite_hybrid as R
    m, cfg, weights, tokens, _ = tiny
    E, D, F = m["num_local_experts"], m["hidden_size"], m["intermediate_size"]
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    lp = {"moe_router": jax.random.normal(k[0], (D, E)) * D ** -0.5,
          "moe_w_gate": jax.random.normal(k[1], (E, D, F)) * D ** -0.5,
          "moe_w_up": jax.random.normal(k[2], (E, D, F)) * D ** -0.5,
          "moe_w_down": jax.random.normal(k[3], (E, F, D)) * F ** -0.5,
          "ws_gate": weights["layers"]["ws_gate"][0, 0],
          "ws_up": weights["layers"]["ws_up"][0, 0],
          "ws_down": weights["layers"]["ws_down"][0, 0]}
    h = jax.random.normal(k[4], (1, 128, D))
    shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    whole = R.experts(lp, h[0], _model(m)._replace(first=0), "exact")
    held = E // 4
    total = 0.0
    for share in range(4):
        at = slice(share * held, (share + 1) * held)
        y, stats = expert.moe_share(
            h, lp["moe_router"], None, lp["moe_w_gate"][at],
            lp["moe_w_up"][at], lp["moe_w_down"][at],
            shared if share == 0 else None, k=m["num_experts_per_tok"],
            first=share * held, route="topk_softmax")
        assert int(stats[2]) == 0
        total = total + y[0]
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-4 * float(
        jnp.max(jnp.abs(whole)))


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(tiny):
    m, cfg, weights, tokens, targets = tiny
    tied = jax.grad(T.loss_fn)(weights, tokens, targets, cfg)["embed"]
    # the same model with the head untied and started at the rows
    apart = dataclasses.replace(cfg, tied_head=False)
    split = dict(weights, w_out=weights["embed"].T)
    g = jax.grad(T.loss_fn)(split, tokens, targets, apart)
    both = g["embed"] + g["w_out"].T
    assert float(jnp.max(jnp.abs(g["w_out"]))) > 0.0
    assert float(jnp.max(jnp.abs(tied - both))) < 1e-5 * float(
        jnp.max(jnp.abs(both)))


def test_the_router_takes_the_k_largest_and_a_softmax_over_them():
    from mxnet_tpu.parallel import expert
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    experts, weights = expert.route_topk_softmax(h, w, 3)
    logits = h @ w
    for t in range(5):
        top = sorted(range(6), key=lambda e: -float(logits[t, e]))[:3]
        assert list(map(int, experts[t])) == top
        want = jax.nn.softmax(logits[t, jnp.array(top)])
        assert float(jnp.max(jnp.abs(weights[t] - want))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(weights, -1) - 1.0))) < 1e-6


def test_a_bfloat16_multiplier_is_taken_in_float32():
    x = jnp.full((4,), 51.0, jnp.bfloat16)
    assert T._scaled(x, 1.0) is x
    got = T._scaled(x, 0.22)
    assert got.dtype == jnp.bfloat16
    assert float(got[0]) == float(jnp.asarray(51 * 0.22, jnp.bfloat16))
    assert float((x * 0.22)[0]) == 11.1875      # bfloat16(0.22) is 0.2197
    assert float(got[0]) == 11.25


def test_the_compiled_steps_text_carries_the_mixers_scopes(tiny):
    """Both parsers of the compiled text (the program's and the
    benchmark's copy) find the four ``mx.ssm_*`` scopes in every phase the
    layer remat gives them."""
    from mxnet_tpu._debug import devicetable
    from chipbench import scopes
    m, cfg, weights, tokens, targets = tiny
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=0.1)
    state = (weights, jax.tree_util.tree_map(jnp.zeros_like, weights))
    with mesh.mesh:
        text = step.lower(state, tokens, targets).compile().as_text()
    for parser in (devicetable, scopes):
        found = {}
        for op_name in parser.scope_map(text).values():
            scope, phase = parser.classify(op_name)
            found.setdefault(scope, set()).add(phase)
        for scope in ("mx.ssm_proj", "mx.ssm_conv", "mx.ssm_scan",
                      "mx.ssm_gate"):
            assert found.get(scope) == {"forward", "backward",
                                        "recompute"}, (scope, found)
        assert "mx.moe_experts" in found and "mx.flash" in found
