"""The trunk with layer kinds: grouped-query heads, sliding and full layers
in one scanned period after a leading dense layer, gated attention, q/k
norms, four norms a layer, a scaled embedding, and scanned layers that are
one chip's share of an expert layer. The program's loss and every gradient
against the plain reference (``chipbench/reference/afmoe_decoder.py``) on
seeded weights; and the dense decoder's step, which none of this may move,
bitwise against the parent's."""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_afmoe", "configs",
                    "tiny_afmoe.json")


def _tiny(**assumed):
    """The tiny share as its file states it (``assumed`` overrides what the
    file assumes): its file, what was assumed, configuration, seeded
    weights, one batch."""
    from chipbench.models import afmoe_decoder as adapter
    with open(TINY) as f:
        m = json.load(f)
    a = dict(m["assumed"], **assumed)
    cfg = adapter.transformer_config(m, a, 128)
    words = adapter.seed_words(3000000019)
    weights = adapter.make_weights(m, words, jnp.dtype(a["dtype"]))
    (tokens, targets), = adapter.make_batches(
        m, {"n_batches": 1, "batch": 2, "seq_len": 128}, words)
    return m, a, cfg, weights, tokens, targets


@pytest.fixture(scope="module")
def tiny():
    """The tiny share in float32: configuration, weights, one batch."""
    m, _, cfg, weights, tokens, targets = _tiny(dtype="float32")
    return m, cfg, weights, tokens, targets


def _reference_loss(m, weights, tokens, targets, variant="exact"):
    """Mean token NLL by the reference's own layer and head."""
    from chipbench.models import afmoe_decoder as adapter
    from chipbench.reference import afmoe_decoder as R
    model = R.Model(eps=m["rms_norm_eps"], window=m["sliding_window"],
                    k=m["num_experts_per_tok"], route_scale=m["route_scale"],
                    first=m["first_expert_held"], theta=float(m["rope_theta"]))
    layers = R.split_layers(weights, adapter.kinds_of(m))
    total = 0.0
    for b in range(tokens.shape[0]):
        x = jnp.take(weights["embed"], tokens[b], axis=0) \
            * m["hidden_size"] ** 0.5
        for _, kind, lp in layers:
            x = R.layer(lp, x, kind, model, 64, variant)
        total = total + R.head_nll(weights["ln_f"], weights["w_out"], x,
                                   targets[b], model.eps, variant)
    return total / tokens.size


def test_loss_and_every_gradient_against_the_plain_reference(tiny):
    m, cfg, weights, tokens, targets = tiny
    assert cfg.layer_pattern == ("sliding", "full") and cfg.periods == 1
    assert cfg.dense_layers == ("sliding",) and cfg.expert_share == (4, 4)
    loss, grads = jax.value_and_grad(T.loss_fn)(weights, tokens, targets,
                                                cfg)
    want, want_grads = jax.value_and_grad(
        lambda w: _reference_loss(m, w, tokens, targets))(weights)
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert len(flat) == len(ref) == 3 + 14 + 19
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        top = float(jnp.max(jnp.abs(ref[path])))
        if "moe_bias" in name:       # a buffer: it selects, and that is all
            assert top == 0.0 and float(jnp.max(jnp.abs(g))) == 0.0
            continue
        assert top > 0.0, name
        assert float(jnp.max(jnp.abs(g - ref[path]))) < 2e-4 * top, name


@pytest.mark.parametrize("variant", ["no_window", "expert_missing"])
def test_the_references_planted_faults_move_the_loss(tiny, variant):
    m, cfg, weights, tokens, targets = tiny
    sound = float(_reference_loss(m, weights, tokens, targets))
    broken = float(_reference_loss(m, weights, tokens, targets, variant))
    assert abs(broken - sound) > 1e-4 * sound


def test_the_step_counts_its_slots_on_the_device_and_drops_none(tiny):
    import dataclasses
    import mxnet_tpu as mx
    m, cfg, weights, tokens, targets = tiny
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=0.1)
    state = (weights, jax.tree_util.tree_map(jnp.zeros_like, weights))
    before = mx.profiler.metrics()["moe"]
    with mesh.mesh:
        for _ in range(2):
            state, loss = step(state, tokens, targets)
    after = mx.profiler.metrics()["moe"]
    assert after["layers"] - before["layers"] == 2 * 2   # 2 shares a step
    assert after["slots_dropped"] == 0
    held = after["slots_held"] - before["slots_held"]
    assert 0 < held < 2 * 2 * tokens.size * m["num_experts_per_tok"]
    assert onp.isfinite(float(loss))
    # the caller's view is the dense step's: (state, loss), lower of three
    with mesh.mesh:
        text = step.lower(state, tokens, targets).as_text(debug_info=True)
    for scope in ("mx.moe_route", "mx.moe_dispatch", "mx.moe_experts",
                  "mx.moe_combine", "mx.moe_shared", "mx.flash", "mx.ffn"):
        assert scope in text, scope
    assert "ragged_dot" in text       # the grouped products
    # whole periods or nothing
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(cfg, n_layers=4).periods


def test_the_norms_eps_is_the_configurations():
    x = jnp.full((1, 4), 1e-3)
    loose = T._rms_norm(x, jnp.ones(4), 1e-2)
    tight = T._rms_norm(x, jnp.ones(4), 1e-6)
    assert float(loose[0, 0]) == pytest.approx(1e-3 / (1e-6 + 1e-2) ** 0.5)
    assert float(tight[0, 0]) == pytest.approx(1e-3 / (2e-6) ** 0.5)
    assert T.TransformerConfig().norm_eps == 1e-6     # Baichuan's stays


_PLAIN = dict(vocab_size=64, dim=32, n_layers=4, n_heads=4, ffn_hidden=48)


def _granite():
    """The tiny share of mixers and an attention layer, as its file states
    it: three stacks, two sets of leaves, no ``w_out``."""
    from chipbench.models import granite_hybrid as adapter
    with open(os.path.join(ROOT, "tests", "chipbench", "tiny_granite",
                           "configs", "tiny_granite.json")) as f:
        m = json.load(f)
    return adapter.transformer_config(m, m["assumed"], 128)


@pytest.mark.parametrize("make", [
    lambda: T.TransformerConfig(**_PLAIN),
    lambda: T.TransformerConfig(pp=2, **_PLAIN),
    lambda: T.TransformerConfig(num_experts=4, **_PLAIN),
    lambda: _tiny()[2], lambda: _granite()], ids=[
        "plain", "plain_pp2", "gshard", "share", "mixers"])
def test_the_table_is_the_only_writer_of_a_layers_leaves(make):
    cfg = make()
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jr.PRNGKey(0))
    specs = T.param_specs(cfg)
    is_spec = lambda l: isinstance(l, T.P)  # noqa: E731
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(specs, is_leaf=is_spec)
    for leaf, spec in zip(jax.tree_util.tree_leaves(shapes),
                          jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(spec) == leaf.ndim
    for stack, (lead, _, experts, kind) in T._stacks(cfg).items():
        table = T._layer_leaves(cfg, experts, kind)
        assert {n: lead + shape for n, (shape, _, _) in table.items()} == \
            {n: v.shape for n, v in shapes[stack].items()}


# The step at the parent commit, CPU, one device: three losses, the sha256 of
# the state after them and of the lowered program's text. "dense": the plain
# decoder at 8ac7738 (PR 30's parent), unmoved since. "pattern": the tiny
# share in its own ``assumed`` type and learning rate, seeded weights, zero
# momentum, at 97c2934 (PR 32's parent). Re-taken at PR 38 (parent
# 3a185ac), whose head takes its gradient in its forward rule: the lowered
# programs move by design, the states do not. "dense"'s first loss moves by
# one float32 ulp (0x1.7cb0ec -> 0x1.7cb0ee): the CPU's compiler sums the
# chunk's exp otherwise once the rule's gradient also reads it. The loss
# feeds nothing, so the later losses and the state are the parent's.
# "pattern"'s lowered program re-taken when the expert shares' counters
# gained ``gate_in_kernel``, a sixth int32 carried, and again for
# ``tokens_to_held_groups``, a seventh (0 here); losses and state held.
PARENT = {
    "dense": {"losses": ["0x1.7cb0ee0000000p+2", "0x1.41f6f80000000p+2",
                         "0x1.daa90c0000000p+1"],
              "state": "f9467e1e4620f3524bac36bf099bee21415e08177ceb2243743d"
                       "9bc9fa27670d",
              "lowered": "ed71ea4213edff9c9aed714074ac0c9f00c850186b8f6111238c"
                         "3b1e28fb4d70"},
    "pattern": {"losses": ["0x1.7e1c0a0000000p+2", "0x1.3256480000000p+2",
                           "0x1.e07f7c0000000p+1"],
                "state": "e711cab76d3d57be2a5ae0094902011627f360ba3552be6e90"
                         "180ac8fbcaa80b",
                "lowered": "b13e239f9cfd83867c9ff6ffca9c66f94171f3aefb1cce73"
                           "d03251d4ea83189f"},
}


def _dense_case():
    cfg = T.TransformerConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                              ffn_hidden=128, max_seq_len=128,
                              dtype="bfloat16", attn_mode="local", remat=True,
                              loss_chunks=4)
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    init, step = T.make_train_step(cfg, mesh, learning_rate=1.0)
    with mesh.mesh:
        state = init(jr.PRNGKey(7))
    ids = jr.randint(jr.PRNGKey(8), (2, 129), 0, 256, jnp.int32)
    return mesh, step, state, ids[:, :-1], ids[:, 1:]


def _pattern_case():
    _, a, cfg, weights, tokens, targets = _tiny()
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    _, step = T.make_train_step(cfg, mesh, learning_rate=a["learning_rate"])
    state = (weights, jax.tree_util.tree_map(jnp.zeros_like, weights))
    return mesh, step, state, tokens, targets


@pytest.mark.parametrize("case", ["dense", "pattern"])
def test_the_step_is_the_parents_bit_for_bit(case):
    mesh, step, state, tokens, targets = {
        "dense": _dense_case, "pattern": _pattern_case}[case]()
    with mesh.mesh:
        losses = []
        for _ in range(3):
            state, loss = step(state, tokens, targets)
            losses.append(float(loss).hex())
        text = step.lower(state, tokens, targets).as_text()
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        digest.update(onp.asarray(leaf.astype(jnp.float32)).tobytes())
    got = {"losses": losses, "state": digest.hexdigest(),
           "lowered": hashlib.sha256(text.encode()).hexdigest()}
    assert got == PARENT[case]
