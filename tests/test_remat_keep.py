"""What the layer remat keeps (``parallel/transformer.py remat_choice``):
the flash forward kernel's output and row sums, named inside the kernels'
``custom_vjp``, where the device has room. With interpreted kernels the
backward runs one forward kernel fewer and every gradient is the same to the
bit, alone and inside a ``shard_map``; the choice as a table over the
benchmark's three cells; the counters in ``metrics()['train_step']``."""
import dataclasses
import functools
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import jax.random as jr
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu import pallas_kernels, profiler  # noqa: E402
from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

FA = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")
NAMES = ("flash_out", "flash_lse")
V5E_LIMIT = 16911433728     # 15.75 GiB: one v5e's bytes_limit (PERF.md)


def _kernels(jaxpr, name):
    """Pallas calls of that name in a jaxpr, its sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" \
                and eqn.params["name"] == name:
            n += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _kernels(sub, name)
    return n


def _same_bits(a, b):
    return all(bool(jnp.all(x == y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# -- the kernels under a checkpointed layer ----------------------------------

@pytest.mark.parametrize("h,g,window", [(2, 2, None), (4, 2, 128)])
def test_a_checkpointed_layer_runs_one_forward_kernel_fewer(h, g, window):
    """dq, dk, dv and the weight's gradient of flash attention and a
    product after it, under ``jax.checkpoint``: full remat against the two
    names kept."""
    q, k, v, w = (jr.normal(jr.PRNGKey(i), shape, jnp.bfloat16)
                  for i, shape in enumerate(
                      [(1, h, 256, 128)] + [(1, g, 256, 128)] * 2
                      + [(h, 128, 64)]))

    def layer(q, k, v, w):
        o = FA.flash_attention(q, k, v, causal=True, window=window,
                               block_q=128, block_k=128, interpret=True)
        return jnp.einsum("bhsd,hde->bse", o, w)

    def grads(policy):
        kept = jax.checkpoint(layer, policy=policy)
        grad = jax.grad(lambda *a: jnp.sum(kept(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2, 3))
        return jax.make_jaxpr(grad)(q, k, v, w).jaxpr, grad(q, k, v, w)

    full, want = grads(None)
    kept, got = grads(jax.checkpoint_policies.save_only_these_names(*NAMES))
    assert (_kernels(full, "mx_flash_fwd"), _kernels(kept, "mx_flash_fwd")) \
        == (2, 1)
    for name in ("mx_flash_dq", "mx_flash_dkv"):
        assert _kernels(full, name) == _kernels(kept, name) == 1
    assert _same_bits(got, want)


@pytest.mark.parametrize("axes", [{"dp": 1}, {"dp": 2}, {"dp": 2, "tp": 2}],
                         ids=["one_device", "dp2", "dp2_tp2"])
def test_the_names_reach_the_kernel_through_the_trunk_and_a_mesh(
        axes, monkeypatch):
    """The whole trunk's gradient on the CPU mesh with interpreted kernels:
    with more than one device the call sits inside a ``shard_map``, and the
    policy still finds the names in it."""
    monkeypatch.setattr(pallas_kernels, "flash_attention", functools.partial(
        FA.flash_attention, interpret=True))
    mesh = create_mesh(devices=jax.devices()[:math.prod(axes.values())],
                       **axes)
    ids = jr.randint(jr.PRNGKey(1), (2, 129), 0, 256)

    def grads(save):
        cfg = T.TransformerConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, ffn_hidden=128,
            max_seq_len=128, dtype="float32", remat=True, remat_save=save)
        params = T.init_params(jr.PRNGKey(0), cfg)
        grad = jax.grad(lambda p: T.loss_fn(p, ids[:, :-1], ids[:, 1:], cfg,
                                            mesh))
        with mesh.mesh:
            return jax.make_jaxpr(grad)(params).jaxpr, jax.jit(grad)(params)

    full, want = grads(())
    kept, got = grads(NAMES)
    # the scanned layer's kernel shows once in each scan's body
    assert (_kernels(full, "mx_flash_fwd"), _kernels(kept, "mx_flash_fwd")) \
        == (2, 1)
    assert _kernels(kept, "mx_flash_dq") == 1
    assert _same_bits(got, want)


# -- the choice --------------------------------------------------------------

def _cell(config, traffic, **changes):
    """(cfg, batch, seq, bytes of weights and momentum, of the gradients) of
    a cell of BENCHMARK.json, from its files: no array is made."""
    def load(group, name):
        with open(os.path.join(ROOT, "chipbench", group, name + ".json")) as f:
            return json.load(f)

    m, t = load("configs", config), load("traffic", traffic)
    a = m["assumed"]
    if m.get("adapter") == "afmoe_decoder":
        from chipbench.models import afmoe_decoder
        cfg = afmoe_decoder.transformer_config(m, a, t["seq_len"])
    else:
        cfg = T.TransformerConfig(
            vocab_size=m["vocab_size"], dim=m["hidden_size"],
            n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
            ffn_hidden=m["intermediate_size"], max_seq_len=t["seq_len"],
            dtype=a["dtype"], remat=a["remat"], loss_chunks=a["loss_chunks"])
    cfg = dataclasses.replace(cfg, **changes)
    shapes = jax.eval_shape(lambda: T.init_params(jr.PRNGKey(0), cfg))
    weights = sum(s.size * s.dtype.itemsize
                  for s in jax.tree_util.tree_leaves(shapes))
    return cfg, t["batch"], t["seq_len"], 2 * weights, weights


CELLS = {"cell1": ("baichuan_7b.l5", "seq2048"),
         "cell2": ("baichuan_7b.l5", "seq16384"),
         "cell3": ("trinity_mini.ep4.l5", "seq8192")}
# five layers of 16384 tokens x 32 heads x (128 x 2 + 4) bytes
KEPT = 5 * 16384 * 32 * 260


@pytest.mark.parametrize("cell,changes,limit,want", [
    ("cell1", {}, None, ()),                    # the CPU, a described chip
    ("cell1", {}, V5E_LIMIT, NAMES),
    ("cell2", {}, V5E_LIMIT, NAMES),
    ("cell3", {}, V5E_LIMIT, NAMES),
    ("cell2", {"n_layers": 8}, V5E_LIMIT, ()),  # deeper: the state takes it
    ("cell2", {}, 12 * 2 ** 30, ()),            # a smaller device
    ("cell2", {"remat_save": ()}, V5E_LIMIT, ()),
    ("cell2", {"remat_save": ("ffn_prod",)}, V5E_LIMIT, ("ffn_prod",)),
    ("cell2", {"remat": False}, V5E_LIMIT, ()),
])
def test_the_choice_as_a_table(cell, changes, limit, want):
    cfg, batch, seq, state, grads = _cell(*CELLS[cell], **changes)
    names, kept, budget = T.remat_choice(cfg, batch, seq, state, grads,
                                         {"dp": 1}, limit)
    assert names == want
    rows = cfg.n_layers * KEPT // 5
    assert kept == (rows if names == NAMES else 0)
    if "remat_save" in changes or not cfg.remat or not limit:
        assert budget is None           # nothing was the program's to choose
    else:
        assert (rows <= budget) == (names == NAMES)


def test_the_choice_counts_a_devices_share_of_batch_and_heads():
    cfg, batch, seq, state, grads = _cell("baichuan_7b.l5", "seq2048")
    one = T.remat_choice(cfg, batch, seq, state, grads, {"dp": 1}, V5E_LIMIT)
    four = T.remat_choice(cfg, batch, seq, state // 2, grads // 2,
                          {"dp": 2, "tp": 2, "sp": 1}, V5E_LIMIT)
    assert one[1] == KEPT and four[1] == KEPT // 4 and four[0] == NAMES


# -- the counters --------------------------------------------------------------

def _step(**changes):
    cfg = T.TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, ffn_hidden=128,
        max_seq_len=128, dtype="bfloat16", remat=True, loss_chunks=4,
        **changes)
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    init, step = T.make_train_step(cfg, mesh, learning_rate=1.0)
    ids = jr.randint(jr.PRNGKey(8), (2, 129), 0, 256, jnp.int32)
    with mesh.mesh:
        state, loss = step(init(jr.PRNGKey(7)), ids[:, :-1], ids[:, 1:])
    return float(loss)


def _counters():
    got = profiler.metrics()["train_step"]
    return (got["remat_kept"], got["remat_kept_bytes"],
            got["remat_budget_bytes"])


def test_the_counters_say_what_the_step_keeps(monkeypatch):
    plain = _step()
    assert _counters() == ([], 0, None)         # the CPU reports no limit
    monkeypatch.setattr(T, "_mesh_bytes_limit", lambda mesh: 2 ** 30)
    roomy = _step()
    kept, nbytes, budget = _counters()
    # two layers of 2 x 4 x 128 rows: 16 bf16 values and a float32 sum
    assert (kept, nbytes) == (list(NAMES), 2 * 2 * 4 * 128 * (16 * 2 + 4))
    assert nbytes <= budget < 2 ** 30 // 8
    # the reference branch has nothing of those names: the same program
    assert roomy == plain
    profiler.metrics(reset=True)                # a fact, not a count
    assert _counters()[0] == list(NAMES)
    pinned = _step(remat_save=("ffn_prod",))
    assert _counters() == (["ffn_prod"], 0, None) and pinned == plain
    _step(remat_save=())
    assert _counters() == ([], 0, None)
