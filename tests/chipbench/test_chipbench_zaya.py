"""The ``zaya_decoder`` kind (one pipeline stage's share of a decoder with
compressed convolutional attention, a router that is an MLP with a state and
a whole top-1 expert layer) as chipbench has it: its count's integers at the
cell's shapes, the cell's files against the published configuration key by
key, the new reader, and ``run_cell`` end to end at toy widths on the CPU
from a tiny root of its own: a run comes out correct, a program that leaves
out one of this model's four parts does not. Device metrics are never
asserted here: a CPU run has none."""
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_zaya")
CELL, TINY_CELL = "zaya1_8b.pp8.l5-seq8192x4", "tiny_zaya-seq128"
SEED = 3000000019       # past 2**31, as the driver's seeds are

# Zyphra/ZAYA1-8B config.json, as the catalog row has it
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


def _work():
    spec = run.load_cell(CELL)
    t = spec["traffic"]
    return spec, {"model": spec["config"], "batch": t["batch"],
                  "seq_len": t["seq_len"], "dtype": "bfloat16"}


def test_the_counts_integers_at_the_cells_shapes():
    """By hand, a token and layer: q, k, the two halves of v and o
    2*2048*128*(2*8+2*2); conv 1's two taps of ten [128, 128] products; the
    router 2*(2048*256 + 2*256*256 + 256*16); ONE slot of 6*2048*2048; the
    head 2*2048*32784; 33,558,528 pairs kept a head."""
    spec, work = _work()
    count = run.load_named("counts", "zaya_decoder")
    tokens, expert = 4 * 8192, 6 * 2048 * 2048
    assert count.kept_pairs(8192) == 8192 * 8193 // 2 == 33558528
    # every token one slot, whatever the loads: all sixteen are held
    assert count.slots_held(spec["config"], tokens) == tokens == 32768
    parts = count.forward_parts(work)
    assert parts == {
        "attn_proj": 5 * tokens * 2 * 2048 * 128 * 20,
        "conv1": 5 * tokens * 2 * 10 * 2 * 128 * 128,
        "attn_pairs": 5 * 2 * 2 * 8 * 128 * 33558528 * 4,
        "router": 5 * tokens * 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16),
        "routed": 5 * tokens * expert,
        "head": tokens * 2 * 2048 * 32784}
    r = spec["required"](work)
    fwd = sum(parts.values())
    assert r == {"step_flops": 3 * fwd == 39941786566656 and 3 * fwd,
                 "kernels": {
                     "mx_flash_": {"flops": 3 * parts["attn_pairs"],
                                   "bytes": 6 * 10 * 128 * tokens * 2 * 5},
                     # nine grouped products a layer, five layers: 32768
                     # slots of width 2048 on both sides, 16 matrices
                     "mx_gmm_": {"flops": 3 * 5 * tokens * expert,
                                 "bytes": 9 * (tokens * 4096
                                               + 16 * 2048 * 2048) * 2 * 5}}}
    # the issue's round figures: 39.9 TF a step, 37.6 MFLOP of products and
    # 16.8 of attention a token and layer, the head a third of a step
    assert round(r["step_flops"] / 1e12, 1) == 39.9
    products = (parts["attn_proj"] + parts["conv1"] + parts["router"]
                + parts["routed"]) / (5 * tokens)
    assert round(products / 1e6, 1) == 37.6
    assert round(parts["attn_pairs"] / (5 * tokens) / 1e6, 1) == 16.8
    assert round(100 * parts["head"] / fwd) == 33


def test_the_configuration_is_the_published_one_key_by_key():
    spec, _ = _work()
    held, bench = spec["config"], spec["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == held["name"])
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_rows_held"]
    assert set(held["reduced_why"]) == set(held["published"]) \
        == set(entry["reduced"])
    # every key of the published file is in ours, unchanged unless reduced;
    # the nested group whole
    differ = sorted(k for k, v in PUBLISHED.items() if held[k] != v)
    assert differ == ["layer_types", "num_hidden_layers"]
    assert held["layer_types"] == ["hybrid"] * 5
    assert held["num_hidden_layers"] == 5 == len(held["layer_types"])
    assert held["published"]["num_hidden_layers"] == 40
    # the whole expert layer, an eighth of the rows
    assert (held["num_experts_held"], held["first_expert_held"],
            held["num_experts"], held["num_experts_per_tok"]) == (16, 0, 16, 1)
    assert held["vocab_rows_held"] * 8 == held["vocab_size"] \
        == held["published"]["vocab_rows_held"] == 262272
    assert held["vocab_rows_held"] == 32784 == 16 * 2049
    assert "8 stages" in held["deployment"] and "8-chip" in held["deployment"]
    assert not [k for k in entry["reduced"]
                if k.endswith(("_size", "_dim", "_rank"))]
    for form in ("conv_biases", "conv_forms", "mean_grouping", "value_shift",
                 "unit_norms", "rotary", "residual_scaling", "router",
                 "router_bias", "mixture_of_depths", "learning_rate",
                 "learning_rate_why", "init"):
        assert held["assumed"][form] not in (None, ""), form
    # the cell: one chip; it reports whatever the first cell reports, the
    # expert share's numbers and the mixing stage's share (membership:
    # wherever it stands in a list, whatever else it or a later cell reports)
    cell = spec["cell"]
    assert (cell["chips"], cell["traffic"]) == (1, "seq8192x4")
    assert (spec["traffic"]["batch"], spec["traffic"]["seq_len"],
            spec["traffic"]["n_batches"]) == (4, 8192, 4)
    shared = {m["name"] for m in bench["per_layer"]
              if "baichuan_7b.l5-seq2048" in m["workloads"]}
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert shared and shared <= mine
    assert {"moe.experts_share", "moe.route_share", "moe.dropped_slots",
            "moe.slots_held", "kernels.gmm_roofline",
            "cca.mix_share"} <= mine
    assert not {n for n in mine if n.startswith("ssm.")}
    mix = next(m for m in bench["per_layer"] if m["name"] == "cca.mix_share")
    assert mix == {"name": "cca.mix_share", "unit": "%", "better": "lower",
                   "source": "device_trace", "layer": "compressed attention",
                   "moves": "step_ms", "workloads": [CELL]}


def test_the_adapters_configuration_is_the_share_the_file_states():
    from chipbench.models import zaya_decoder as adapter
    spec, _ = _work()
    m = spec["config"]
    cfg = adapter.transformer_config(m, m["assumed"], 8192)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.dim) == (8, 2, 128,
                                                                  2048)
    assert cfg.n_layers == 5 and cfg.layer_pattern == ()
    assert cfg.expert_share == (0, 16) and cfg.num_experts == 16
    assert (cfg.moe_k, cfg.moe_hidden, cfg.moe_shared) == (1, 2048, 0)
    assert cfg.route == "mlp_softmax" and cfg.router_hidden == 256
    assert (cfg.qk_mix, cfg.mix_taps, cfg.v_shift) == ("cca", (2, 2), True)
    assert (cfg.rope_theta, cfg.rope_dims, cfg.rope_on) == (5e6, 64, "all")
    assert cfg.vocab_size == 32784 and cfg.tied_head and cfg.norm_eps == 1e-5
    assert cfg.residual_scaling and cfg.remat_save is None
    assert cfg.loss_chunks == 8 and cfg.dtype == "bfloat16"
    layer, top = adapter.weight_shapes(m)
    size = lambda leaves: sum(  # noqa: E731
        math.prod(shape) for shape, _ in leaves.values())
    # the issue's table: attention 5.243 M, the two convolutions 0.333 M,
    # the router 0.660 M, sixteen experts 201.327 M, norms and residual
    # scaling 0.020 M: a layer 207.58 M, the rows 67.14 M, 1105.1 M in all
    part = lambda *names: sum(math.prod(layer[n][0]) for n in names)  # noqa: E731
    assert part("wq", "wk", "wv_cur", "wv_prev", "wo") == 5242880
    assert part("cca_conv0_w", "cca_conv0_b", "cca_conv1_w", "cca_conv1_b",
                "cca_temp") == 3840 + 328960 + 2
    assert part(*(n for n in layer if n.startswith("moe_router_")),
                "moe_bias") == 659729
    assert part("moe_w_gate", "moe_w_up", "moe_w_down") == 201326592
    assert part("ln1", "ln2", *(n for n in layer if n.startswith("res"))) \
        == 10 * 2048
    assert size(layer) == 207582483
    assert size(top) == 32784 * 2048 + 2048
    assert 5 * size(layer) + size(top) == 1105056095
    # the program's own table agrees, leaf for leaf
    from mxnet_tpu.parallel import transformer as T
    assert {n: s for n, (s, _) in layer.items()} == {
        n: s for n, (s, _, _) in T._layer_leaves(cfg).items()}


def test_the_reader_reads_the_scope_or_finds_nothing():
    read = run.metric_reader("cca.mix_share")
    scopes = {"mx.cca_mix": {"forward": 1.0, "backward": 2.5,
                             "recompute": 1.5},
              "mx.attn_proj": {"forward": 5.0, "backward": 5.0,
                               "recompute": 5.0}}
    assert read({"trace": {"scopes": scopes, "op_sum_s": 50.0}}) \
        == pytest.approx(10.0)
    # a program without the scope (the parent), or no program text: nothing
    assert read({"trace": {"scopes": {"mx.ffn": {}}, "op_sum_s": 1.0}}) is None
    assert read({"trace": {"op_sum_s": 1.0}}) is None
    assert read({"trace": {"scopes": scopes, "op_sum_s": 0.0}}) is None


def _run(wrap=None):
    import jax
    return run.run_cell(TINY_CELL, SEED, 0.2, False,
                        devices=jax.devices()[:1], wrap=wrap, root=TINY)


def test_a_tiny_stage_runs_correct_and_gives_every_token_its_slot():
    from mxnet_tpu import profiler
    before = profiler.metrics()["moe"]
    r = _run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["compared"]) == {"grad_norm_gap", "grad_norm_gap_med",
                                  "delta_norm_gap", "delta_norm_gap_med"}
    assert all(v <= lim for v, lim in r["compared"].values())
    moe = profiler.metrics()["moe"]
    # 3 expert layers a step, 256 tokens of one slot each, all held
    layers = moe["layers"] - before["layers"]
    assert layers >= 3 * (3 + r["attempted"])
    assert moe["slots_held"] - before["slots_held"] == 256 * layers
    assert moe["slots_dropped"] == before["slots_dropped"]
    assert profiler.metrics()["cca"]["layers"] == 3
    assert list(r)[-3:] == ["left_out", "not_compared", "compared"]
    json.dumps(r)


def _mix_dropped(monkeypatch):
    """No convolutions: the latents and their mean, normalised."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import cca

    def mix(q0, k0, lp):
        B, S, H, d = q0.shape
        G = k0.shape[2]
        f32 = jnp.float32
        m_q = (q0.astype(f32).reshape(B, S, G, H // G, d)
               + k0.astype(f32)[:, :, :, None]) * 0.5
        q = q0.astype(f32) + m_q.reshape(B, S, H, d)
        k = k0.astype(f32) + jnp.mean(m_q, axis=3)
        return (cca._unit(q).astype(q0.dtype),
                cca._unit(k, lp["cca_temp"].astype(f32)).astype(q0.dtype))

    monkeypatch.setattr(cca, "mix", mix)


def _shift_dropped(monkeypatch):
    """The later half of v from this position, as the first."""
    from mxnet_tpu.parallel import cca
    monkeypatch.setattr(cca, "shift", lambda a: a)


def _state_dropped(monkeypatch):
    """Every layer's router starts from nought: a scan that does not carry
    the state."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import expert
    real = expert.route_mlp_softmax
    monkeypatch.setattr(
        expert, "route_mlp_softmax",
        lambda h, router, bias, k, state, eps: real(
            h, router, bias, k, jnp.zeros_like(state), eps))


def _weight_normalised(monkeypatch):
    """The chosen probabilities normalised over the one chosen: weight 1."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import expert
    real = expert.route_mlp_softmax

    def route(*args):
        experts, weights, r = real(*args)
        return experts, weights / jnp.sum(weights, -1, keepdims=True), r

    monkeypatch.setattr(expert, "route_mlp_softmax", route)


@pytest.mark.parametrize("fault", [_mix_dropped, _shift_dropped,
                                   _state_dropped, _weight_normalised],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_stage_that_leaves_a_part_out_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = _run()
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert "grad_norm_gap" in over
