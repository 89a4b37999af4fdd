"""The ``afmoe`` kind (one chip's share of an expert-parallel decoder) as
chipbench has it: its count's integers at the cell's shapes, the cell's
files, and ``run_cell`` end to end at toy widths on the CPU from a tiny root
of its own: a run comes out correct, a program without its windows or
without one of its experts does not. Device metrics are never asserted
here: a CPU run has none."""
import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_afmoe")
CELL, TINY_CELL = "trinity_mini.ep4.l5-seq8192", "tiny_afmoe-seq128"
SEED = 3000000019       # past 2**31, as the driver's seeds are


def _work():
    spec = run.load_cell(CELL)
    t = spec["traffic"]
    return spec, {"model": spec["config"], "batch": t["batch"],
                  "seq_len": t["seq_len"], "dtype": "bfloat16"}


def test_the_counts_integers_at_the_cells_shapes():
    """By hand: 16384 tokens; attention products 2*2048*128*(3*32+2*4) a
    token and layer; the dense layer 6*2048*6144; an expert layer the
    router 2*2048*128, the shared expert 6*2048*1024 and 16384*8*32/128 =
    32768 slots of 6*2048*1024; the head 2*2048*50048; pairs kept 14,681,088
    a sequence and head on a sliding layer and 33,558,528 on the full one."""
    spec, work = _work()
    count = run.load_named("counts", "afmoe_decoder")
    assert count.kept_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14681088
    assert count.kept_pairs(8192) == 8192 * 8193 // 2 == 33558528
    assert count.layer_kinds(spec["config"]) == [
        ("sliding", False), ("sliding", True), ("sliding", True),
        ("sliding", True), ("full", True)]
    tokens, expert = 16384, 6 * 2048 * 1024
    attn = 2 * 2 * 32 * 128 * 2 * (4 * 14681088 + 33558528)
    fwd = (tokens * (5 * 2 * 2048 * 128 * 104 + 6 * 2048 * 6144
                     + 4 * (2 * 2048 * 128 + expert) + 2 * 2048 * 50048)
           + 4 * 32768 * expert + attn)
    r = spec["required"](work)
    assert r == {"step_flops": 3 * fwd == 43783701921792 and 3 * fwd,
                 "kernels": {
                     "mx_flash_": {"flops": 3 * attn,
                                   "bytes": 6 * 36 * 128 * tokens * 2 * 5},
                     # nine grouped products a layer: 32768 slots of width
                     # 2048 and 1024 between them, 32 matrices of 2048 x 1024
                     "mx_gmm_": {"flops": 3 * 4 * 32768 * expert,
                                 "bytes": 9 * (32768 * 3072
                                               + 32 * 2048 * 1024) * 2 * 4}}}
    assert 3 * attn == 9071776235520
    assert r["kernels"]["mx_gmm_"] == {"flops": 4947802324992,
                                       "bytes": 12079595520}
    # the issue's round figures: 43.8 TF a step, attention 21% of it
    assert round(r["step_flops"] / 1e12, 1) == 43.8
    assert round(100 * 3 * attn / r["step_flops"]) == 21


def test_the_configuration_is_the_catalogs_at_the_published_widths():
    spec, _ = _work()
    held, bench = spec["config"], spec["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == held["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types", "num_experts_held",
                                "vocab_rows_held"]
    assert set(held["reduced_why"]) == set(held["published"]) \
        == set(entry["reduced"])
    # every width as published (Trinity-Mini's config.json)
    assert {k: held[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
        "num_experts", "num_experts_per_tok", "num_shared_experts",
        "sliding_window", "vocab_size", "rms_norm_eps", "route_scale")} == {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "sliding_window": 2048, "vocab_size": 200192, "rms_norm_eps": 1e-05,
        "route_scale": 2.826}
    assert len(held["layer_types"]) == held["num_hidden_layers"] == 5
    assert held["num_experts_held"] * 4 == held["num_experts"]
    assert held["vocab_rows_held"] * 4 == held["vocab_size"]
    # the expert share's metrics list this cell, and only cells of a
    # configuration that holds a share of its experts (membership: nothing
    # on which entry is last, how many there are, or where a cell stands)
    lists = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    config_of = {w["name"]: w["config"] for w in bench["workloads"]}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for name in ("moe.experts_share", "moe.route_share", "moe.dropped_slots",
                 "moe.slots_held", "kernels.gmm_roofline"):
        assert CELL in lists[name]
        for cell in lists[name]:
            with open(os.path.join(ROOT, files[config_of[cell]])) as f:
                assert "num_experts_held" in json.load(f), (name, cell)


def test_the_adapters_configuration_is_the_share_the_file_states():
    from chipbench.models import afmoe_decoder as adapter
    spec, _ = _work()
    m = spec["config"]
    cfg = adapter.transformer_config(m, m["assumed"], 8192)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.dense_layers == ("sliding",) and cfg.periods == 1
    assert cfg.layer_pattern == ("sliding", "sliding", "sliding", "full")
    assert cfg.expert_share == (0, 32) and cfg.num_experts == 128
    assert cfg.vocab_size == 50048 and cfg.norm_eps == 1e-5
    dense, expert, top = adapter.weight_shapes(m)
    size = lambda leaves: sum(  # noqa: E731
        __import__("math").prod(shape) for shape, _ in leaves.values())
    # 1211 M parameters: the dense layer, four expert layers, the top
    assert size(dense) + 4 * size(expert) + size(top) == 1210625792


def _readers_find(run_like):
    return {n: run.metric_reader(n)(run_like)
            for n in ("moe.experts_share", "moe.route_share",
                      "moe.dropped_slots")}


def test_the_new_readers_read_scopes_and_counters_or_find_nothing():
    scopes = {"mx.moe_experts": {"forward": 1.0, "backward": 2.0,
                                 "recompute": 1.0},
              "mx.moe_route": {"forward": 0.5, "backward": 0.0,
                               "recompute": 0.5},
              "mx.moe_combine": {"forward": 1.0, "backward": 1.0,
                                 "recompute": 0.0},
              "mx.ffn": {"forward": 5.0, "backward": 5.0, "recompute": 3.0}}
    got = _readers_find({"trace": {"scopes": scopes, "op_sum_s": 20.0},
                         "counters": {"slots_dropped": 0, "layers": 8}})
    assert got == {"moe.experts_share": pytest.approx(20.0),
                   "moe.route_share": pytest.approx(15.0),
                   "moe.dropped_slots": 0.0}
    # a program without the scopes or the counter (the parent): nothing
    assert set(_readers_find({"trace": {"scopes": {"mx.ffn": {}},
                                        "op_sum_s": 1.0},
                              "counters": None}).values()) == {None}
    assert set(_readers_find({"trace": {"op_sum_s": 1.0}}).values()) == {None}


def _run(wrap=None):
    import jax
    return run.run_cell(TINY_CELL, SEED, 0.2, False,
                        devices=jax.devices()[:1], wrap=wrap, root=TINY)


def test_a_tiny_share_runs_correct_and_drops_no_slot():
    r = _run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["compared"]) == {"loss1", "grad_norm_gap",
                                  "grad_norm_gap_med", "delta_norm_gap",
                                  "delta_norm_gap_med"}
    assert all(v <= lim for v, lim in r["compared"].values())
    from mxnet_tpu import profiler
    moe = profiler.metrics()["moe"]
    # 2 expert layers a step, 256 tokens x 4 slots each, a quarter held on
    # average; nothing dropped
    assert moe["layers"] >= 2 * (3 + r["attempted"])
    assert moe["slots_dropped"] == 0 and moe["max_load"] > 0
    assert 0.5 < moe["mean_load"] / (256 * 4 / 16) < 2.0
    json.dumps(r)


def _no_window(cell):
    """The sliding layers computed as full: the step rebuilt without its
    window."""
    from chipbench.models import afmoe_decoder as adapter
    from mxnet_tpu.parallel import transformer as T
    cfg = dataclasses.replace(adapter.transformer_config(
        cell.m, cell.a, cell.t["seq_len"]), window=None)
    _, cell.step_fn = T.make_train_step(
        cfg, cell.mesh, learning_rate=cell.a["learning_rate"])
    return cell


def _expert_missing(cell):
    """One held expert left out: its three matrices are nought in every
    expert layer, so it adds nothing and its gradient is nought too."""
    params, mom = cell.state
    layers = dict(params["layers"])
    for n in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        layers[n] = layers[n].at[:, :, 0].set(0)
    cell.state = (dict(params, layers=layers), mom)
    return cell


@pytest.mark.parametrize("fault", [_no_window, _expert_missing])
def test_a_share_without_its_windows_or_an_expert_is_not_correct(fault):
    r = _run(wrap=fault)
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert "grad_norm_gap" in over and "grad_norm_gap_med" in over
