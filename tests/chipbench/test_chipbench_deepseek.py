"""The ``deepseek_decoder`` kind (one chip's share of a deepseek_v3 decoder:
latent attention over a share of the heads, YaRN positions, group-limited
routing over a share of the experts) as chipbench has it: its count's
integers at the cell's shapes, the cell's files, the new reader, and
``run_cell`` end to end at toy widths on the CPU from a tiny root of its
own: a run comes out correct, a program that routes without the group limit
does not. Device metrics are never asserted here: a CPU run has none."""
import dataclasses
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_deepseek")
CELL, TINY_CELL = "gigachat3_1.ep32.l5-seq4096x1", "tiny_deepseek-seq128"
SEED = 3000000019       # past 2**31, as a benchmark run's seeds may be


def _work():
    spec = run.load_cell(CELL)
    t = spec["traffic"]
    return spec, {"model": spec["config"], "batch": t["batch"],
                  "seq_len": t["seq_len"], "dtype": "bfloat16"}


def test_the_counts_integers_at_the_cells_shapes():
    """By hand, multiply-adds a token: a layer's latent stage 7168 * 1536 +
    1536 * 8 * 192 + 7168 * 576 + 512 * 8 * 320 = 18,808,832 and its output
    rows 8 * 192 * 7168 = 11,010,048; attention 8 heads * (192 + 192) *
    8,390,656 kept pairs / 4096 tokens; the dense layer 3 * 7168 * 18432;
    an expert layer the shared expert 3 * 7168 * 2048, the router 7168 *
    256 and 4096 * 8 * 8 / 256 = 1024 slots of 3 * 7168 * 2048; the head
    7168 * 16032. 919.4 M multiply-adds a token, 5.52 GF a token forward
    and backward, 22.59 TF a step: the round figures hold."""
    spec, work = _work()
    count = run.load_named("counts", "deepseek_decoder")
    assert count.kept_pairs(4096) == 4096 * 4097 // 2 == 8390656
    assert count.slots_held(spec["config"], 4096) == 1024
    tokens, expert = 4096, 3 * 7168 * 2048
    latent = 7168 * 1536 + 1536 * 8 * 192 + 7168 * 576 + 512 * 8 * 320
    assert latent == 18808832
    pairs = 5 * 8 * 384 * 8390656
    macs = (tokens * (5 * (latent + 8 * 192 * 7168) + 3 * 7168 * 18432
                      + 4 * (expert + 7168 * 256) + 7168 * 16032)
            + 4 * 1024 * expert + pairs)
    assert macs == 3765778251776
    r = spec["required"](work)
    assert r == {"step_flops": 6 * macs,
                 "kernels": {
                     # causal pairs at d = 192 for the 8 heads held, q k^T
                     # and p v, forward once and backward twice
                     "mx_flash_": {"flops": 3 * 2 * pairs,
                                   "bytes": 6 * 16 * 192 * tokens * 2 * 5},
                     # nine grouped products a layer: 1024 slots of width
                     # 7168 and 2048 between them, 8 matrices 7168 x 2048
                     "mx_gmm_": {"flops": 3 * 4 * 1024 * 2 * expert,
                                 "bytes": 9 * (1024 * 9216
                                               + 8 * 7168 * 2048) * 2 * 4}}}
    assert r["step_flops"] == 22594669510656
    assert r["kernels"]["mx_flash_"]["flops"] == 773282856960
    assert round(macs / tokens / 1e6, 1) == 919.4
    assert round(r["step_flops"] / tokens / 1e9, 2) == 5.52
    assert round(r["step_flops"] / 1e12, 1) == 22.6


# the row of the model-configs catalog (ai-sage/GigaChat3.1-702B-A36B,
# config.json), every number as published
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 192, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4,
    "num_experts_per_tok": 8, "moe_layer_freq": 1,
    "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "deepseek_v3"}


def test_the_configuration_is_the_catalogs_at_the_published_widths():
    spec, _ = _work()
    held, bench = spec["config"], spec["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == held["name"])
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/ai-sage/GigaChat3.1-702B-A36B/blob/main/"
        "config.json")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers",
                                "num_experts_held", "heads_held",
                                "vocab_rows_held"]
    assert set(held["reduced_why"]) == set(held["published"]) \
        == set(entry["reduced"])
    # every key of the row as published, but the cut in depth
    changed = {k for k, v in PUBLISHED.items() if held[k] != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "num_nextn_predict_layers"}
    assert all(held["published"][k] == PUBLISHED[k] for k in changed)
    assert (held["num_hidden_layers"], held["first_k_dense_replace"],
            held["num_nextn_predict_layers"]) == (5, 1, 0)
    # the share: 32-way experts, 8-way heads and vocabulary
    assert held["num_experts_held"] * 32 == held["n_routed_experts"]
    assert held["heads_held"] * 8 == held["num_attention_heads"]
    assert held["vocab_rows_held"] * 8 == held["vocab_size"]
    assert held["published"]["vocab_rows_held"] == held["vocab_size"]
    assert set(held["assumed"]) >= {"dtype", "optimizer", "learning_rate",
                                    "remat", "loss_chunks", "init",
                                    "router_bias", "group_masking",
                                    "rope_pairing"}
    lists = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    for name in ("moe.experts_share", "moe.route_share", "moe.dropped_slots",
                 "moe.slots_held", "kernels.gmm_roofline",
                 "kernels.flash_roofline", "kernels.mosaic_share",
                 "mla.latent_share", "step.mfu", "device.peak_hbm_gb"):
        assert CELL in lists[name], name


def test_the_adapters_configuration_is_the_share_the_file_states():
    from chipbench.models import deepseek_decoder as adapter
    spec, _ = _work()
    m = spec["config"]
    cfg = adapter.transformer_config(m, m["assumed"], 4096)
    assert (cfg.attention, cfg.n_heads, cfg.heads_here, cfg.head_dim) == (
        "mla", 64, 8, 192)
    assert (cfg.q_latent, cfg.kv_latent, cfg.nope_head, cfg.rope_head) \
        == (1536, 512, 128, 64)
    # a v head of another size than q's and k's is refused where the
    # published value arrives: the kernels take one head size
    with pytest.raises(ValueError, match="one head size"):
        adapter.transformer_config(dict(m, v_head_dim=128), m["assumed"],
                                   4096)
    assert cfg.dense_layers == ("full",) and cfg.layer_pattern == ()
    assert cfg.expert_share == (0, 8) and cfg.num_experts == 256
    assert (cfg.route_groups, cfg.route_groups_kept, cfg.moe_k) == (8, 4, 8)
    assert cfg.vocab_size == 16032 and cfg.norm_eps == 1e-6
    assert cfg.scores_scale == pytest.approx(
        192 ** -0.5 * (1 + 0.1 * math.log(64)) ** 2)
    dense, expert, top = adapter.weight_shapes(m)
    size = lambda leaves: sum(  # noqa: E731
        math.prod(shape) for shape, _ in leaves.values())
    # by hand: 426.2 M, 428.0 M a layer, 229.8 M the top;
    # 2.368 B parameters, 14.21 GB at 6 bytes a parameter
    assert round(size(dense) / 1e6, 1) == 426.2
    assert round(size(expert) / 1e6, 1) == 428.0
    assert round(size(top) / 1e6, 1) == 229.8
    total = size(dense) + 4 * size(expert) + size(top)
    assert total == 2368167936 and round(6 * total / 1e9, 2) == 14.21


def test_the_new_reader_reads_its_scope_or_finds_nothing():
    read = run.metric_reader("mla.latent_share")
    scopes = {"mx.mla_latent": {"forward": 1.0, "backward": 2.0,
                                "recompute": 1.0},
              "mx.ffn": {"forward": 5.0, "backward": 5.0, "recompute": 3.0}}
    assert read({"trace": {"scopes": scopes, "op_sum_s": 20.0}}) \
        == pytest.approx(20.0)
    # a program without the scope (the parent), or no program text
    assert read({"trace": {"scopes": {"mx.ffn": {}}, "op_sum_s": 1.0}}) \
        is None
    assert read({"trace": {"op_sum_s": 1.0}}) is None


@pytest.fixture(scope="module")
def tiny_run():
    """One run of the tiny cell; the reference it was judged against is
    kept for the fault below."""
    import jax
    kept = {}

    def keep(cell):
        made = cell.reference

        def reference(steps, variant="exact"):
            kept["reference"] = made(steps, variant)
            return kept["reference"]

        cell.reference = reference
        return cell

    r = run.run_cell(TINY_CELL, SEED, 0.2, False, devices=jax.devices()[:1],
                     wrap=keep, root=TINY)
    return r, kept["reference"]


def test_a_tiny_share_runs_correct_and_drops_no_slot(tiny_run):
    r, _ = tiny_run
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["compared"]) == {"loss1", "grad_norm_gap",
                                  "grad_norm_gap_med", "delta_norm_gap",
                                  "delta_norm_gap_med"}
    from mxnet_tpu import profiler
    moe, mla = profiler.metrics()["moe"], profiler.metrics()["mla"]
    # 2 expert layers a step, 256 tokens x 4 slots each, a quarter held on
    # average; each token's 2 kept groups of 4 hold the share's one group
    # about half the time; nothing dropped
    assert moe["layers"] >= 2 * (3 + r["attempted"])
    assert moe["slots_dropped"] == 0 and moe["max_load"] > 0
    assert 0.5 < moe["mean_load"] / (256 * 4 / 16) < 2.0
    share = moe["tokens_to_held_groups"] / (moe["layers"] * 256)
    assert 0.2 < share < 0.8
    assert (mla["layers"], mla["heads_held"]) == (3, 4)
    json.dumps(r)


def test_a_share_that_routes_without_its_groups_is_not_correct(tiny_run):
    """The step rebuilt with one group of experts (every expert open to
    every token), judged against the reference the correct run had."""
    import jax
    from chipbench.models import deepseek_decoder as adapter
    from mxnet_tpu.parallel import transformer as T
    _, reference = tiny_run
    spec = run.load_cell(TINY_CELL, TINY)
    cell = adapter.build(spec["config"], spec["traffic"], SEED,
                         jax.devices()[:1])
    cfg = dataclasses.replace(adapter.transformer_config(
        cell.m, cell.a, cell.t["seq_len"]), route_groups=1,
        route_groups_kept=1)
    _, cell.step_fn = T.make_train_step(
        cfg, cell.mesh, learning_rate=cell.a["learning_rate"])
    program, _ = run.first_steps(cell)
    cell.free()
    correct, compared, _, _ = check.judge(program, reference, spec["limits"])
    assert correct is False
    over = [n for n, (v, lim) in compared.items() if not v <= lim]
    assert "grad_norm_gap" in over
