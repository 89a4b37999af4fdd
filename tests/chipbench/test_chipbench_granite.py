"""The ``granite_hybrid`` kind (one chip's share of a decoder of Mamba-2
mixers and attention layers with routed experts after each) as chipbench has
it: its count's integers at the cell's shapes, the cell's files against the
published configuration key by key, the two readers, and ``run_cell`` end to
end at toy widths on the CPU from a tiny root of its own: a run comes out
correct, a program whose scan drops its states or that lacks one of its
experts does not. Device metrics are never asserted here: a CPU run has
none."""
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

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny_granite")
CELL, TINY_CELL = "granite_4_h_small.ep8.l10-seq8192x1", "tiny_granite-seq128"
SEED = 3000000019       # past 2**31, as the driver's seeds are

# ibm-granite/granite-4.0-h-small config.json, as the catalog row has it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}
# the published list: an attention layer at 5, 15, 25 and 35
PUBLISHED["layer_types"] = [
    "attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)]


def _work():
    spec = run.load_cell(CELL)
    t = spec["traffic"]
    return spec, {"model": spec["config"], "batch": t["batch"],
                  "seq_len": t["seq_len"], "dtype": "bfloat16"}


def test_the_counts_integers_at_the_cells_shapes():
    """By hand, a token and layer: the mixer's in_proj 2*4096*16768 and
    out_proj 2*8192*4096, the conv 2*4*8448, the recurrence 5*64*128 a head
    of 128; attention's products 2*4096*128*(2*32+2*8); the router 2*4096*72,
    the shared expert 6*4096*1536, 8192*10*9/72 = 10240 slots of 6*4096*768;
    the head 2*4096*12544; 33,558,528 pairs kept a head."""
    spec, work = _work()
    count = run.load_named("counts", "granite_hybrid")
    tokens, expert = 8192, 6 * 4096 * 768
    assert count.kept_pairs(8192) == 8192 * 8193 // 2 == 33558528
    assert count.slots_held(spec["config"], tokens) == 10240
    parts = count.forward_parts(work)
    assert parts == {
        "mixer_proj": 9 * tokens * (2 * 4096 * 16768 + 2 * 8192 * 4096),
        "mixer_conv": 9 * tokens * 2 * 4 * 8448,
        "mixer_recurrence": 9 * tokens * 5 * 64 * 128 * 128,
        "attn_proj": tokens * 2 * 4096 * 128 * 80,
        "attn_pairs": 2 * 2 * 32 * 128 * 33558528,
        "router": 10 * tokens * 2 * 4096 * 72,
        "shared": 10 * tokens * 6 * 4096 * 1536,
        "routed": 10 * 10240 * expert,
        "head": tokens * 2 * 4096 * 12544}
    r = spec["required"](work)
    fwd = sum(parts.values())
    assert r == {"step_flops": 3 * fwd == 67857379491840 and 3 * fwd,
                 "kernels": {
                     "mx_flash_": {"flops": 3 * parts["attn_pairs"],
                                   "bytes": 6 * 40 * 128 * tokens * 2},
                     # nine grouped products a layer, ten layers: 10240
                     # slots of width 4096 and 768 between them, 9 matrices
                     "mx_gmm_": {"flops": 3 * 10 * 10240 * expert,
                                 "bytes": 9 * (10240 * 4864
                                               + 9 * 4096 * 768) * 2 * 10}}}
    # the issue's round figures: 67.9 TF a step, 2.76 GFLOP a token forward,
    # the mixers' own products 68% of it, the routed experts 9%
    assert round(r["step_flops"] / 1e12, 1) == 67.9
    assert round(fwd / tokens / 1e9, 2) == 2.76
    mixers = sum(v for n, v in parts.items() if n.startswith("mixer_"))
    assert round(100 * mixers / fwd) == 68
    assert round(100 * parts["routed"] / fwd) == 9


def test_the_configuration_is_the_published_one_key_by_key():
    spec, _ = _work()
    held, bench = spec["config"], spec["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == held["name"])
    assert entry["source"] == held["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_experts_held", "vocab_rows_held"]
    assert set(held["reduced_why"]) == set(held["published"]) \
        == set(entry["reduced"])
    # every key of the published file is in ours, unchanged unless reduced
    differ = sorted(k for k, v in PUBLISHED.items() if held[k] != v)
    assert differ == ["layer_types", "num_hidden_layers"]
    assert held["layer_types"] == PUBLISHED["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert held["num_hidden_layers"] == 10 == len(held["layer_types"])
    assert held["published"]["num_hidden_layers"] == 40
    assert (held["num_experts_held"], held["first_expert_held"]) == (9, 0)
    assert held["num_experts_held"] * 8 == held["num_local_experts"] \
        == held["published"]["num_experts_held"] == 72
    assert held["vocab_rows_held"] * 8 == held["vocab_size"] \
        == held["published"]["vocab_rows_held"] == 100352
    assert "8 chips" in held["deployment"] and "32-chip" in held["deployment"]
    assert not [k for k in entry["reduced"]
                if k.endswith(("_size", "_dim", "_rank"))]
    # the cell: one chip; it reports whatever the first cell reports, and
    # its mixers' two shares (membership: wherever it stands in a list, and
    # whatever else it or a later cell reports)
    cell = spec["cell"]
    assert (cell["chips"], cell["traffic"]) == (1, "seq8192x1")
    assert (spec["traffic"]["batch"], spec["traffic"]["seq_len"]) == (1, 8192)
    shared = {m["name"] for m in bench["per_layer"]
              if "baichuan_7b.l5-seq2048" in m["workloads"]}
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert shared and shared <= mine
    assert {"ssm.scan_share", "ssm.mixer_share"} <= mine


def test_the_adapters_configuration_is_the_share_the_file_states():
    from chipbench.models import granite_hybrid as adapter
    spec, _ = _work()
    m = spec["config"]
    cfg = adapter.transformer_config(m, m["assumed"], 8192)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 128)
    assert cfg.layer_pattern == ("mamba",) * 5 + ("full",) + ("mamba",) * 4
    assert cfg.periods == 1 and cfg.attn_layers == 1 and not cfg.dense_layers
    assert cfg.expert_share == (0, 9) and cfg.num_experts == 72
    assert (cfg.moe_k, cfg.moe_hidden, cfg.moe_shared) == (10, 768, 2)
    assert cfg.route == "topk_softmax" and cfg.rope_on == "none"
    assert cfg.vocab_size == 12544 and cfg.tied_head and cfg.norm_eps == 1e-5
    assert (cfg.residual_mult, cfg.embed_mult, cfg.logit_mult,
            cfg.attn_scale) == (0.22, 12.0, 1 / 16, 1 / 128)
    assert (cfg.ssm_heads, cfg.ssm_head_size, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (128, 64, 128, 4, 256)
    assert cfg.remat_save is None and cfg.loss_chunks == 8
    assert adapter.runs_of(m) == [("mamba", "mamba", 5),
                                  ("layers", "attention", 1),
                                  ("mamba_1", "mamba", 4)]
    mixer, attn, top = adapter.weight_shapes(m)
    size = lambda leaves: sum(  # noqa: E731
        math.prod(shape) for shape, _ in leaves.values())
    # a mixer layer 206.4 M, the attention layer 146.0 M, the rows 51.4 M
    assert (size(mixer), size(attn), size(top)) == (206399104, 146055168,
                                                    51384320)
    assert 9 * size(mixer) + size(attn) + size(top) == 2055031424
    assert mixer["ssm_in"][0] == (4096, 8192 + 8448 + 128)
    # the program's own table agrees, leaf for leaf
    from mxnet_tpu.parallel import transformer as T
    for table, kind in ((mixer, "mamba"), (attn, "full")):
        assert {n: s for n, (s, _) in table.items()} == {
            n: s for n, (s, _, _) in T._layer_leaves(cfg, True, kind).items()}


def _readers_find(run_like):
    return {n: run.metric_reader(n)(run_like)
            for n in ("ssm.scan_share", "ssm.mixer_share")}


def test_the_two_readers_read_scopes_or_find_nothing():
    scopes = {"mx.ssm_scan": {"forward": 1.0, "backward": 2.0,
                              "recompute": 1.0},
              "mx.ssm_proj": {"forward": 2.0, "backward": 4.0,
                              "recompute": 2.0},
              "mx.ssm_conv": {"forward": 0.5, "backward": 1.0,
                              "recompute": 0.5},
              "mx.ssm_gate": {"forward": 0.5, "backward": 1.0,
                              "recompute": 0.5},
              "mx.ffn": {"forward": 5.0, "backward": 5.0, "recompute": 3.0}}
    got = _readers_find({"trace": {"scopes": scopes, "op_sum_s": 40.0}})
    assert got == {"ssm.scan_share": pytest.approx(10.0),
                   "ssm.mixer_share": pytest.approx(40.0)}
    # a program without the scopes (the parent), or no program text: nothing
    assert set(_readers_find({"trace": {"scopes": {"mx.ffn": {}},
                                        "op_sum_s": 1.0}}).values()) == {None}
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
    # 4 expert layers a step, 256 tokens x 4 slots each, a quarter held on
    # average; nothing dropped
    assert moe["layers"] >= 4 * (3 + r["attempted"])
    assert moe["slots_dropped"] == 0 and moe["max_load"] > 0
    assert 0.5 < moe["mean_load"] / (256 * 4 / 16) < 2.0
    assert profiler.metrics()["ssm"]["layers"] == 3
    json.dumps(r)


def _expert_missing(cell):
    """One held expert left out: its three matrices are nought in every
    layer, so it adds nothing and its gradient is nought too."""
    params, mom = cell.state
    params = dict(params)
    for stack in ("mamba", "layers", "mamba_1"):
        layers = dict(params[stack])
        for n in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            layers[n] = layers[n].at[:, :, 0].set(0)
        params[stack] = layers
    cell.state = (params, mom)
    return cell


def test_a_share_without_an_expert_is_not_correct():
    r = _run(wrap=_expert_missing)
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert "grad_norm_gap" in over


def _leaf_unchanged(cell):
    """One leaf's update dropped: the first mixer layer's ``ssm_out`` is
    put back after every step; every other leaf of every layer moves."""
    # a slice is an array of its own: the step's donation leaves it whole
    real, first = cell.dispatch, cell.state[0]["mamba"]["ssm_out"][:, 0]

    def dispatch(i):
        loss = real(i)
        params, mom = cell.state
        mamba = dict(params["mamba"])
        mamba["ssm_out"] = mamba["ssm_out"].at[:, 0].set(first)
        cell.state = (dict(params, mamba=mamba), mom)
        return loss

    cell.dispatch = dispatch
    return cell


def test_a_leaf_that_one_layer_never_moves_is_not_correct():
    """The one fault only the WORST leaf's change sees: the gradients are
    sound, and the median leaf's change is."""
    r = _run(wrap=_leaf_unchanged)
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert over == ["delta_norm_gap"]
    assert r["worst_leaf"]["delta_norm_gap"] == "mamba.ssm_out"
    # one mixer layer of three: 1 - sqrt(2/3) where they move alike
    assert 0.05 < r["compared"]["delta_norm_gap"][0] < 0.5
    assert list(r)[-3:] == ["left_out", "not_compared", "compared"]
    assert isinstance(r["left_out"]["delta_norm_gap"], list)


def test_a_scan_that_drops_its_states_is_not_correct(monkeypatch):
    """Every chunk scanned as a sequence of its own: what a chunked scan
    that forgets to pass its states computes."""
    from mxnet_tpu.parallel import ssm
    whole = ssm.chunked_scan

    def dropped(xs, dt, a_head, bm, cm, d_head, chunk):
        b, s = xs.shape[:2]
        cut = lambda t: t.reshape((b * s // chunk, chunk) + t.shape[2:])  # noqa: E731
        return whole(cut(xs), cut(dt), a_head, cut(bm), cut(cm), d_head,
                     chunk).reshape(xs.shape)

    monkeypatch.setattr(ssm, "chunked_scan", dropped)
    r = _run()
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert "grad_norm_gap" in over and "delta_norm_gap" in over
