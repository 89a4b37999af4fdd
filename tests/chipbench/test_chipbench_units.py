"""chipbench's own arithmetic, on the CPU: the trace reduction on a
hand-made trace, the required-work counts against hand counts, and
BENCHMARK.json against the rules its files are found by."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, flops, run, trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

FUSION = ("%fusion.430 = bf16[8,2048,11008]{2,1,0:T(8,128)(2,1)} fusion("
          "bf16[8,2048,4096]{2,1,0:T(8,128)(2,1)} %remat2.225, bf16[11008,"
          "4096]{1,0:T(8,128)(2,1)S(1)} %custom-call.15), kind=kOutput")
FLASH = ("%checkpoint.22 = (bf16[256,2048,128]{2,1,0:T(8,128)(2,1)}, bf16["
         "256,2048,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[256,2048,128]"
         "{2,1,0:T(8,128)(2,1)} %pad.8), custom_call_target=\"tpu_custom_"
         "call\"")
WHILE = ("%while.15 = (s32[]{:T(128)}, /*index=5*/bf16[5,4096,11008]{2,1,0:"
         "T(8,128)(2,1)}) while((s32[]{:T(128)}) %tuple.1), condition=%c")
ALLRED = "%all-reduce.3 = f32[16]{0} all-reduce(f32[16]{0} %x), channel_id=1"


def _trace():
    """Two whole steps of 1000 us on two devices. Device 0 a step: a while
    from 0 to 900 holding a fusion (0-400), a flash call (400-600) and an
    all-reduce (650-900); idle 600-650 and 900-1000."""
    ops, modules, us = [], [], 1000.0
    for k in range(3):
        t = 1000 * us * k
        modules.append(("jit_step_fn(1)", t, 900 * us))
        ops += [(WHILE, t, 900 * us), (FUSION, t, 400 * us),
                (FLASH, t + 400 * us, 200 * us),
                (ALLRED, t + 650 * us, 250 * us)]
    modules.append(("jit_small(2)", 2950 * us, 10 * us))
    dev1 = {"ops": [(FUSION, 0.0, 2000 * us)], "modules": modules}
    host = [("chipbench.read_loss", 0.0, 2500 * us)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules},
                        "/device:TPU:1": dev1}, "host": host}


def test_opcodes_are_read_from_the_instruction_not_its_operands():
    assert trace.opcode(FUSION) == "fusion"
    assert not trace.is_mosaic(FUSION)        # %custom-call.15 is an operand
    assert trace.is_mosaic(FLASH) and not trace.is_collective(FLASH)
    assert trace.opcode(WHILE) == "while"
    assert trace.is_collective(ALLRED)
    assert trace.is_collective("%ar = f32[4] all-reduce-start(f32[4] %y)")
    assert trace.opcode("not hlo text") == "not hlo text"


def test_trace_reduction_on_a_hand_made_trace():
    r = trace.reduce(_trace())
    assert r["steps"] == 2 and r["chips"] == 2
    assert r["window_s"] == pytest.approx(2000e-6)
    assert r["step_s"] == pytest.approx(1000e-6)
    # device 0 is busy 0-900 of each 1000 (the while covers the inner gap);
    # device 1 all the time; busy_s is their mean
    assert r["idle_share"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((1800 + 2000) / 2 * 1e-6)
    # the while is a container: its body's ops are counted, it is not
    assert r["op_sum_s"] == pytest.approx(2 * 850e-6)
    assert r["mosaic_s"] == pytest.approx(2 * 200e-6)
    assert r["collective_s"] == pytest.approx(2 * 250e-6)
    assert all(trace.opcode(n) != "while" for n, _ in r["device_ops"])
    assert r["device_ops"][0][1] == pytest.approx(800e-6)
    gaps = dict(r["idle_gaps"])
    assert gaps["chipbench.read_loss"] == pytest.approx(200e-6)


def test_a_trace_without_a_whole_step_is_refused():
    t = _trace()
    t["devices"]["/device:TPU:0"]["modules"] = [("jit_step_fn(1)", 0.0, 9.0)]
    with pytest.raises(ValueError):
        trace.reduce(t)
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


BAICHUAN = {"hidden_size": 4096, "intermediate_size": 11008,
            "vocab_size": 64000, "num_hidden_layers": 5}


@pytest.mark.parametrize("batch,seq,gf_per_token,attn_tf", [
    # by hand, per token and layer: 8*4096^2 + 6*4096*11008 = 404,750,336
    # products' flops; causal attention 4*4096*(S+1)/2; the head 2*4096*64000
    (8, 2048, 3 * (5 * (404750336 + 8192 * 2049) + 524288000) / 1e9,
     3 * 5 * 8192 * 2049 * 16384 / 1e12),
    (1, 16384, 3 * (5 * (404750336 + 8192 * 16385) + 524288000) / 1e9,
     3 * 5 * 8192 * 16385 * 16384 / 1e12),
])
def test_required_flops_of_baichuan_7b(batch, seq, gf_per_token, attn_tf):
    r = flops.required({"kind": "dense_decoder", "model": BAICHUAN,
                        "batch": batch, "seq_len": seq, "dtype": "bfloat16"})
    assert r["step_flops"] / (batch * seq) / 1e9 == pytest.approx(
        gf_per_token, rel=1e-12)
    assert r["attention_flops"] / 1e12 == pytest.approx(attn_tf, rel=1e-12)
    assert r["attention_bytes"] == 12 * batch * seq * 4096 * 2 * 5
    # the issue's round figures: 7.9 GF a token and 4.1 TF of attention at 2k
    if seq == 2048:
        assert round(gf_per_token, 1) == 7.9 and round(attn_tf, 1) == 4.1


def test_required_flops_of_resnet50_v1():
    convs = dict((n, m) for m, n in flops.resnet_v1_convs())
    assert convs["conv0"] == 112 * 112 * 64 * 3 * 49
    assert convs["stage1.block0.conv3x3"] == 56 * 56 * 64 * 64 * 9
    # v1: the stride sits on the first 1x1, so stage 2's 3x3 runs at 28x28
    assert convs["stage2.block0.conv1x1a"] == 28 * 28 * 256 * 128
    assert convs["stage2.block0.shortcut"] == 28 * 28 * 256 * 512
    assert convs["dense"] == 2048 * 1000
    assert len(convs) == 1 + 16 * 3 + 4 + 1
    macs = sum(convs.values())
    assert 3.8e9 < macs < 3.9e9     # He et al., table 1: 3.8e9 for 50 layers
    r = flops.required({"kind": "resnet_v1", "batch": 128, "image": 224,
                        "model": {"layers": [3, 4, 6, 3], "classes": 1000}})
    assert r["step_flops"] == 6 * macs * 128


def test_leaf_gaps_are_gaps_of_norms_against_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 4.0, "c": 1e-9}
    got = {"a": 1.1, "b": 4.0, "c": 0.5}
    gaps = check.leaf_gaps(got, ref)
    assert gaps["a"] == pytest.approx(0.1)      # median is 1.0
    assert gaps["c"] == pytest.approx(0.5)      # held against the median
    prog = {"loss": [2.0], "grad_norm": ref, "delta_norm": got}
    refd = {"loss": [2.0], "grad_norm": ref, "delta_norm": ref}
    vals, worst = check.numbers(prog, refd)
    # c's gradient is nought to rounding: its change is not compared
    assert worst["delta_norm_gap"] == "a" and vals["loss1"] == 0.0
    ok, compared, skipped, _ = check.judge(prog, refd, {
        "loss1": 0, "grad_norm_gap": 0, "delta_norm_gap": 0.03,
        "not_compared": ["grad_norm_gap_med", "delta_norm_gap_med"]})
    assert set(skipped) == {"grad_norm_gap_med", "delta_norm_gap_med"}
    # a's gap of 0.1 is held against the median of the leaves that moved
    assert not ok and compared["delta_norm_gap"] == [pytest.approx(0.04), 0.03]
    with pytest.raises(KeyError):
        check.judge(prog, refd, {"loss1": 0})


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_names_units_and_keys_are_legal():
    b = _bench()
    assert sorted(b) == ["command", "configs", "end_to_end", "paths",
                         "per_layer", "run_seconds", "workloads"]
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = []
    for c in b["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    for w in b["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert w["config"] in [c["name"] for c in b["configs"]]
    for group, keys in (("end_to_end", {"bound"}), ("per_layer",
                                                    {"layer", "moves"})):
        for m in b[group]:
            assert set(m) - {"workloads"} == keys | {
                "name", "unit", "better", "source"}
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in b[group]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert [m["name"] for m in b["end_to_end"]] == ["step_ms", "setup_s"]
    assert all(0 < m["bound"] <= 0.1 for m in b["end_to_end"])
    for text in ([c["why"] for c in b["configs"] + b["workloads"]]
                 + [c["source"] for c in b["configs"]]
                 + [m["layer"] for m in b["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_cell_finds_its_files_by_name():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    for name in cells:
        spec = run.load_cell(name)
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "models", spec["config"]["adapter"] + ".py"))
        assert spec["config"]["flops"] in flops.KINDS
        # every number check.py computes has a limit or is named as not
        # compared; PERF.md gives the readings either way
        listed = set(spec["limits"]) | set(spec["limits"].get(
            "not_compared", ()))
        assert {"loss1", "loss2", "loss3", "grad_norm_gap",
                "grad_norm_gap_med", "delta_norm_gap",
                "delta_norm_gap_med"} <= listed
        assert {"grad_norm_gap", "delta_norm_gap"} & set(spec["limits"])
        reported = [m for m in b["per_layer"]
                    if name in m.get("workloads", cells)]
        assert reported and all(callable(run.metric_reader(m["name"]))
                                for m in reported)
    for m in b["per_layer"]:
        assert m["moves"] in ("step_ms", "setup_s")
        assert set(m["workloads"]) <= set(cells)   # every one opts in
    entry = {c["name"]: c for c in b["configs"]}
    for c in entry.values():
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"]
        for key in c["reduced"]:        # a width is never reduced
            assert key in held and not key.endswith(("_dim", "_rank", "_size"))
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12 and peaks["source"]
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")
