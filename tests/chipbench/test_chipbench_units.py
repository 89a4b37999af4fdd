"""chipbench's own arithmetic, on the CPU: the trace reduction on a
hand-made trace, the required-work counts against hand counts, and
BENCHMARK.json against the rules its files are found by."""
import copy
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, run, scopes, trace  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")

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
# a Pallas call's event is named by the kernel's ``name=`` (PERF.md)
KERNEL = ("%%%s = (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, f32[32,1,16384]"
          "{2,1,0:T(1,128)}) custom-call(bf16[32,16384,128]{2,1,0:T(8,128)"
          "(2,1)} %%bitcast.7), custom_call_target=\"tpu_custom_call\"")
FLASH_FWD, FLASH_DQ = KERNEL % "mx_flash_fwd.18", KERNEL % "mx_flash_dq.11"
GROUP = KERNEL % "mx_group_mm.3"
CUT = 400.0     # us of the execution that was running when the trace began
# the compiled step's text for the events above, as this runtime writes it
TEXT = """
HloModule jit_step_fn, is_scheduled=true
%fused_computation.9 (p0: bf16[8,2048,4096]) -> bf16[8,2048,11008] {
  ROOT %convolution.5 = bf16[8,2048,11008]{2,1,0} convolution(%p0, %p1), metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.ffn/bsd,df->bsf/dot_general" stack_frame_id=9}
}
  %fusion.430 = bf16[8,2048,11008]{2,1,0:T(8,128)(2,1)} fusion(%remat2.225, %custom-call.15), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.ffn/bsd,df->bsf/dot_general" stack_frame_id=9}
  %checkpoint.22 = (bf16[256,2048,128]{2,1,0}) custom-call(%pad.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(mx.layer)/while/body/closed_call/mx.flash/checkpoint"}
  %mx_flash_fwd.18 = (bf16[32,16384,128]{2,1,0}) custom-call(%bitcast.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/rematted_computation/mx.flash/mx_flash_fwd" stack_frame_id=4}
  %mx_flash_dq.11 = (bf16[32,16384,128]{2,1,0}) custom-call(%bitcast.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.flash/mx_flash_dq"}
  %mx_group_mm.3 = (bf16[32,16384,128]{2,1,0}) custom-call(%bitcast.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(mx.layer)/while/body/closed_call/mx.ffn/mx_group_mm"}
  %all-reduce.3 = f32[16]{0} all-reduce(%x), channel_id=1, metadata={op_name="jit(step_fn)/mx.optimizer/psum"}
  %while.15 = (s32[]) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(step_fn)/jvp(mx.layer)/while"}
"""


def _trace(whole=2):
    """``whole`` whole steps of 1000 us on two devices, after the execution
    that was running when the trace began: recorded from the trace's start,
    so only its last 400 us are there (the end of a flash call, an
    all-reduce). Device 0 a step: a while from 0 to 900 holding a fusion
    (0-400), a flash forward (400-500) and a flash dq (500-600), a grouped
    product of a second kernel family (600-650) and an all-reduce (650-900);
    idle 900-1000."""
    us = 1000.0
    modules = [("jit_step_fn(1)", 0.0, (CUT - 100) * us)]
    ops = [(WHILE, 0.0, 300 * us), (FLASH_FWD, 0.0, 50 * us),
           (ALLRED, 50 * us, 250 * us)]
    for k in range(whole + 1):
        t = (CUT + 1000 * k) * us
        modules.append(("jit_step_fn(1)", t, 900 * us))
        ops += [(WHILE, t, 900 * us), (FUSION, t, 400 * us),
                (FLASH_FWD, t + 400 * us, 100 * us),
                (FLASH_DQ, t + 500 * us, 100 * us),
                (GROUP, t + 600 * us, 50 * us),
                (ALLRED, t + 650 * us, 250 * us)]
    end = CUT + 1000 * (whole + 1)
    modules.append(("jit_small(2)", (end - 50) * us, 10 * us))
    dev1 = {"ops": [(FUSION, 0.0, end * us)], "modules": modules}
    host = [("chipbench.read_loss", 0.0, (CUT + 1900) * us),
            ("chipbench.dispatch", (CUT + 1900) * us, 100 * us),
            ("mx.train_step", (CUT + 1910) * us, 80 * us)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules},
                        "/device:TPU:1": dev1}, "host": host}


def test_opcodes_are_read_from_the_instruction_not_its_operands():
    assert trace.opcode(FUSION) == "fusion"
    assert not trace.is_mosaic(FUSION)        # %custom-call.15 is an operand
    assert trace.is_mosaic(FLASH) and not trace.is_collective(FLASH)
    assert trace.instruction(FUSION) == "fusion.430"
    assert trace.kernel_name(FLASH_FWD) == "mx_flash_fwd"
    assert trace.kernel_name(GROUP) == "mx_group_mm"
    assert trace.kernel_name(FLASH) == "checkpoint"   # a kernel with no name=
    # the compiler's own custom calls are no kernels
    assert not trace.is_mosaic(
        '%custom-call.7 = f32[8]{0:T(128)} custom-call(), '
        'custom_call_target="AllocateBuffer"')
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
    assert r["op_sum_s"] == pytest.approx(2 * 900e-6)
    assert r["mosaic_s"] == pytest.approx(2 * 250e-6)
    assert r["collective_s"] == pytest.approx(2 * 250e-6)
    assert all(trace.opcode(n) != "while" for n, _ in r["device_ops"])
    assert r["device_ops"][0][1] == pytest.approx(800e-6)
    # no program text: raw instruction text, no scopes
    assert r["device_ops"][0][0] == FUSION[:160]
    assert "scopes" not in r and "phases" not in r
    # a step's last 100 us are idle: the first step's under read_loss, the
    # second's under dispatch and, inside it, the program's own call, which
    # as the innermost span is the one named
    gaps = dict(r["idle_gaps"])
    assert gaps["chipbench.read_loss"] == pytest.approx(100e-6)
    assert gaps["mx.train_step"] == pytest.approx(100e-6)


def test_a_trace_without_a_whole_step_is_refused():
    t = _trace()
    t["devices"]["/device:TPU:0"]["modules"] = [("jit_step_fn(1)", 0.0, 9.0)]
    with pytest.raises(ValueError):
        trace.reduce(t)
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


def test_the_cut_first_execution_is_left_out_and_two_executions_refused():
    r = trace.reduce(_trace())
    # first start to last start would read 2400 us over 3 steps: 800 a step
    assert r["window_s"] == pytest.approx(2000e-6) and r["steps"] == 2
    # nothing of the cut execution's 400 us: its flash call is no call
    assert r["kernels"]["mx_flash_fwd"]["calls"] == 2
    assert trace.reduce(_trace(whole=1))["steps"] == 1
    with pytest.raises(ValueError, match="2 executions"):
        trace.reduce(_trace(whole=0))   # the cut one and one start: no step


def test_each_kernel_family_has_its_own_seconds_and_calls():
    r = trace.reduce(_trace())
    assert set(r["kernels"]) == {"mx_flash_fwd", "mx_flash_dq",
                                 "mx_group_mm"}      # all, and no container
    for name, us in (("mx_flash_fwd", 100), ("mx_flash_dq", 100),
                     ("mx_group_mm", 50)):
        assert r["kernels"][name]["calls"] == 2
        assert r["kernels"][name]["s"] == pytest.approx(2 * us * 1e-6)
    assert r["mosaic_s"] == pytest.approx(
        sum(k["s"] for k in r["kernels"].values()))


def test_scopes_and_phases_where_the_program_text_is_given():
    r = trace.reduce(_trace(), TEXT)
    s = r["scopes"]
    assert s["mx.ffn"]["backward"] == pytest.approx(2 * 400e-6)
    assert s["mx.ffn"]["forward"] == pytest.approx(2 * 50e-6)    # the group
    assert s["mx.flash"]["recompute"] == pytest.approx(2 * 100e-6)
    assert s["mx.flash"]["backward"] == pytest.approx(2 * 100e-6)
    assert s["mx.optimizer"]["forward"] == pytest.approx(2 * 250e-6)
    p = r["phases"]
    assert p == {"forward": pytest.approx(100e-6),
                 "backward": pytest.approx(1000e-6),
                 "recompute": pytest.approx(200e-6),
                 "optimizer": pytest.approx(500e-6)}
    assert sum(p.values()) == pytest.approx(r["op_sum_s"])
    # the result line's device_ops: scope, phase, kernel or opcode
    assert r["device_ops"][:2] == [
        ["mx.ffn backward fusion", pytest.approx(800e-6)],
        ["mx.optimizer forward all-reduce", pytest.approx(500e-6)]]
    assert ["mx.flash recompute mx_flash_fwd",
            pytest.approx(200e-6)] in r["device_ops"]
    # an instruction the text does not have is unscoped, not lost
    r = trace.reduce(_trace(), "HloModule empty")
    assert r["scopes"] == {"unscoped": {
        "forward": pytest.approx(r["op_sum_s"]), "backward": 0.0,
        "recompute": 0.0}}


def test_the_scope_parser_on_lines_of_compiled_text():
    """Lines as XLA wrote them for the tiny decoder (CPU) and, for the
    kernel and the fusion, as the v5e's traces name them."""
    m = scopes.scope_map(TEXT + """
  %add.215 = f32[4,128,64]{2,1,0} add(%convert.4306, %bitcast.133), metadata={op_name="jit(step_fn)/jvp(mx.layer)/while/body/closed_call/mx.ffn/add" stack_frame_id=92}
  %add_any.192 = f32[512,64]{1,0} add(%convert.4400, %convert.4398), metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.ffn/bsd,df->bsf/add_any" stack_frame_id=9}
  %broadcast.445 = f32[4,128,64]{2,1,0} broadcast(%convert.4402), dimensions={2}, metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/rematted_computation/mx.ffn/mul" stack_frame_id=9}
  %subtract.13 = f32[2,64]{1,0} subtract(%a, %b), metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/sub"}
  %broadcast.477 = f32[64]{0} broadcast(%c), metadata={op_name="jit(step_fn)/mx.optimizer/mul" stack_frame_id=3}
  %reduce_sum.253 = f32[] reduce(%x, %zero), dimensions={0}, metadata={op_name="reduce_sum"}
  %constant.437 = f32[] constant(0)
""")
    at = lambda n: scopes.classify(m.get(n, ""))
    assert at("add.215") == ("mx.ffn", "forward")        # innermost wins
    assert at("add_any.192") == ("mx.ffn", "backward")   # a bare checkpoint/
    assert at("broadcast.445") == ("mx.ffn", "recompute")
    assert at("subtract.13") == ("mx.layer", "backward")
    assert at("broadcast.477") == ("mx.optimizer", "forward")
    assert at("reduce_sum.253") == at("constant.437") == ("unscoped",
                                                          "forward")
    assert "constant.437" not in m
    assert at("convolution.5") == at("fusion.430")       # a ROOT line too
    assert at("mx_flash_fwd.18") == ("mx.flash", "recompute")


def _run_of(reduced, cell="tiny_newkind-seq128"):
    """What ``run_cell`` hands a reader, from a reduced trace: the count
    found by the configuration's name, two chips of round peaks."""
    spec = run.load_cell(cell, TINY)
    work = {"batch": 4, "seq_len": 128, "model": spec["config"],
            "dtype": "bfloat16"}
    return spec, {"trace": reduced, "required": spec["required"](work),
                  "chips": 2, "work": work,
                  "peaks": {"bf16_flops": 1e10, "hbm_bytes_per_s": 1e9}}


def test_a_kind_a_kernel_roofline_and_a_scope_share_arrive_as_files():
    """Nothing under chipbench/ knows this kind, this kernel family or this
    scope's share: a count under the tiny root's counts/, two readers under
    its metrics/, entries in its BENCHMARK.json."""
    for group, name in (("counts", "tiny_two_families"),
                        ("metrics", "kernels.group_roofline"),
                        ("metrics", "scope.ffn_bwd_share")):
        assert os.path.exists(os.path.join(TINY, group, name + ".py"))
        assert not os.path.exists(os.path.join(ROOT, "chipbench", group,
                                               name + ".py"))
    spec, made = _run_of(trace.reduce(_trace(), TEXT))
    assert made["required"]["step_flops"] == 6000 * 512
    read = lambda name: run.metric_reader(name, spec["data"])(made)
    # 512 tokens x 100 bytes at 1 GB/s over two chips = 25.6 us of the 50
    # the group kernel takes a step on a chip: bound by bytes
    assert read("kernels.group_roofline") == pytest.approx(51.2)
    assert read("scope.ffn_bwd_share") == pytest.approx(100 * 800 / 1800)
    # the harness's own readers, found from the same root
    # 512 tokens x 1000 flops at 10 GF/s over two chips = 25.6 us of 200
    assert read("kernels.flash_roofline") == pytest.approx(12.8)
    reported = {m["name"]: read(m["name"])
                for m in run.metrics_of(spec["bench"],
                                        "tiny_newkind-seq128", "per_layer")}
    assert {"kernels.group_roofline", "scope.ffn_bwd_share", "step.mfu",
            "kernels.flash_roofline"} <= set(reported)
    assert all(0 < v <= 100 for v in reported.values())


def test_a_reader_finds_nothing_where_there_is_nothing_to_read():
    spec, made = _run_of(trace.reduce(_trace()))        # no program text
    for name in ("step.fwd_share", "step.bwd_share", "step.recompute_share",
                 "step.optimizer_share", "scope.ffn_bwd_share"):
        assert run.metric_reader(name, spec["data"])(made) is None
    # a kind that counts no such family; a family of which no kernel ran
    spec, made = _run_of(trace.reduce(_trace()), "tiny_decoder-seq128")
    assert run.metric_reader("kernels.group_roofline",
                             spec["data"])(made) is None
    made["trace"]["kernels"] = {}
    assert run.metric_reader("kernels.flash_roofline")(made) is None


def test_no_share_of_the_hand_made_trace_exceeds_100():
    """The least time of a sound count is never more than the time taken:
    the dense decoder's own count on the tiny decoder's shapes against
    kernels that take their roofline's time exactly reads 100, and the four
    phases sum to 100."""
    spec, made = _run_of(trace.reduce(_trace(), TEXT), "tiny_decoder-seq128")
    need = made["required"]["kernels"]["mx_flash_"]
    made["peaks"] = {"bf16_flops": need["flops"] / 2 / 200e-6,
                     "hbm_bytes_per_s": 1e30}
    assert run.metric_reader("kernels.flash_roofline")(made) == \
        pytest.approx(100.0)
    shares = [run.metric_reader("step.%s_share" % p)(made)
              for p in ("fwd", "bwd", "recompute", "optimizer")]
    assert all(0 <= v <= 100 for v in shares)
    assert sum(shares) == pytest.approx(100.0)
    assert shares[3] == pytest.approx(100 * 500 / 1800)
    assert run.metric_reader("kernels.mosaic_share")(made) == \
        pytest.approx(100 * 500 / 1800)


def _count(kind, where=None):
    """``required`` of counts/<kind>.py, found as a cell's is."""
    return run.load_named("counts", kind, where or run.HERE).required


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
    r = _count("dense_decoder")({"model": BAICHUAN, "batch": batch,
                                 "seq_len": seq, "dtype": "bfloat16"})
    assert r["step_flops"] / (batch * seq) / 1e9 == pytest.approx(
        gf_per_token, rel=1e-12)
    flash = r["kernels"]["mx_flash_"]
    assert flash["flops"] / 1e12 == pytest.approx(attn_tf, rel=1e-12)
    assert flash["bytes"] == 12 * batch * seq * 4096 * 2 * 5
    # the issue's round figures: 7.9 GF a token and 4.1 TF of attention at 2k
    if seq == 2048:
        assert round(gf_per_token, 1) == 7.9 and round(attn_tf, 1) == 4.1


def test_required_flops_of_resnet50_v1():
    resnet_v1_convs = run.load_named("counts", "resnet_v1").resnet_v1_convs
    convs = dict((n, m) for m, n in resnet_v1_convs())
    assert convs["conv0"] == 112 * 112 * 64 * 3 * 49
    assert convs["stage1.block0.conv3x3"] == 56 * 56 * 64 * 64 * 9
    # v1: the stride sits on the first 1x1, so stage 2's 3x3 runs at 28x28
    assert convs["stage2.block0.conv1x1a"] == 28 * 28 * 256 * 128
    assert convs["stage2.block0.shortcut"] == 28 * 28 * 256 * 512
    assert convs["dense"] == 2048 * 1000
    assert len(convs) == 1 + 16 * 3 + 4 + 1
    macs = sum(convs.values())
    assert 3.8e9 < macs < 3.9e9     # He et al., table 1: 3.8e9 for 50 layers
    r = _count("resnet_v1")({"batch": 128, "image": 224,
                             "model": {"layers": [3, 4, 6, 3],
                                       "classes": 1000}})
    assert r["step_flops"] == 6 * macs * 128


@pytest.mark.parametrize("kind,work,pinned", [
    # what chipbench/flops.py gave at the parent (d3e5a09), to the digit
    ("dense_decoder", {"model": BAICHUAN, "batch": 8, "seq_len": 2048,
                       "dtype": "bfloat16"},
     {"step_flops": 129366428221440,
      "kernels": {"mx_flash_": {"flops": 4125181870080,
                                "bytes": 8053063680}}}),
    ("dense_decoder", {"model": BAICHUAN, "batch": 1, "seq_len": 16384,
                       "dtype": "bfloat16"},
     {"step_flops": 158228608450560,
      "kernels": {"mx_flash_": {"flops": 32987362099200,
                                "bytes": 8053063680}}}),
    ("resnet_v1", {"batch": 128, "image": 224,
                   "model": {"layers": [3, 4, 6, 3], "classes": 1000}},
     {"step_flops": 2962923454464}),
])
def test_the_moved_counts_give_the_parents_numbers(kind, work, pinned):
    assert _count(kind)(work) == pinned


def test_a_kind_with_no_count_file_is_an_error_that_names_the_file():
    with pytest.raises(SystemExit) as e:
        _count("no_such_kind", TINY)
    for base in (TINY, os.path.join(ROOT, "chipbench")):
        assert os.path.join(base, "counts", "no_such_kind.py") in str(
            e.value)
    # the tiny root's own kind is found from that root and from no other
    assert _count("tiny_two_families", TINY)
    with pytest.raises(SystemExit):
        _count("tiny_two_families")


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


def test_a_change_of_a_few_flipped_last_bits_is_left_out_of_the_worst_leaf():
    """Two leaves of large values (a conv's bias and taps: values near 0.5
    held in bfloat16) of which the float32 reference moved five elements by
    their last bit, 2**-9, and the program three; three leaves of many
    small steps. With no floor the conv's bias is the worst leaf by far:
    rounding. One ordinary leaf's update dropped in one layer of nine is
    what the worst leaf is kept for."""
    bit = 2.0 ** -9
    ref = {"conv_b": 5 ** 0.5 * bit, "conv_w": 0.005, "ssm_out": 0.0045,
           "ssm_in": 0.0040, "embed": 0.0042}
    moved = {"conv_b": 5, "conv_w": 5, "ssm_out": 40960, "ssm_in": 100000,
             "embed": 4096}
    sound = dict({n: v * (1 + 1e-4) for n, v in ref.items()},
                 conv_b=3 ** 0.5 * bit, conv_w=ref["conv_w"])
    grads = dict.fromkeys(ref, 1.0)
    refd = {"loss": [2.0], "grad_norm": grads, "delta_norm": ref,
            "moved": moved}
    limits = {"loss1": 0, "grad_norm_gap": 0, "grad_norm_gap_med": 0,
              "delta_norm_gap": 0.03, "delta_norm_gap_med": 5e-3,
              "moved_floor": 1000}
    assert check.left_out(refd, 1000) == ["conv_b", "conv_w"]
    assert check.left_out(refd, 0) == [] == check.left_out(refd, 5)
    prog = {"loss": [2.0], "grad_norm": grads, "delta_norm": sound}
    vals, worst = check.numbers(prog, refd, 1000)
    assert worst["delta_norm_gap"] in ("ssm_out", "ssm_in", "embed")
    assert vals["delta_norm_gap"] == pytest.approx(1e-4, rel=0.1)
    assert check.judge(prog, refd, limits)[0]
    # a leaf left out still counts in the median leaf's change
    gaps = check.leaf_gaps(sound, ref)
    assert gaps["conv_b"] == pytest.approx(0.2254, rel=1e-3)
    assert vals["delta_norm_gap_med"] == pytest.approx(1e-4, rel=0.1) \
        == sorted(gaps.values())[2]
    # a cell whose file gives no floor holds every leaf that moved
    vals, worst = check.numbers(prog, refd)
    assert worst["delta_norm_gap"] == "conv_b"
    assert vals["delta_norm_gap"] == pytest.approx(0.2254, rel=1e-3)
    no_floor = {n: v for n, v in limits.items() if n != "moved_floor"}
    assert not check.judge(prog, refd, no_floor)[0]
    # one layer of nine never moved ssm_out: the worst leaf alone sees it
    faulty = dict(prog, delta_norm=dict(
        sound, ssm_out=ref["ssm_out"] * (8 / 9) ** 0.5))
    ok, compared, _, worst = check.judge(faulty, refd, limits)
    over = [n for n, (v, lim) in compared.items() if not v <= lim]
    assert not ok and over == ["delta_norm_gap"]
    assert worst["delta_norm_gap"] == "ssm_out"
    assert compared["delta_norm_gap"][0] == pytest.approx(
        1 - (8 / 9) ** 0.5, rel=1e-6)
    # a floor that leaves no leaf in is a mistake in the file, not a pass
    with pytest.raises(ValueError):
        check.numbers(prog, refd, 10 ** 9)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _appended(b):
    """What a later ``model_config`` PR brings, made in memory: a fifth cell
    of a configuration that is there, appended to ``workloads`` and to the
    END of every list the first cell is in, and one entry appended to the
    END of ``per_layer`` (a reader the tree has and no cell reports)."""
    b = copy.deepcopy(b)
    first = b["workloads"][0]
    fifth = dict(first, name=first["config"] + "-seq4096", traffic="seq4096",
                 why="a later PR's cell: batch 4 x 4096")
    b["workloads"].append(fifth)
    for m in b["per_layer"]:
        if first["name"] in m["workloads"]:
            m["workloads"].append(fifth["name"])
    b["per_layer"].append({
        "name": "step.off_path", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "step_ms", "workloads": [fifth["name"]]})
    return b


def _invariants(b):
    """What has to hold of BENCHMARK.json's lists wherever a cell or an
    entry stands in them and however many there are: membership, never
    position or count."""
    assert sorted(b) == ["command", "configs", "end_to_end", "paths",
                         "per_layer", "run_seconds", "workloads"]
    configs = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    for c in b["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert c["name"] in [w["config"] for w in b["workloads"]]
    for w in b["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert w["config"] in configs
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group, keys in (("end_to_end", {"bound"}), ("per_layer",
                                                    {"layer", "moves"})):
        for m in b[group]:
            assert set(m) - {"workloads"} == keys | {
                "name", "unit", "better", "source"}
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in b[group]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    end_to_end = {m["name"] for m in b["end_to_end"]}
    assert {"step_ms", "setup_s"} <= end_to_end
    assert all(0 < m["bound"] <= 0.1 for m in b["end_to_end"])
    for text in ([c["why"] for c in b["configs"] + b["workloads"]]
                 + [c["source"] for c in b["configs"]]
                 + [m["layer"] for m in b["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    # every entry opts its cells in, and names only cells that are there
    for m in b["per_layer"]:
        assert m["moves"] in end_to_end
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        assert len(set(m["workloads"])) == len(m["workloads"])
        assert callable(run.metric_reader(m["name"]))
    # a layer has one spelling
    layers = {m["layer"] for m in b["per_layer"]}
    assert len({" ".join(l.lower().split()) for l in layers}) == len(layers)
    # every cell reports something besides the end-to-end metrics
    for name in cells:
        assert run.metrics_of(b, name, "per_layer")
        assert {m["name"] for m in run.metrics_of(b, name, "end_to_end")} \
            >= {"step_ms", "setup_s"}


@pytest.mark.parametrize("grown", [False, True],
                         ids=["as_it_stands", "a_cell_and_an_entry_appended"])
def test_the_lists_hold_wherever_a_cell_or_an_entry_is_appended(grown):
    """The second case is the test a later PR's additions have to pass: a
    fifth cell at the end of ``workloads`` and of every list it reports, an
    entry at the end of ``per_layer``."""
    b = _bench()
    if grown:
        before, b = b, _appended(b)
        # appended: what was there stands where it stood, whole
        assert b["workloads"][:-1] == before["workloads"]
        for old, new in zip(before["per_layer"], b["per_layer"]):
            assert new["workloads"][:len(old["workloads"])] \
                == old["workloads"]
            assert dict(new, workloads=old["workloads"]) == old
        fifth = b["workloads"][-1]["name"]
        assert {m["name"] for m in run.metrics_of(b, fifth, "per_layer")} \
            == {m["name"] for m in run.metrics_of(
                b, b["workloads"][0]["name"], "per_layer")} | {"step.off_path"}
    _invariants(b)


def test_benchmark_json_names_units_and_keys_are_legal():
    b = _bench()
    _invariants(b)
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    assert "chipbench" in b["paths"] and "tests/chipbench" in b["paths"]


def test_every_cell_finds_its_files_by_name():
    b = _bench()
    for name in [w["name"] for w in b["workloads"]]:
        spec = run.load_cell(name)
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "models", spec["config"]["adapter"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "counts", spec["config"]["flops"] + ".py"))
        assert callable(spec["required"])
        # every number check.py computes has a limit or is named as not
        # compared; PERF.md gives the readings either way
        listed = set(spec["limits"]) | set(spec["limits"].get(
            "not_compared", ()))
        assert {"loss1", "loss2", "loss3", "grad_norm_gap",
                "grad_norm_gap_med", "delta_norm_gap",
                "delta_norm_gap_med"} <= listed
        assert {"grad_norm_gap", "delta_norm_gap"} & set(spec["limits"])
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
