"""The three per-layer metrics that read the program's own counters and
spans (chipbench/metrics/step.retraces.py, step.call_ms_p50.py,
setup.jax_compile_s.py), on the tiny decoder on the CPU: driven by the
harness's own warm-up and window, each reads what the window did; on a
program without the section (the parent commit) each returns None."""
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
READERS = ("step.retraces", "step.call_ms_p50", "setup.jax_compile_s")
PHASES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
          "backend_compile_duration")


@pytest.fixture(scope="module")
def driven():
    """The tiny decoder through first_steps and a short window, as
    run_cell drives it; -> (the hand-made ``run``, the program's metrics)."""
    import importlib
    import jax
    from mxnet_tpu import profiler
    profiler._reset()
    spec = run.load_cell("tiny_decoder-seq128", TINY)
    adapter = importlib.import_module(
        "chipbench.models." + spec["config"]["adapter"])
    cell = adapter.build(spec["config"], spec["traffic"], 3000000019,
                         jax.devices()[:1])
    run.first_steps(cell)
    gaps, _, dispatch = run.timed_window(cell, 0.2, run.WARM_STEPS - 1)
    # what compiles after the window (the reference does, and the step's
    # text for a traced run's scopes) is not set-up
    jax.jit(lambda x: x - 2)(jax.numpy.ones(5))
    text = cell.program_text()
    assert len(dispatch) >= 2 and len(gaps) == len(dispatch) + 1
    yield {"dispatch_ms": [1e3 * d for d in dispatch],
           "program_text": text}, profiler.metrics()
    profiler._reset()


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_the_programs_own_section(driven, name):
    made, m = driven
    n = len(made["dispatch_ms"])
    value = run.metric_reader(name)(made)
    steps = m["train_step"]
    assert steps["steps"] == run.WARM_STEPS + 1 + n     # all before + window
    if name == "step.retraces":
        assert value == 0.0 and steps["compiles"] == 1
    elif name == "step.call_ms_p50":
        inside = [us / 1e3 for us, _ in steps["calls"][-n:]]
        assert value == pytest.approx(statistics.median(inside))
        # the program's span lies inside the harness's timing of the call
        assert 0 < value <= statistics.median(made["dispatch_ms"])
    else:
        before = [e for e in m["jax_compile"]["entries"]
                  if e["event"] in PHASES
                  and e["at_step"] <= run.WARM_STEPS + 1]
        assert value == pytest.approx(sum(e["seconds"] for e in before))
        assert any("step_fn" in e["fun_name"] for e in before)
        # what compiled after the window is in the ledger and left out
        assert 0 < value < m["jax_compile"]["compile_s"]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_where_the_section_is_missing(
        driven, name, monkeypatch):
    from mxnet_tpu import profiler
    made, m = driven
    parent = {k: v for k, v in m.items()
              if k not in ("train_step", "jax_compile")}
    monkeypatch.setattr(profiler, "metrics", lambda reset=False: parent)
    assert run.metric_reader(name)(made) is None


def test_the_adapters_program_text_carries_the_programs_scopes(driven):
    """What ``run_cell`` hands ``trace.reduce`` in a traced run: the compiled
    step's own text, every phase of the layer scopes in it; asking for it
    after the window is no retrace of the step."""
    from chipbench import scopes
    made, m = driven
    seen = {scopes.classify(op_name) for op_name in
            scopes.scope_map(made["program_text"]).values()}
    assert {(s, p) for s in ("mx.ffn", "mx.attn_proj", "mx.flash")
            for p in scopes.PHASES} <= seen
    assert {("mx.head_ce", "backward"), ("mx.embed", "forward"),
            ("mx.optimizer", "forward")} <= seen
    assert m["train_step"]["retraces"] == 0
