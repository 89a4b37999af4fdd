"""The five readers of the program's own account of its start
(chipbench/metrics/setup.trace_s.py, setup.cache_load_s.py,
setup.cache_misses.py, setup.before_import_s.py, setup.import_s.py) on a
hand-made ledger: what compiled before the window is counted by
``setup.jax_compile_s``'s rule, a load and a compile apart; over a program
without the ledger's split or the start-up section (the parent) each returns
None. Last, on the tiny decoder driven as ``run_cell`` drives it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
READERS = ("setup.trace_s", "setup.cache_load_s", "setup.cache_misses",
           "setup.before_import_s", "setup.import_s")
TRACE, LOWER, BACKEND = ("jaxpr_trace_duration",
                         "jaxpr_to_mlir_module_duration",
                         "backend_compile_duration")


def _entry(event, seconds, at_step, cache=None):
    return {"event": event, "fun_name": "jit(step_fn)", "seconds": seconds,
            "at_step": at_step, "in_step": at_step > 0, "start_us": 0.0,
            "end_us": 1e6 * seconds, "cache": cache, "cache_read_s": 0.0}


def _metrics():
    """Five steps started before the window's two dispatches: the entries at
    step 5 or before are set-up, the one at step 6 (as the reference's
    compiles after the window) is not."""
    entries = [_entry(TRACE, 1.0, 0), _entry(LOWER, 2.0, 0),
               _entry(BACKEND, 4.0, 0, "hit"),
               _entry(TRACE, 0.5, 1), _entry(LOWER, 0.25, 1),
               _entry(BACKEND, 8.0, 1, "miss"),
               _entry(BACKEND, 16.0, 5, None),
               _entry(TRACE, 32.0, 6), _entry(BACKEND, 64.0, 6, "hit"),
               _entry(BACKEND, 128.0, 7, "miss")]
    return {"train_step": {"steps": 7, "compiles": 2, "retraces": 0},
            "jax_compile": {"entries": entries, "cache_load_s": 68.0,
                            "fresh_compile_s": 152.0, "trace_s": 35.75},
            "setup": {"before_import_s": 12.5, "import_s": 1.25}}


WANT = {"setup.trace_s": 3.75, "setup.cache_load_s": 4.0,
        "setup.cache_misses": 1.0, "setup.before_import_s": 12.5,
        "setup.import_s": 1.25}
RUN = {"dispatch_ms": [500.0, 500.0]}


@pytest.mark.parametrize("name", READERS)
def test_a_setup_reader_counts_what_came_before_the_window(name, monkeypatch):
    from mxnet_tpu import profiler
    m = _metrics()
    monkeypatch.setattr(profiler, "metrics", lambda reset=False: m)
    assert run.metric_reader(name)(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_a_setup_reader_returns_none_over_the_parents_program(
        name, monkeypatch):
    """The parent's ledger has entries without ``cache`` and no totals of
    the split, and no start-up section; without any ledger, none either."""
    from mxnet_tpu import profiler
    m = _metrics()
    keep = ("event", "fun_name", "seconds", "at_step", "in_step")
    parent = {"train_step": m["train_step"], "jax_compile": {
        "entries": [{k: e[k] for k in keep}
                    for e in m["jax_compile"]["entries"]],
        "compile_s": 255.75}}
    for seen in (parent, {}):
        monkeypatch.setattr(profiler, "metrics", lambda reset=False: seen)
        assert run.metric_reader(name)(RUN) is None


def test_before_import_is_none_where_the_program_had_no_proc(monkeypatch):
    from mxnet_tpu import profiler
    m = _metrics()
    m["setup"]["before_import_s"] = None
    monkeypatch.setattr(profiler, "metrics", lambda reset=False: m)
    assert run.metric_reader("setup.before_import_s")(RUN) is None
    assert run.metric_reader("setup.import_s")(RUN) == 1.25


def test_a_hit_and_a_miss_are_counted_apart(monkeypatch):
    """A load adds seconds and no miss; a compile the cache did not hold
    and then stored adds a miss and no load; one for which nothing was read
    or written adds neither."""
    from mxnet_tpu import profiler
    load, misses = (run.metric_reader("setup.cache_load_s"),
                    run.metric_reader("setup.cache_misses"))
    for cache, want in (("hit", (2.0, 0.0)), ("miss", (0.0, 1.0)),
                        (None, (0.0, 0.0))):
        m = _metrics()
        m["jax_compile"]["entries"] = [_entry(TRACE, 1.0, 0),
                                       _entry(BACKEND, 2.0, 0, cache)]
        monkeypatch.setattr(profiler, "metrics", lambda reset=False: m)
        assert (load(RUN), misses(RUN)) == want


def test_the_readers_on_the_tiny_decoder_driven_as_run_cell_drives_it():
    """On a real run (the tiny decoder on the CPU, through first_steps and
    a short window): the split lies inside ``setup.jax_compile_s``, and the
    start-up section is the package's."""
    import importlib
    import jax
    from mxnet_tpu import profiler
    profiler._reset()
    try:
        spec = run.load_cell("tiny_decoder-seq128", TINY)
        adapter = importlib.import_module(
            "chipbench.models." + spec["config"]["adapter"])
        cell = adapter.build(spec["config"], spec["traffic"], 2139390011,
                             jax.devices()[:1])
        run.first_steps(cell)
        _, _, dispatch = run.timed_window(cell, 0.2, run.WARM_STEPS - 1)
        made = {"dispatch_ms": [1e3 * d for d in dispatch]}
        got = {name: run.metric_reader(name)(made)
               for name in READERS + ("setup.jax_compile_s",)}
    finally:
        profiler._reset()
    assert 0 < got["setup.trace_s"]
    assert got["setup.trace_s"] + got["setup.cache_load_s"] \
        <= got["setup.jax_compile_s"] + 1e-9
    assert got["setup.cache_misses"] >= 0
    assert got["setup.import_s"] > 0
    assert got["setup.before_import_s"] is None \
        or got["setup.before_import_s"] >= 0
