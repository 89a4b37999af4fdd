"""The start-up ledger, at tiny sizes on the CPU: each backend compile says
whether JAX's persistent cache loaded the program or it was compiled, each
phase lies on the profiler's clock and is a span of the ``compile`` lane
while the profiler runs, and ``metrics()["setup"]`` holds the package's
import."""
import contextlib
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import compilation_cache

from mxnet_tpu import profiler

CORE = "/jax/core/compile/"
CACHE = "/jax/compilation_cache/"
TRACE, LOWER, BACKEND = ("jaxpr_trace_duration",
                         "jaxpr_to_mlir_module_duration",
                         "backend_compile_duration")


@pytest.fixture
def clean():
    profiler._reset()
    yield
    profiler._reset()


@contextlib.contextmanager
def persistent_cache(path):
    """JAX's persistent cache in ``path``, every program kept (as the
    benchmark keeps it); None: the cache off."""
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", path is not None)
    jax.config.update("jax_compilation_cache_dir",
                      None if path is None else str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.clear_caches()


def tiny_step(x):
    return jnp.tanh(x) @ x.T + 1.0


def _entries(name, event=BACKEND):
    return [e for e in profiler.metrics()["jax_compile"]["entries"]
            if e["event"] == event and name in e["fun_name"]]


# -- a load or a compile, from what JAX itself reports -----------------------

def test_a_miss_then_a_load_from_the_cache_and_none_without_it(
        clean, tmp_path):
    x = jnp.ones((8, 8))
    with persistent_cache(tmp_path / "jax_cache"):
        jax.jit(tiny_step)(x)
        (miss,) = _entries("tiny_step")
        loaded_before = profiler.metrics()["jax_compile"]["cache_load_s"]
        jax.clear_caches()
        jax.jit(tiny_step)(x)
    assert miss["cache"] == "miss" and miss["cache_read_s"] == 0.0
    hit = _entries("tiny_step")[1]
    assert hit["cache"] == "hit"
    assert 0 < hit["cache_read_s"] <= hit["seconds"]
    ledger = profiler.metrics()["jax_compile"]
    assert ledger["cache_load_s"] >= loaded_before + hit["seconds"]
    assert ledger["cache_hits"] >= 1 and ledger["cache_misses"] >= 1
    with persistent_cache(None):
        fresh_before = profiler.metrics()["jax_compile"]["fresh_compile_s"]
        jax.jit(tiny_step)(x)
    uncached = _entries("tiny_step")[2]
    assert uncached["cache"] is None and uncached["cache_read_s"] == 0.0
    ledger = profiler.metrics()["jax_compile"]
    assert ledger["fresh_compile_s"] >= fresh_before + uncached["seconds"]
    # JAX's default, the cache on with no directory, stores nothing either
    with persistent_cache(None):
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        jax.jit(tiny_step)(x)
    assert _entries("tiny_step")[3]["cache"] is None
    # the backend compile's seconds, divided; trace and lowering apart
    assert ledger["cache_load_s"] + ledger["fresh_compile_s"] \
        == pytest.approx(ledger["seconds"][BACKEND])
    assert ledger["trace_s"] == pytest.approx(
        ledger["seconds"][TRACE] + ledger["seconds"][LOWER])
    assert ledger["trace_s"] + ledger["seconds"][BACKEND] \
        == pytest.approx(ledger["compile_s"])
    assert all(e["cache"] is None for e in ledger["entries"]
               if e["event"] != BACKEND)


def _replay(events):
    """Feed the listeners what JAX 0.9.0's log_elapsed_time and
    compile_or_get_cached report, in their order: ("start", phase),
    ("event", name), ("secs", name, s), ("end", phase, fun, start, end)."""
    for ev in events:
        if ev[0] == "start":
            profiler._jax_phase_start(CORE + ev[1], 0.0)
        elif ev[0] == "event":
            profiler._jax_event(CACHE + ev[1])
        elif ev[0] == "secs":
            profiler._jax_event(CACHE + ev[1], ev[2])
        else:
            _, phase, fun, start, end = ev
            profiler._jax_event(CORE + phase, end - start, fun_name=fun)
            profiler._jax_span(CORE + phase, start, end, fun_name=fun)


def _compile(fun, start, end, kind):
    """A backend compile as the cache saw it: "hit", "miss" (compiled and
    written), "unstored" (asked, compiled, not written: under JAX's
    threshold of compile time) or None (the cache off)."""
    asked = [("event", "compile_requests_use_cache")] if kind else []
    got = {"hit": [("event", "cache_hits"),
                   ("secs", "compile_time_saved_sec", 1.0),
                   ("secs", "cache_retrieval_time_sec", 0.25)],
           "miss": [("event", "cache_misses")], "unstored": [],
           None: []}[kind]
    return ([("start", BACKEND)] + asked + got
            + [("end", BACKEND, fun, start, end)])


@pytest.mark.parametrize("kind,cache", [("hit", "hit"), ("miss", "miss"),
                                        ("unstored", None), (None, None)])
def test_a_recorded_sequence_is_classified_and_a_nested_compile_kept_out(
        clean, kind, cache):
    t0 = time.time() - (time.perf_counter() - profiler._t0)
    _replay([("start", TRACE),
             # a jit compiled while the outer function is traced: nested
             *_compile("jit(inner)", t0 + 1.0, t0 + 1.5, "hit"),
             ("end", TRACE, "outer", t0 + 0.5, t0 + 2.0),
             ("start", LOWER), ("end", LOWER, "jit(outer)", t0 + 2.0,
                                t0 + 3.0),
             *_compile("jit(outer)", t0 + 3.0, t0 + 7.0, kind)])
    ledger = profiler.metrics()["jax_compile"]
    assert [(e["event"], e["fun_name"]) for e in ledger["entries"]] == [
        (TRACE, "outer"), (LOWER, "jit(outer)"), (BACKEND, "jit(outer)")]
    compiled = ledger["entries"][-1]
    assert compiled["cache"] == cache
    assert compiled["cache_read_s"] == (0.25 if cache == "hit" else 0.0)
    assert compiled["seconds"] == pytest.approx(4.0)
    assert (compiled["start_us"], compiled["end_us"]) == pytest.approx(
        (3e6, 7e6), abs=1e3)
    assert ledger["nested"] == 1 and ledger["trace_s"] == pytest.approx(2.5)
    assert (ledger["cache_hits"], ledger["cache_misses"]) == (
        int(cache == "hit"), int(cache == "miss"))
    assert ledger["cache_load_s"] == pytest.approx(
        4.0 if cache == "hit" else 0.0)
    assert ledger["fresh_compile_s"] == pytest.approx(
        0.0 if cache == "hit" else 4.0)
    # a reset clears the totals and the entries
    profiler.metrics(reset=True)
    ledger = profiler.metrics()["jax_compile"]
    assert ledger["entries"] == [] and ledger["cache_load_s"] == 0.0 \
        and ledger["fresh_compile_s"] == 0.0 and ledger["cache_hits"] == 0


# -- one clock ---------------------------------------------------------------

def test_each_phase_lies_on_the_profilers_clock_in_order(clean):
    def clock_step(x):
        return jnp.cos(x) * 2.0

    before = profiler._now_us()
    jax.jit(clock_step)(jnp.ones(5))
    after = profiler._now_us()
    phases = [_entries("clock_step", event)[0]
              for event in (TRACE, LOWER, BACKEND)]
    for e in phases:
        assert before <= e["start_us"] < e["end_us"] <= after
        assert (e["end_us"] - e["start_us"]) / 1e6 == pytest.approx(
            e["seconds"], abs=1e-6)
    # traced, then lowered, then compiled
    assert phases[0]["end_us"] <= phases[1]["start_us"]
    assert phases[1]["end_us"] <= phases[2]["start_us"]


def test_while_the_profiler_runs_each_phase_is_a_span_of_the_compile_lane(
        clean, tmp_path, monkeypatch):
    def lane_step(x):
        return jnp.sin(x) + 3.0

    profiler.set_config(filename=str(tmp_path / "p.json"), xprof=False)
    profiler.set_state("run")
    try:
        jax.jit(lane_step)(jnp.ones(5))
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename="profile.json", xprof=True)
    spans = {e["name"]: e for e in profiler._events
             if e.get("tid") == profiler.LANES["compile"]}
    entries = {"jax.trace:lane_step": _entries("lane_step", TRACE)[0],
               "jax.lower:jit(lane_step)": _entries("lane_step", LOWER)[0],
               "jax.compile:jit(lane_step)": _entries("lane_step")[0]}
    for name, entry in entries.items():
        span = spans[name]
        assert span["ph"] == "X" and span["cat"] == "compile"
        assert span["args"] == {"cache": entry["cache"]}
        assert span["ts"] == pytest.approx(entry["start_us"], abs=1.0)
        assert span["ts"] + span["dur"] == pytest.approx(entry["end_us"],
                                                         abs=1.0)
    # with neither a profile run nor the flight recorder: entries only
    monkeypatch.setattr(profiler, "_LIVE", False)
    n = len(profiler._events)
    jax.jit(lambda x: x * 7.0)(jnp.ones(5))
    assert len(profiler._events) == n


# -- the package's import ----------------------------------------------------

def test_setup_holds_the_import_and_no_reset_clears_it(clean, monkeypatch):
    setup = profiler.metrics(reset=True)["setup"]
    assert setup["import_s"] > 0
    assert setup["before_import_s"] is None or setup["before_import_s"] >= 0
    assert profiler.metrics()["setup"] == setup
    profiler._reset()
    assert profiler.metrics()["setup"] == setup
    # set once: a second hand-over changes nothing
    profiler.note_import(0.0, 1e6)
    assert profiler.metrics()["setup"] == setup
    age = profiler._process_age_s()
    assert age is None or age >= setup["import_s"]


def test_without_proc_the_process_age_is_none(monkeypatch):
    def no_proc(*a, **k):
        raise FileNotFoundError("/proc")

    monkeypatch.setattr(profiler, "open", no_proc, raising=False)
    assert profiler._process_age_s() is None
