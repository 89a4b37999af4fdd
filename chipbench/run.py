"""chipbench: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in BENCHMARK.json (README.md).
The last line of standard output is the result; without the chips the cell
asks for the run exits non-zero and prints none.
"""
import time

T0 = time.perf_counter()   # set-up is everything from here to the window

import argparse
import gc
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARM_STEPS = 4      # steps 1-3 are followed by the reference; the arrival
#                     of step 4's loss opens the window
TRACE_FROM = 3      # in a traced run: profile from the window's 3rd arrival
TRACE_SECONDS = 4.0  # ... for this long, and until five more have arrived:
TRACE_ARRIVALS = 5   # the execution running at the start is cut and left
#                      out (trace.py), so these hold three whole steps


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, root=ROOT):
    """The cell's entry with its configuration, traffic and limits, all found
    by name from ``root``/BENCHMARK.json (tests bring a tiny root)."""
    bench = load_json(root, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit("no workload %r in BENCHMARK.json" % name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    data = os.path.dirname(os.path.dirname(os.path.join(root, entry["file"])))
    config = load_json(root, entry["file"])
    return {
        "bench": bench, "cell": cell, "data": data, "config": config,
        "traffic": load_json(data, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(data, "limits", name + ".json"),
        "required": load_named("counts", config["flops"], data).required,
    }


def load_named(group, name, where=HERE):
    """The module ``<group>/<name>.py`` under ``where`` (a test's own root
    or directory) or else under chipbench/. A name with no file in either
    is an error that names the file wanted."""
    tried = [os.path.join(base, group, name + ".py")
             for base in dict.fromkeys((where, HERE))]
    path = next((p for p in tried if os.path.exists(p)), None)
    if path is None:
        raise SystemExit("chipbench: %r needs the file %s"
                         % (name, " or ".join(tried)))
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (group, re.sub(r"\W", "_", name)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name, where=HERE):
    """``read(run) -> float or None`` of ``metrics/<name>.py``."""
    return load_named("metrics", name, where).read


def metrics_of(bench, cell_name, group):
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def find_chips(chips):
    """The cell's devices, or exit: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say("chipbench: the cell needs %d TPU chip(s); JAX found %d %s "
            "device(s)" % (chips, len(devs), devs[0].platform))
        raise SystemExit(3)
    return devs[:chips]


def peaks_for(kind):
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise SystemExit("device kind %r is not in chipbench/peaks.json"
                         % kind)
    return table[kind]


def use_compile_cache():
    """The program's persistent compile cache (a fixed directory in the
    checkout, or where JAX_COMPILATION_CACHE_DIR says), with every program
    kept, the small ones too: a run after the first compiles nothing."""
    import jax
    from mxnet_tpu import runtime
    cache_dir = runtime.use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def first_steps(cell):
    """Drive the built cell through its first steps, 0 to WARM_STEPS - 2,
    by the window's own call and feed. -> (what `correct` compares: each
    step's loss, the first gradient's norms, the norms of the weights'
    change after these steps; seconds in the first call of each program)."""
    program, first_call_s, took = {"loss": []}, {}, []
    for i in range(WARM_STEPS - 1):
        t = time.perf_counter()
        program["loss"].append(cell.read(cell.dispatch(i)))
        took.append(time.perf_counter() - t)
        if i == 0:
            t = time.perf_counter()
            program["grad_norm"] = cell.grad_norms()
            first_call_s["grad_norms"] = time.perf_counter() - t
    t = time.perf_counter()
    program["delta_norm"] = cell.delta_norms()
    first_call_s["delta_norms"] = time.perf_counter() - t
    # a step's first two calls (trace, compile or cache load; Gluon's eager
    # step, then its compile) over what the third, steady, call takes
    first_call_s["step"] = max(0.0, took[0] + took[1] - 2 * took[2])
    return program, first_call_s


def peak_bytes(stats):
    """The most of a device's memory that was taken at once. The TPU runtime
    counts live arrays (``peak_bytes_in_use``) apart from what it reserves
    for the compiled programs' own temporaries (``peak_bytes_reserved``:
    activations, gradients); both are held while a step runs, and their sum
    is what ``bytes_limit`` less ``largest_free_block_bytes`` reads too
    (PERF.md)."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def timed_window(cell, seconds, first, trace_dir=None, clock=time.perf_counter):
    """Drive the step with one step of run-ahead for ``seconds``.

    Step ``first`` is the last warm-up step: the arrival of its loss opens
    the window. Each later step is dispatched before the loss of the step
    before it is read. Once the clock passes ``seconds`` nothing more is
    dispatched and what is in flight is drained; the window closes at the
    last arrival. -> gaps between arrivals (s; their sum IS the window),
    losses, dispatch times (s)."""
    import jax
    spans = trace_dir is not None
    if spans:
        note = jax.profiler.TraceAnnotation
    tracing, traced_from = False, None
    cur = cell.dispatch(first)
    nxt = cell.dispatch(first + 1)
    cell.read(cur)
    t_open = last = clock()
    deadline = t_open + seconds
    gaps, losses, dispatch = [], [], []
    cur, i = nxt, first + 2
    while cur is not None:
        nxt = None
        if clock() < deadline:
            t = clock()
            if spans:
                with note("chipbench.dispatch"):
                    nxt = cell.dispatch(i)
            else:
                nxt = cell.dispatch(i)
            dispatch.append(clock() - t)
            i += 1
        if spans:
            with note("chipbench.read_loss"):
                losses.append(cell.read(cur))
        else:
            losses.append(cell.read(cur))
        now = clock()
        gaps.append(now - last)
        last, cur = now, nxt
        if spans and not tracing and traced_from is None \
                and len(gaps) == TRACE_FROM:
            jax.profiler.start_trace(trace_dir)
            tracing, traced_from = True, (clock(), len(gaps))
        elif tracing and clock() - traced_from[0] >= TRACE_SECONDS \
                and len(gaps) - traced_from[1] >= TRACE_ARRIVALS:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    return gaps, losses, dispatch


def run_cell(workload, seed, seconds, trace, devices=None, keep_trace=False,
             wrap=None, root=ROOT):
    """One run. ``devices`` given: the look for a chip is skipped (tests).
    ``wrap`` (tests): takes the built cell and may break its timed path."""
    spec = load_cell(workload, root)
    cell_entry, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    on_chip = devices is None
    if on_chip:
        devices = find_chips(cell_entry["chips"])
        say("chipbench: compile cache at %s" % use_compile_cache())
    adapter = importlib.import_module("chipbench.models." + config["adapter"])
    # ---- set-up: build, first steps (followed by the reference), warm ----
    cell = adapter.build(config, traffic, seed, devices)
    if wrap is not None:
        cell = wrap(cell)
    counters0 = cell.counters()
    program, first_call_s = first_steps(cell)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(HERE, "out", "trace.%s.seed%d"
                                 % (workload, seed))
        shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0

    # ---- the measured window --------------------------------------------------
    gaps, losses, dispatch = timed_window(cell, seconds, WARM_STEPS - 1,
                                          trace_dir)
    gc.unfreeze()
    window_s = sum(gaps)
    peak = max(peak_bytes(d.memory_stats() or {}) for d in devices)
    say("chipbench: memory_stats of device 0: %s"
        % json.dumps(devices[0].memory_stats() or {}))
    counters1 = cell.counters()
    counters = None if counters1 is None else {
        k: counters1[k] - counters0.get(k, 0) for k in counters1}
    work = cell.work()
    required = spec["required"](work)
    # the compiled step's text, for the trace's scopes: asked for only here,
    # after the window and the peak, so in neither setup_s, step_ms nor the
    # peak
    program_text, program_text_s = None, 0.0
    if trace and hasattr(cell, "program_text"):
        t = time.perf_counter()
        program_text = cell.program_text()
        program_text_s = time.perf_counter() - t
    failed = sum(1 for v in losses if v != v or v in (float("inf"),
                                                      float("-inf")))
    step_ms = 1e3 * window_s / len(gaps)
    say("chipbench: %s seed %d: %d steps in %.3f s, %.3f ms a step, %.1f %s/s,"
        " %.6g GF a step required (counts/%s.py); set-up %.1f s; three "
        "largest gaps (ms): %s"
        % (workload, seed, len(gaps), window_s, step_ms,
           work["items_per_step"] / (window_s / len(gaps)), work["item"],
           required["step_flops"] / 1e9, config["flops"],
           setup_s, ", ".join("%.1f" % (1e3 * g)
                              for g in sorted(gaps, reverse=True)[:3])))
    if not trace:
        with open(os.path.join(HERE, "out", "%s.seed%d.steps.json"
                               % (workload, seed)), "w") as f:
            json.dump({"workload": workload, "seed": seed,
                       "window_s": window_s, "steps": len(gaps),
                       "setup_s": setup_s,
                       "gaps_ms": [1e3 * g for g in gaps],
                       "dispatch_ms": [1e3 * d for d in dispatch]}, f)

    # ---- the reference, once the program's state is freed ------------------
    cell.free()
    t = time.perf_counter()
    reference = cell.reference(WARM_STEPS - 1)
    reference_s = time.perf_counter() - t
    from chipbench import check
    correct, compared, not_compared, worst = check.judge(
        program, reference, spec["limits"])
    correct = correct and failed == 0

    # ---- the result ----------------------------------------------------------------
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    values = {"step_ms": step_ms, "setup_s": setup_s}
    group = "end_to_end"
    result = {"correct": bool(correct), "attempted": len(gaps),
              "failed": failed}
    if trace:
        from chipbench import trace as tr
        reduced = tr.reduce(tr.load(tr.find_xplane(trace_dir)), program_text)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        for name, k in sorted(reduced["kernels"].items()):
            say("chipbench: kernel %s: %g calls and %.3f ms a step"
                % (name, k["calls"] / reduced["steps"],
                   1e3 * k["s"] / reduced["steps"]))
        run = {"gaps_ms": [1e3 * g for g in gaps],
               "dispatch_ms": [1e3 * d for d in dispatch],
               "trace": reduced, "work": work,
               "required": required,
               "peaks": peaks_for(dev0.device_kind) if on_chip else None,
               "chips": len(devices), "counters": counters,
               "first_call_s": first_call_s, "memory_peak_bytes": peak}
        group, values = "per_layer", {}
        for m in metrics_of(spec["bench"], workload, group):
            v = metric_reader(m["name"], spec["data"])(run)
            if v is not None:
                values[m["name"]] = v
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        result["program_text_s"] = program_text_s
    units = {m["name"]: m["unit"] for m in spec["bench"][group]}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": units[m["name"]]}
        for m in metrics_of(spec["bench"], workload, group)
        if m["name"] in values}
    result["device"] = device
    result["reference_s"] = reference_s
    result["worst_leaf"] = worst
    # the leaves the worst leaf's change was not taken over (check.py)
    floor = spec["limits"].get("moved_floor", 0)
    result["left_out"] = {"delta_norm_gap": check.left_out(reference, floor)}
    result["not_compared"] = not_compared
    result["compared"] = compared
    say("chipbench: delta_norm_gap is %s's; left out, fewer than %d of "
        "their elements moved: %s" % (worst["delta_norm_gap"], floor,
                                      ", ".join(result["left_out"][
                                          "delta_norm_gap"]) or "none"))
    for name, (v, lim) in compared.items():
        say("chipbench: compared %-16s %.6g  limit %.6g  %s"
            % (name, v, lim, "ok" if v <= lim else "OVER"))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=int, choices=(0, 1), default=0,
                    help="leave the profiler's files under chipbench/out/")
    a = ap.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      keep_trace=bool(a.keep_trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
