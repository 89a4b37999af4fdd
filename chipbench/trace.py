"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

``load(path)`` turns the file into plain data (so the arithmetic below can be
tested on a hand-made trace):

    {"devices": {"/device:TPU:0": {"ops": [(name, start_ns, dur_ns), ...],
                                   "modules": [(name, start_ns, dur_ns), ...]}},
     "host": [(name, start_ns, dur_ns), ...]}     # the harness's own spans

The traced window runs from the start of the step program's first execution
in the trace to the start of its last, on the first device: whole steps with
the gaps between them, nothing of the profiler's own start and stop.
"""
import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
SMALL_GAP_NS = 20_000
HOST_SPANS = ("chipbench.dispatch", "chipbench.read_loss")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append((e.name, float(e.start_ns),
                                            float(e.duration_ns)))
    return out


CONTAINERS = ("while", "conditional", "call")   # their bodies' ops are
#                                                 events of their own
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def opcode(name):
    """The HLO opcode of an "XLA Ops" event, whose name is the instruction's
    text: ``%n = <shape> opcode(operands), ...``. A name that is no such text
    is its own opcode."""
    _, eq, rest = name.partition(" = ")
    m = _OPCODE.search(rest) if eq else None
    return m.group(1) if m else name


def is_mosaic(name):
    """A Pallas kernel runs as a custom call (target tpu_custom_call)."""
    return opcode(name) == "custom-call"


def is_collective(name):
    return opcode(name).startswith(COLLECTIVES)


def _union(intervals):
    """Total length of the union of (start, end) intervals, and the gaps
    between its parts as (start, end)."""
    total, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def step_module(modules):
    """The program that takes most of the device's time: the train step."""
    by = {}
    for name, _, dur in modules:
        by[name] = by.get(name, 0.0) + dur
    return max(by, key=by.get)


def window_of(dev):
    """(start_ns, end_ns, whole steps) of the traced window on one device."""
    name = step_module(dev["modules"])
    starts = sorted(s for n, s, _ in dev["modules"] if n == name)
    if len(starts) < 2:
        raise ValueError("the trace holds %d executions of %s: no whole step"
                         % (len(starts), name))
    return starts[0], starts[-1], len(starts) - 1


def _clip(ops, lo, hi):
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def reduce(trace):
    """-> the numbers the per-layer readers and the result line take."""
    names = sorted(trace["devices"])
    if not names:
        raise ValueError("the trace holds no TPU device plane")
    lo, hi, steps = window_of(trace["devices"][names[0]])
    window = hi - lo
    busy_each, first = [], None
    for n in names:
        ops = list(_clip(trace["devices"][n]["ops"], lo, hi))
        busy, gaps = _union([(a, b) for _, a, b in ops])
        busy_each.append(busy)
        if first is None:
            if ops:     # the window's own edges are gaps too
                gaps = ([(lo, min(a for _, a, _ in ops))] + gaps
                        + [(max(b for _, _, b in ops), hi)])
            first = (ops, [g for g in gaps if g[1] > g[0]])
    ops, gaps = first
    by_name, mosaic, coll = {}, 0.0, 0.0
    for name, a, b in ops:
        if opcode(name) in CONTAINERS:
            continue
        by_name[name] = by_name.get(name, 0.0) + (b - a)
        if is_collective(name):
            coll += b - a
        elif is_mosaic(name):
            mosaic += b - a
    busy0 = busy_each[0]
    op_sum = sum(by_name.values())
    # what the host was doing in each idle gap: the harness's span that
    # covers the gap's middle
    by_host = {}
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            label = "between_operations_each_under_20_us"
        else:
            mid = (a + b) / 2
            label = next((n for n, s, d in trace["host"]
                          if s <= mid <= s + d), "outside_chipbench_spans")
        by_host[label] = by_host.get(label, 0.0) + (b - a)
    top = lambda d: [[k[:160], v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "steps": steps,
        "step_s": window / steps / 1e9,
        "chips": len(names),
        "idle_share": 1.0 - busy0 / window,
        "op_sum_s": op_sum / 1e9,
        "mosaic_s": mosaic / 1e9,
        "collective_s": coll / 1e9,
        "device_ops": top(by_name),
        "idle_gaps": top(by_host),
    }


def describe(path, n=40):
    """What a trace holds, for a first look at a new machine's names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append("PLANE %s" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines.append("  LINE %s: %d events" % (line.name, len(events)))
            by = {}
            for e in events:
                by[e.name] = by.get(e.name, 0.0) + e.duration_ns
            for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]:
                lines.append("    %.6f s  %s" % (v / 1e9, k[:300]))
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1] if sys.argv[1].endswith(".pb")
                   else find_xplane(sys.argv[1])))
