"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

``load(path)`` turns the file into plain data (so the arithmetic below can be
tested on a hand-made trace):

    {"devices": {"/device:TPU:0": {"ops": [(name, start_ns, dur_ns), ...],
                                   "modules": [(name, start_ns, dur_ns), ...]}},
     "host": [(name, start_ns, dur_ns), ...]}     # the harness's own spans

The traced window runs from the start of the step program's SECOND execution
in the trace to the start of its last, on the first device: whole steps with
the gaps between them, nothing of the profiler's own start and stop. The
execution that was running when the trace began is recorded from the trace's
start, not its own, so it is left out.
"""
import glob
import os
import re

from chipbench import scopes

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
SMALL_GAP_NS = 20_000
# the harness's own spans and the program's own call inside the first
HOST_SPANS = ("chipbench.dispatch", "chipbench.read_loss", "mx.train_step")


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
    """A Pallas kernel runs as a custom call whose target is
    ``tpu_custom_call``; the compiler's own custom calls (ConcatBitcast,
    AllocateBuffer: no time at all) are none."""
    return (opcode(name) == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in name)


def instruction(name):
    """The name of the instruction an "XLA Ops" event ran: the text before
    `` = ``, without its ``%``. The compiled program's text has the same."""
    return name.partition(" = ")[0].strip().lstrip("%")


def kernel_name(name):
    """A custom call's kernel: its instruction's name without the ``.<n>``
    the compiler appends (``%mx_flash_fwd.18`` -> ``mx_flash_fwd``: what the
    Pallas call gave as ``name=``)."""
    return re.sub(r"\.\d+$", "", instruction(name))


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
    """(start_ns, end_ns, whole steps) of the traced window on one device:
    from the start of the step program's second execution in the trace (the
    first is cut: see the top) to the start of its last."""
    name = step_module(dev["modules"])
    starts = sorted(s for n, s, _ in dev["modules"] if n == name)
    if len(starts) < 3:
        raise ValueError("the trace holds %d executions of %s: the first is "
                         "cut, so no whole step" % (len(starts), name))
    return starts[1], starts[-1], len(starts) - 2


def _clip(ops, lo, hi):
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def reduce(trace, program_text=None):
    """-> the numbers the per-layer readers and the result line take.
    ``program_text``: the compiled step's text where the adapter has it;
    then device time is also split by ``mx.*`` scope and phase."""
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
    op_names = None if program_text is None else scopes.scope_map(program_text)
    by_label, kernels, by_scope = {}, {}, {}
    op_sum = mosaic = coll = 0.0
    for name, a, b in ops:
        what = opcode(name)
        if what in CONTAINERS:
            continue
        op_sum += b - a
        if is_collective(name):
            coll += b - a
        elif is_mosaic(name):
            mosaic += b - a
            what = kernel_name(name)
            k = kernels.setdefault(what, {"s": 0.0, "calls": 0})
            k["s"] += (b - a) / 1e9
            k["calls"] += 1
        label = name    # the instruction's text, where no scope is known
        if op_names is not None:
            scope, phase = scopes.classify(op_names.get(instruction(name), ""))
            row = by_scope.setdefault(scope, dict.fromkeys(scopes.PHASES, 0.0))
            row[phase] += (b - a) / 1e9
            label = "%s %s %s" % (scope, phase, what)
        by_label[label] = by_label.get(label, 0.0) + (b - a)
    # what the host was doing in each idle gap: the innermost (shortest) of
    # the harness's and the program's spans that cover the gap's middle
    by_host = {}
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            label = "between_operations_each_under_20_us"
        else:
            mid = (a + b) / 2
            over = [(d, n) for n, s, d in trace["host"] if s <= mid <= s + d]
            label = min(over)[1] if over else "outside_chipbench_spans"
        by_host[label] = by_host.get(label, 0.0) + (b - a)
    top = lambda d: [[k[:160], v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    out = {
        "window_s": window / 1e9,
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "steps": steps,
        "step_s": window / steps / 1e9,
        "chips": len(names),
        "idle_share": 1.0 - busy_each[0] / window,
        "op_sum_s": op_sum / 1e9,
        "mosaic_s": mosaic / 1e9,
        "collective_s": coll / 1e9,
        "kernels": kernels,
        "device_ops": top(by_label),
        "idle_gaps": top(by_host),
    }
    if op_names is not None:
        rest = [r for s, r in by_scope.items() if s != scopes.OPTIMIZER]
        out["scopes"] = by_scope
        out["phases"] = {p: sum(r[p] for r in rest) for p in scopes.PHASES}
        out["phases"]["optimizer"] = sum(
            by_scope.get(scopes.OPTIMIZER, {}).values())
    return out


def kernel_roofline(run, prefix):
    """A kernel family's share (%) of its roofline: the least time a chip
    could take for the work the family REQUIRES in a step (the count's
    ``kernels[prefix]``, from shapes: the larger of flops over ``bf16_flops``
    and bytes over ``hbm_bytes_per_s``, per chip) over the device time a step
    of the events whose kernel name starts with ``prefix``, on the first
    device. None where the kind counts no such family or no such event ran."""
    t, need = run["trace"], run["required"].get("kernels", {}).get(prefix)
    spent = sum(k["s"] for n, k in t["kernels"].items() if n.startswith(prefix))
    if need is None or spent <= 0:
        return None
    p = run["peaks"]
    least = max(need["flops"] / p["bf16_flops"],
                need["bytes"] / p["hbm_bytes_per_s"]) / run["chips"]
    return 100.0 * least / (spent / t["steps"])


def phase_share(run, phase):
    """Device time (%) of ``phase`` (forward, backward, recompute or
    optimizer) over the summed device time in the traced window. None where
    the adapter gave no program text, so no scope is known."""
    t = run["trace"]
    if "phases" not in t or t["op_sum_s"] <= 0:
        return None
    return 100.0 * t["phases"][phase] / t["op_sum_s"]


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
