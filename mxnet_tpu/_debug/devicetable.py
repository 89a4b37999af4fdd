"""Device time by ``mx.*`` scope from an xprof trace: the operator's table.

The analogue of the reference's per-operator aggregate table
(src/profiler/aggregate_stats.cc) for a program that is ONE XLA module a
step: ``profiler.set_state('run')`` writes an ``.xplane.pb``;
``device_table`` reads it back (``jax.profiler.ProfileData``, nothing else)
and splits the train step's device time by the ``jax.named_scope`` names
the program carries (``mx.embed`` ... ``mx.optimizer``; inside a layer
``mx.attn_proj``, ``mx.flash``, ``mx.attn_out`` (with ``mx.cca_mix`` between
the first two where q and k are mixed) or, for a state-space mixer,
``mx.ssm_proj``, ``mx.ssm_conv``, ``mx.ssm_scan``, ``mx.ssm_gate``; then
``mx.ffn`` and an expert share's ``mx.moe_*``), into forward, backward and
recompute, with the Pallas kernels by their ``name=`` and the
program's own host spans beside them.

How an event finds its scope. An "XLA Ops" event is named by its HLO
instruction (``%fusion.454 = bf16[...] fusion(...)``); JAX's name stack is the
instruction's ``metadata.op_name``. Where the runtime puts that into a stat
of the event it is read there; otherwise the instruction's name is looked up
in the compiled program's HLO text (``metadata={op_name="..."}``), which the
caller passes or ``profiler.record_program`` holds. A fusion carries its
root's metadata, so it counts for the scope of its root.
"""
import re

_SCOPE = re.compile(r"mx\.[a-z_]+")
_KERNEL = re.compile(r"mx_[a-z0-9_]+")
_DEFINES = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# an event's stats that may hold metadata.op_name, by runtime version
_NAME_STATS = ("tf_op", "name", "long_name")
_CONTAINERS = (" while(", " conditional(", " call(")  # bodies' ops are
#                                                       events of their own
PHASES = ("forward", "backward", "recompute")
HOST_SPANS = ("mx.train_step", "gluon.train_step")


def load_trace(path):
    """The ``.xplane.pb`` as plain data (so ``device_table`` can be checked
    on a hand-made trace):

        {"devices": {plane: {"ops": [(name, start_ns, dur_ns, op_name)],
                             "modules": [(name, start_ns, dur_ns)]}},
         "host": [(name, start_ns, dur_ns)]}
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        op_name = next((str(stats[k]) for k in _NAME_STATS
                                        if "/" in str(stats.get(k, ""))), "")
                        dev["ops"].append((e.name, float(e.start_ns),
                                           float(e.duration_ns), op_name))
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name in HOST_SPANS)
    return out


def scope_map(hlo):
    """{instruction name: metadata.op_name} of a compiled program's text."""
    out = {}
    for line in hlo.splitlines():
        m = _DEFINES.match(line)
        if m:
            n = _OP_NAME.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def classify(op_name):
    """-> (scope, phase). The innermost ``mx.*`` name wins. Recompute is
    what ``jax.checkpoint`` re-runs in the backward
    (``.../checkpoint/rematted_computation/...``); a bare ``checkpoint/``
    under ``transpose(jvp(...))`` is the backward's own work."""
    scopes = _SCOPE.findall(op_name)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return (scopes[-1] if scopes else "unscoped"), phase


def _instruction(event_name):
    return event_name.partition(" = ")[0].strip().lstrip("%")


def device_table(trace, hlo=None):
    """``trace``: a path to an ``.xplane.pb`` or what ``load_trace``
    returns. ``hlo``: the compiled step's text, for events that carry no
    op_name. -> the table as a dict (``format_table`` prints it), or None
    where the trace holds no device plane (a CPU run)."""
    if isinstance(trace, str):
        trace = load_trace(trace)
    if not trace["devices"]:
        return None
    plane = sorted(trace["devices"])[0]
    dev = trace["devices"][plane]
    by_instr = scope_map(hlo) if hlo else {}
    # the window: whole executions of the program that takes most of the
    # device's time (the train step), from one start to the last start.
    # The execution that was running when the trace began is recorded
    # from the trace's start, not its own, so with three or more the
    # first is left out; a trace that holds one gives that one
    lo, hi, steps, module = float("-inf"), float("inf"), 0, None
    if dev["modules"]:
        by = {}
        for name, _, dur in dev["modules"]:
            by[name] = by.get(name, 0.0) + dur
        module = max(by, key=by.get)
        runs = sorted((s, d) for n, s, d in dev["modules"] if n == module)
        if len(runs) > 1:
            runs = runs[1:] if len(runs) > 2 else runs
            lo, hi, steps = runs[0][0], runs[-1][0], len(runs) - 1
        else:
            lo, hi, steps = runs[0][0], runs[0][0] + runs[0][1], 1
    rows, kernels, spans, total = {}, {}, [], 0.0
    for name, start, dur, op_name in dev["ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a or any(c in name for c in _CONTAINERS):
            continue
        instr = _instruction(name)
        op_name = op_name or by_instr.get(instr, "")
        scope, phase = classify(op_name)
        row = rows.setdefault(scope, dict.fromkeys(PHASES, 0.0))
        row[phase] += b - a
        total += b - a
        spans.append((a, b))
        k = _KERNEL.match(instr) or _KERNEL.search(op_name)
        if k:
            kern = kernels.setdefault(k.group(0).rstrip("_"),
                                      {"calls": 0, "s": 0.0})
            kern["calls"] += 1
            kern["s"] += (b - a) / 1e9
    busy, end = 0.0, float("-inf")
    for a, b in sorted(spans):
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    for row in rows.values():
        for p in PHASES:
            row[p] /= 1e9
        row["total"] = sum(row[p] for p in PHASES)
    opt = rows.get("mx.optimizer", {}).get("total", 0.0)
    phases = {p: sum(r[p] for s, r in rows.items() if s != "mx.optimizer")
              for p in PHASES}
    phases["optimizer"] = opt
    host = {}
    for name, _, dur in trace["host"]:
        host.setdefault(name, []).append(dur / 1e3)
    return {
        "device": plane, "module": module, "steps": steps,
        "window_s": (hi - lo) / 1e9 if steps else 0.0,
        "busy_s": busy / 1e9, "op_sum_s": total / 1e9,
        "rows": rows, "phases": phases, "kernels": kernels,
        "scoped_share": (1.0 - rows.get("unscoped", {}).get("total", 0.0)
                         / (total / 1e9)) if total else 0.0,
        "host_spans": {n: {"count": len(v), "total_us": sum(v),
                           "median_us": sorted(v)[len(v) // 2]}
                       for n, v in host.items()},
    }


def format_table(table):
    """The table as text, shares of the summed device time of the step."""
    if not table:
        return ""
    total = table["op_sum_s"] or 1.0
    per = max(table["steps"], 1)
    lines = ["Device time by scope: %s, %d step(s) of %s, busy %.6f s, "
             "%.1f%% in mx.* scopes"
             % (table["device"], table["steps"], table["module"],
                table["busy_s"], 100.0 * table["scoped_share"]),
             "%-16s %10s %7s %10s %10s %10s"
             % ("Scope", "ms/step", "Share", "fwd(ms)", "bwd(ms)",
                "remat(ms)")]
    for scope, r in sorted(table["rows"].items(),
                           key=lambda kv: -kv[1]["total"]):
        lines.append("%-16s %10.3f %6.2f%% %10.3f %10.3f %10.3f" % (
            scope, 1e3 * r["total"] / per, 100.0 * r["total"] / total,
            1e3 * r["forward"] / per, 1e3 * r["backward"] / per,
            1e3 * r["recompute"] / per))
    lines.append("split: " + " ".join(
        "%s=%.2f%%" % (p, 100.0 * v / total)
        for p, v in table["phases"].items()))
    for name, k in sorted(table["kernels"].items()):
        lines.append("kernel %-14s calls/step=%g ms/step=%.3f share=%.2f%%"
                     % (name, k["calls"] / per, 1e3 * k["s"] / per,
                        100.0 * k["s"] / total))
    for name, h in sorted(table["host_spans"].items()):
        lines.append("host span %-18s count=%d median=%.1f us total=%.1f us"
                     % (name, h["count"], h["median_us"], h["total_us"]))
    return "\n".join(lines)
