"""From a compiled program's text to the scope and phase of an instruction.

JAX writes its name stack into each HLO instruction's
``metadata={op_name="jit(step_fn)/.../mx.ffn/..."}``. A trace event is named
by its instruction and carries no scope on this runtime, so the reduction
maps the one to the other through the compiled step's text. The benchmark's
own copy (the program's table is ``mxnet_tpu/_debug/devicetable.py``): the
yardstick does not move when the program's table does.
"""
import re

_DEFINES = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"mx\.[a-z_]+")

PHASES = ("forward", "backward", "recompute")
OPTIMIZER = "mx.optimizer"      # the scope that is a phase of its own
UNSCOPED = "unscoped"


def scope_map(text):
    """{instruction name: metadata.op_name} of a compiled program's text.
    A fusion carries its root's metadata, so it counts for its root's
    scope."""
    out = {}
    for line in text.splitlines():
        m = _DEFINES.match(line)
        if m:
            n = _OP_NAME.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def classify(op_name):
    """-> (scope, phase). The innermost ``mx.*`` name wins. Recompute is
    what ``jax.checkpoint`` runs again in the backward
    (``.../checkpoint/rematted_computation/...``); a bare ``checkpoint/``
    under ``transpose(jvp(...))`` is the backward's own work."""
    found = _SCOPE.findall(op_name)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return (found[-1] if found else UNSCOPED), phase
