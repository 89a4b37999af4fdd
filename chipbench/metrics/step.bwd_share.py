"""Device time in the backward pass's own work: under ``transpose(jvp(``,
every scope but ``mx.optimizer``, less what is recomputed.

Share (%) of the summed device time of the traced window, first device.
Finds nothing where the adapter gives no program text: the scopes are read
from it (chipbench/scopes.py)."""
from chipbench import trace


def read(run):
    return trace.phase_share(run, "backward")
