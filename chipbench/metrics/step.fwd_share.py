"""Device time in the forward pass: every scope but ``mx.optimizer``, not
under ``transpose(jvp(`` and not a remat's second run.

Share (%) of the summed device time of the traced window, first device.
Finds nothing where the adapter gives no program text: the scopes are read
from it (chipbench/scopes.py)."""
from chipbench import trace


def read(run):
    return trace.phase_share(run, "forward")
