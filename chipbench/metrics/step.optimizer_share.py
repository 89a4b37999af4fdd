"""Device time in the ``mx.optimizer`` scope: the update of the weights and
of the optimizer's state.

Share (%) of the summed device time of the traced window, first device.
Finds nothing where the adapter gives no program text: the scopes are read
from it (chipbench/scopes.py)."""
from chipbench import trace


def read(run):
    return trace.phase_share(run, "optimizer")
