"""Device time in what ``jax.checkpoint`` runs a second time in the backward
(``rematted_computation``): the cost of the remat policy, not required work.

Share (%) of the summed device time of the traced window, first device.
Finds nothing where the adapter gives no program text: the scopes are read
from it (chipbench/scopes.py)."""
from chipbench import trace


def read(run):
    return trace.phase_share(run, "recompute")
