"""Seconds in the warm-up's first call of each program (compile, or the load
from the persistent cache). Moves setup_s."""


def read(run):
    return float(sum(run["first_call_s"].values()))
