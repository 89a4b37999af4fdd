"""Programs compiled before the window's first timed dispatch that the
persistent cache did not hold, so that XLA compiled them and JAX stored them
(entries of the compile ledger, ``setup_ledger.before_window``, with
``cache == "miss"``). 0 in a warm run; more names set-up time that a lost
entry cost. Moves setup_s."""
from chipbench import setup_ledger


def read(run):
    entries = setup_ledger.before_window(run)
    if entries is None:
        return None
    return float(sum(1 for e in entries if e["cache"] == "miss"))
