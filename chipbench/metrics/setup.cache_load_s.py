"""Seconds of the backend compiles before the window's first timed dispatch
that the persistent cache served (entries of the compile ledger,
``setup_ledger.before_window``, with ``cache == "hit"``): the load, the
read of the entry included. Moves setup_s."""
from chipbench import setup_ledger


def read(run):
    entries = setup_ledger.before_window(run)
    if entries is None:
        return None
    return float(sum(e["seconds"] for e in entries if e["cache"] == "hit"))
