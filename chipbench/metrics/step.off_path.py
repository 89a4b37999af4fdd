"""Steps in the window that left the fused path: the sum of the program's
fused_step counters' growth over the window. Must read 0."""


def read(run):
    c = run["counters"]
    return None if c is None else float(sum(c.values()))
