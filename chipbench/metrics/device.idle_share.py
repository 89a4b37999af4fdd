"""1 - union of the first device's op intervals over the traced window."""


def read(run):
    t = run["trace"]
    return 100.0 * t["idle_share"]
