"""Device time in all-reduce, all-gather, reduce-scatter, collective-permute
and all-to-all on the first device over the traced window. Finds nothing on
one chip."""


def read(run):
    t = run["trace"]
    if run["chips"] < 2:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
