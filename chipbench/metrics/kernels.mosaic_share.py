"""Device time in Pallas (Mosaic) custom calls over device busy time, on the
first device, in the traced window."""


def read(run):
    t = run["trace"]
    if t["op_sum_s"] <= 0:
        return None
    return 100.0 * t["mosaic_s"] / t["op_sum_s"]
