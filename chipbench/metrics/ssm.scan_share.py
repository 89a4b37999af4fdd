"""Device time of the mixers' scan (scope ``mx.ssm_scan``: dt, the decays,
the chunked scan and the D skip; forward, backward and recompute together)
over the summed device time of the traced window, first device (%). Finds
nothing where the adapter gives no program text or the program has no such
scope."""


def read(run):
    t = run["trace"]
    row = t.get("scopes", {}).get("mx.ssm_scan")
    if row is None or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(row.values()) / t["op_sum_s"]
