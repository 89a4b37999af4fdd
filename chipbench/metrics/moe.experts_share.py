"""Device time of the grouped products over the slots of the experts held
(scope ``mx.moe_experts``: forward, backward and recompute together) over the
summed device time of the traced window, first device (%). Finds nothing
where the adapter gives no program text or the program has no such scope."""


def read(run):
    t = run["trace"]
    row = t.get("scopes", {}).get("mx.moe_experts")
    if row is None or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(row.values()) / t["op_sum_s"]
