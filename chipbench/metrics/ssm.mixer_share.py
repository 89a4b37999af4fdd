"""Device time of the whole state-space mixer: its products in and out and
first norm (``mx.ssm_proj``), the conv (``mx.ssm_conv``), the scan
(``mx.ssm_scan``), the gate and norm (``mx.ssm_gate``); all phases, over the
summed device time of the traced window, first device (%). Finds nothing
where the adapter gives no program text or the program has none of these
scopes."""

SCOPES = ("mx.ssm_proj", "mx.ssm_conv", "mx.ssm_scan", "mx.ssm_gate")


def read(run):
    t = run["trace"]
    rows = [t.get("scopes", {}).get(s) for s in SCOPES]
    if all(r is None for r in rows) or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(sum(r.values()) for r in rows if r) / t["op_sum_s"]
