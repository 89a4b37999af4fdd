"""Device time of everything around the experts' products that an expert
share adds: scores, top-k and weights (``mx.moe_route``), the sort and the
gather into slot order (``mx.moe_dispatch``), the gather back and the
weighted sum (``mx.moe_combine``); all phases, over the summed device time of
the traced window, first device (%). Finds nothing where the adapter gives no
program text or the program has none of these scopes."""

SCOPES = ("mx.moe_route", "mx.moe_dispatch", "mx.moe_combine")


def read(run):
    t = run["trace"]
    rows = [t.get("scopes", {}).get(s) for s in SCOPES]
    if all(r is None for r in rows) or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(sum(r.values()) for r in rows if r) / t["op_sum_s"]
