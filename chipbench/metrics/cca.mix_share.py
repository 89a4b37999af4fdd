"""Device time of compressed attention's mixing stage (``mx.cca_mix``: the
two causal convolutions over q and k, the mean of the latents, the unit
norms and the temperature, the shift of v's later half); all phases, over the
summed device time of the traced window, first device (%). Finds nothing
where the adapter gives no program text or the program has no such scope."""


def read(run):
    t = run["trace"]
    row = t.get("scopes", {}).get("mx.cca_mix")
    if row is None or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(row.values()) / t["op_sum_s"]
