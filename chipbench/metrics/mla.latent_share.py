"""Device time of latent attention's latent stage (``mx.mla_latent``: the
down-projections to the q and key-value latents, their norms, the
up-projections of the heads held, and the nope and rope parts split and laid
together); all phases, over the summed device time of the traced window,
first device (%). Finds nothing where the adapter gives no program text or
the program has no such scope."""


def read(run):
    t = run["trace"]
    row = t.get("scopes", {}).get("mx.mla_latent")
    if row is None or t["op_sum_s"] <= 0:
        return None
    return 100.0 * sum(row.values()) / t["op_sum_s"]
