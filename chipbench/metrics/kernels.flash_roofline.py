"""Least time the chip could take for the attention work a step REQUIRES
(causal forward once plus backward, no recompute; the larger of FLOPs over
peak and bytes over peak, from shapes) over the device time of the events
that compute attention in a step: today the Mosaic flash calls (forward, dq,
dkv; the forward runs twice under full remat, which is the implementation's
cost and not required work). Finds nothing where no such event ran."""


def bound(run):
    r, p = run["required"], run["peaks"]
    per_chip = 1.0 / run["chips"]
    by_flops = r["attention_flops"] * per_chip / p["bf16_flops"]
    by_bytes = r["attention_bytes"] * per_chip / p["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    t = run["trace"]
    if "attention_flops" not in run["required"] or t["mosaic_s"] <= 0:
        return None
    least, _ = bound(run)
    return 100.0 * least / (t["mosaic_s"] / t["steps"])
