"""One scope's backward over the summed device time: what a later PR adds
for a scope of its own, as a file and an entry."""


def read(run):
    t = run["trace"]
    if "scopes" not in t or "mx.ffn" not in t["scopes"]:
        return None
    return 100.0 * t["scopes"]["mx.ffn"]["backward"] / t["op_sum_s"]
