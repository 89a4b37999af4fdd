"""The comparison that decides ``correct`` for a training cell.

Both sides give {"loss": [..], "grad_norm": {leaf: x}, "delta_norm": {leaf:
x}}: the program's from the timed path's own first steps, the reference's
from chipbench/reference. Each number compared has a limit of its own in
chipbench/limits/<cell>.json, set from readings on the chip (PERF.md).
"""
import statistics

GRAD_FLOOR = 1e-3   # of the median leaf's gradient norm: below it a leaf's
#                     gradient is nought to rounding and its change is noise


def leaf_gaps(program, reference):
    """{leaf: |program's norm - reference's| over the reference's norm of
    that leaf or of the median leaf, whichever is larger}."""
    med = statistics.median(reference.values())
    return {n: abs(program[n] - ref) / max(ref, med, 1e-300)
            for n, ref in reference.items()}


def numbers(program, reference):
    """-> ({name: value compared}, {name: the leaf that set it})."""
    out, worst = {}, {}
    for i, ref in enumerate(reference["loss"]):
        out["loss%d" % (i + 1)] = abs(program["loss"][i] - ref) / abs(ref)
    gaps = leaf_gaps(program["grad_norm"], reference["grad_norm"])
    worst["grad_norm_gap"] = max(gaps, key=gaps.get)
    out["grad_norm_gap"] = gaps[worst["grad_norm_gap"]]
    out["grad_norm_gap_med"] = statistics.median(gaps.values())
    med = statistics.median(reference["grad_norm"].values())
    moved = [n for n, g in reference["grad_norm"].items()
             if g >= GRAD_FLOOR * med]
    gaps = leaf_gaps({n: program["delta_norm"][n] for n in moved},
                     {n: reference["delta_norm"][n] for n in moved})
    worst["delta_norm_gap"] = max(gaps, key=gaps.get)
    out["delta_norm_gap"] = gaps[worst["delta_norm_gap"]]
    out["delta_norm_gap_med"] = statistics.median(gaps.values())
    return out, worst


def judge(program, reference, limits):
    """-> (correct, {name: [value, limit]}, {name: value not compared},
    {name: leaf}). The cell's file gives every number a limit or names it
    under "not_compared" (PERF.md says why, with its readings); a number it
    does not mention is an error, not a pass."""
    vals, worst = numbers(program, reference)
    skipped = {n: vals[n] for n in limits.get("not_compared", ())}
    compared = {n: [v, float(limits[n])] for n, v in vals.items()
                if n not in skipped}
    ok = all(v == v and v <= lim for v, lim in compared.values())
    return ok, compared, skipped, worst
