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


def left_out(reference, moved_floor):
    """The leaves of which the reference moved fewer than ``moved_floor``
    elements (its ``moved``): where a step is smaller than most weights'
    last bit only the smallest elements move, each by its last bit, and
    where they are a handful, which of them flip (rounding's to say, and
    different under bfloat16 and float32 activations) decides the norm. A
    rule on the reference alone, as GRAD_FLOOR is: a fault of the program
    cannot move a leaf into it."""
    if not moved_floor:
        return []
    return sorted(n for n, k in reference["moved"].items() if k < moved_floor)


def numbers(program, reference, moved_floor=0):
    """-> ({name: value compared}, {name: the leaf that set it}).
    ``moved_floor`` (a cell's limits file may give one, set from readings):
    the WORST leaf's change is taken over the leaves that ``left_out``
    leaves in; the median leaf's over all that moved."""
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
    out_of = set(left_out(reference, moved_floor))
    kept = {n: g for n, g in gaps.items() if n not in out_of}
    worst["delta_norm_gap"] = max(kept, key=kept.get)
    out["delta_norm_gap"] = kept[worst["delta_norm_gap"]]
    out["delta_norm_gap_med"] = statistics.median(gaps.values())
    return out, worst


def judge(program, reference, limits):
    """-> (correct, {name: [value, limit]}, {name: value not compared},
    {name: leaf}). The cell's file gives every number a limit or names it
    under "not_compared" (PERF.md says why, with its readings); a number it
    does not mention is an error, not a pass. Its "moved_floor", where it
    has one, is ``numbers``'s."""
    vals, worst = numbers(program, reference, limits.get("moved_floor", 0))
    skipped = {n: vals[n] for n in limits.get("not_compared", ())}
    compared = {n: [v, float(limits[n])] for n, v in vals.items()
                if n not in skipped}
    ok = all(v == v and v <= lim for v, lim in compared.values())
    return ok, compared, skipped, worst
