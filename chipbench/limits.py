"""Readings from which a cell's limits are set (PERF.md, "How correct is
decided"); not part of a benchmark run.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 \\
        [--variants fp8,half_batch] [--out chiprun_out/limits.jsonl]

For each seed, in one process: the program's first steps through the timed
entry (no measured window: training's readings need none) against the
reference; then, for each variant, the reference computed that way and put
in the program's place. One JSON line a seed, every leaf's norms in it, so
that a rule of check.py can be tried on readings already taken.

The variants are the reference's own (chipbench/reference/<kind>.py says
which a kind has): the control ``fp8``; the planted faults ``half_batch``
and ``unchanged`` (every kind), ``no_window`` (afmoe), ``state_dropped`` and
``leaf_unchanged`` (granite_hybrid: one leaf's update dropped, which only
the worst leaf's change sees), ``expert_missing`` (both expert kinds).
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, run  # noqa: E402


def readings(workload, seed, variants, devices, root=ROOT):
    spec = run.load_cell(workload, root)
    adapter = importlib.import_module(
        "chipbench.models." + spec["config"]["adapter"])
    steps = run.WARM_STEPS - 1
    t0 = time.perf_counter()
    cell = adapter.build(spec["config"], spec["traffic"], seed, devices)
    program, _ = run.first_steps(cell)
    cell.free()
    t1 = time.perf_counter()
    reference = cell.reference(steps)
    t2 = time.perf_counter()
    floor = spec["limits"].get("moved_floor", 0)
    vals, worst = check.numbers(program, reference, floor)
    out = {"workload": workload, "seed": seed, "program": vals,
           "program_worst_leaf": worst, "program_s": t1 - t0,
           "reference_s": t2 - t1, "reference_loss": reference["loss"],
           "program_loss": program["loss"],
           "reference_grad_norm": reference["grad_norm"],
           "program_grad_norm": program["grad_norm"],
           "reference_delta_norm": reference["delta_norm"],
           "program_delta_norm": program["delta_norm"],
           "reference_moved": reference["moved"],
           "left_out": check.left_out(reference, floor)}
    for v in variants:
        made = cell.reference(steps, v)
        vals, worst = check.numbers(made, reference, floor)
        out[v] = vals
        out[v + "_worst_leaf"] = worst
        out[v + "_loss"] = made["loss"]
        out[v + "_grad_norm"] = made["grad_norm"]
        out[v + "_delta_norm"] = made["delta_norm"]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="the variants are read on this many of the seeds")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    spec = run.load_cell(a.workload)
    devices = run.find_chips(spec["cell"]["chips"])
    run.use_compile_cache()
    variants = [v for v in a.variants.split(",") if v]
    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        line = json.dumps(readings(a.workload, seed,
                                   variants if k < a.variant_seeds else [],
                                   devices))
        print(line, flush=True)
        if a.out:
            os.makedirs(os.path.dirname(os.path.abspath(a.out)),
                        exist_ok=True)
            with open(a.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
