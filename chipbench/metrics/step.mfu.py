"""The whole step's share of the chips' bf16 peak: operations the forward
and backward REQUIRE (the kind's count, chipbench/counts/, from shapes) over
the traced steady step time x chips x peak. The step time is the traced
window over its whole steps, the cut first execution left out (trace.py)."""


def read(run):
    t = run["trace"]
    return 100.0 * run["required"]["step_flops"] / (
        t["step_s"] * run["chips"] * run["peaks"]["bf16_flops"])
