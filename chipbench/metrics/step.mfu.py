"""The whole step's share of the chips' bf16 peak: operations the forward
and backward REQUIRE (chipbench/flops.py, from shapes) over the traced steady
step time x chips x peak."""


def read(run):
    t = run["trace"]
    return 100.0 * run["required"]["step_flops"] / (
        t["step_s"] * run["chips"] * run["peaks"]["bf16_flops"])
