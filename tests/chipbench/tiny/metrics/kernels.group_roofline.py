"""A second kernel family's share of its roofline: what a later PR adds for
a new kernel, as a file and an entry."""
from chipbench import trace


def read(run):
    return trace.kernel_roofline(run, "mx_group_")
