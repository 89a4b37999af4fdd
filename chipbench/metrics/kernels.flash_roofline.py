"""Least time the chip could take for the attention work a step REQUIRES
(causal forward once plus backward, no recompute; from shapes,
counts/<kind>.py) over the device time a step of the flash kernels
(``mx_flash_fwd``, ``mx_flash_dq``, ``mx_flash_dkv``; the forward runs twice
under full remat, which is the implementation's cost and not required work).
Finds nothing where the kind counts no attention or no such kernel ran."""
from chipbench import trace


def read(run):
    return trace.kernel_roofline(run, "mx_flash_")
