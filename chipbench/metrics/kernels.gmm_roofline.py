"""Least time the chip could take for the routed experts' grouped products a
step REQUIRES (forward three a layer, backward six, at the expected number of
token-slots of the experts held; no recompute, no padding; from shapes,
counts/<kind>.py) over the device time a step of the grouped-product kernels
(``mx_gmm_fwd``, ``mx_gmm_dx``, ``mx_gmm_dw``; the forward ones run twice
under full remat). Finds nothing where the kind counts no such products or
no such kernel ran."""
from chipbench import trace


def read(run):
    return trace.kernel_roofline(run, "mx_gmm_")
