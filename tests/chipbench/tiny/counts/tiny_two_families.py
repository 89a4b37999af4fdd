"""A kind that exists only under the tests' tiny root: the tiny decoder's
sizes with two kernel families, as a count a later PR brings for a model
whose expert products run in a second Pallas kernel. A toy: the numbers are
round so that a test can do the arithmetic by hand."""


def required(work):
    tokens = work["batch"] * work["seq_len"]
    return {"step_flops": 6000 * tokens,
            "kernels": {"mx_flash_": {"flops": 1000 * tokens, "bytes": tokens},
                        "mx_group_": {"flops": tokens, "bytes": 100 * tokens}}}
