"""Calls of the program's train step, beyond each step object's first,
during which JAX traced, lowered, compiled or loaded a program: the program's
own counter (``mxnet_tpu.profiler.metrics()["train_step"]["retraces"]``),
read in the run's process after the window. Must read 0."""


def read(run):
    from mxnet_tpu import profiler
    section = profiler.metrics().get("train_step")
    if not section or "retraces" not in section:
        return None     # a program without the counter (the parent)
    return float(section["retraces"])
