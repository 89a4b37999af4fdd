"""Median host time inside the program's own ``mx.train_step`` span, over the
window's dispatches: the last ``len(run["dispatch_ms"])`` entries of the ring
in ``mxnet_tpu.profiler.metrics()["train_step"]["calls"]``. What
``step.dispatch_ms_p50`` times from outside, less the harness's own call."""
import statistics


def read(run):
    from mxnet_tpu import profiler
    n = len(run["dispatch_ms"])
    calls = (profiler.metrics().get("train_step") or {}).get("calls")
    if not calls or not n:
        return None     # a program without the ring (the parent)
    return statistics.median(us for us, _ in calls[-n:]) / 1e3
