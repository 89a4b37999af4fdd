"""Token-slots routed to an expert held that no grouped product covered,
over the window: growth of the program's own counter
(``mxnet_tpu.profiler.metrics()["moe"]["slots_dropped"]``, kept on the device
and read before the first step and after the window). Must read 0. Finds
nothing where the adapter gives no such counter."""


def read(run):
    counters = run.get("counters") or {}
    if "slots_dropped" not in counters:
        return None
    return float(counters["slots_dropped"])
