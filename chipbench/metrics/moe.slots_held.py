"""Token-slots a step's routers sent to the experts held, an expert layer:
growth of the program's own counters over the window
(``mxnet_tpu.profiler.metrics()["moe"]``: ``slots_held`` over ``layers``, kept
on the device and read before the first step and after the window). What the
expert share does follows this number (its row movements and its products
touch the rows of the slots held and no others), so ``step_ms`` does: a fall
means the router has left the experts held and the step does less than the
cell is for. Finds nothing where the adapter gives no such counters or no
expert layer ran."""


def read(run):
    counters = run.get("counters") or {}
    if "slots_held" not in counters or not counters.get("layers"):
        return None
    return counters["slots_held"] / counters["layers"]
