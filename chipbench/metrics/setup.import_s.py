"""Seconds the program's package takes to import, its first line to its last
(``mxnet_tpu.profiler.metrics()["setup"]["import_s"]``). Moves setup_s."""
from chipbench import setup_ledger


def read(run):
    return setup_ledger.start_up("import_s")
