"""Seconds from the start of the process to the first line of the program's
package (``mxnet_tpu.profiler.metrics()["setup"]["before_import_s"]``, from
/proc, to 10 ms): the interpreter, the harness's imports, JAX's import and the
TPU runtime's start, which ``run.find_chips`` asks for before the package is
imported. Moves setup_s."""
from chipbench import setup_ledger


def read(run):
    return setup_ledger.start_up("before_import_s")
