"""Seconds JAX itself reports tracing and lowering the programs before the
window's first timed dispatch (``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` of the compile ledger's top-level entries,
``setup_ledger.before_window``): the part of ``setup.jax_compile_s`` that the
persistent cache cannot save. Moves setup_s."""
from chipbench import setup_ledger

PHASES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")


def read(run):
    entries = setup_ledger.before_window(run)
    if entries is None:
        return None
    return float(sum(e["seconds"] for e in entries if e["event"] in PHASES))
