"""Seconds JAX itself reports in tracing, lowering and backend compile (a
load from the persistent cache lies inside the last and is counted once)
before the window's first timed dispatch: the entries of
``mxnet_tpu.profiler.metrics()["jax_compile"]`` whose ``at_step`` is at most
the steps started less the window's dispatches. The reference's compiles come
after the window and are left out. Moves setup_s."""

PHASES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
          "backend_compile_duration")


def read(run):
    from mxnet_tpu import profiler
    m = profiler.metrics()
    ledger, steps = m.get("jax_compile"), m.get("train_step")
    if not ledger or not steps or "entries" not in ledger:
        return None     # a program without the ledger (the parent)
    before = steps["steps"] - len(run["dispatch_ms"])
    return float(sum(e["seconds"] for e in ledger["entries"]
                     if e["event"] in PHASES and e["at_step"] <= before))
