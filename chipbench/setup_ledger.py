"""What the program under test recorded of its own start, as the ``setup.*``
readers take it: the top-level entries of the compile ledger
(``mxnet_tpu.profiler.metrics()["jax_compile"]``) before the window's first
timed dispatch, and the start-up section (``["setup"]``). Both are None over
a program that has neither (the parent of the ledger's split)."""


def before_window(run):
    """The ledger's entries whose ``at_step`` is at most the steps started
    less the window's dispatches: ``setup.jax_compile_s``'s rule."""
    from mxnet_tpu import profiler
    m = profiler.metrics()
    ledger, steps = m.get("jax_compile"), m.get("train_step")
    if not ledger or not steps or "cache_load_s" not in ledger:
        return None
    before = steps["steps"] - len(run["dispatch_ms"])
    return [e for e in ledger["entries"] if e["at_step"] <= before]


def start_up(key):
    """``metrics()["setup"][key]``: a number of seconds, or None."""
    from mxnet_tpu import profiler
    value = (profiler.metrics().get("setup") or {}).get(key)
    return None if value is None else float(value)
