"""Median host time inside the step's call, before any read (host clock)."""
import statistics


def read(run):
    return statistics.median(run["dispatch_ms"]) if run["dispatch_ms"] else None
