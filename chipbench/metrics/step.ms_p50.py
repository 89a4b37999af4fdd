"""Median gap between consecutive loss arrivals in the traced run's window
(host clock). Says "the program did not change" beside a step_ms that moved."""
import statistics


def read(run):
    return statistics.median(run["gaps_ms"]) if run["gaps_ms"] else None
