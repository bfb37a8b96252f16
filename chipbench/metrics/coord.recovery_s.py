"""Seconds the crash cost: the disturbed step's wall minus the median
step wall of the window (as ``benchmarks/perf_runtime`` defines it)."""


def read(run):
    return run.counters.get("recovery_s")
