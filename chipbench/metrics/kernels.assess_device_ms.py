"""Device milliseconds per tick of the assessment programs (the Pallas
bucket passes with their XLA tails, and the XLA cores), from the trace."""
from chipbench.trace import program_seconds

PROGRAMS = ("pallas_spatial", "pallas_temporal", "pallas_winning",
            "pallas_reap", "pallas_late", "failure_core", "spatial_core",
            "temporal_core", "winning_core", "reap_core", "late_core")


def read(run):
    s = program_seconds(run.trace_summary, PROGRAMS)
    ticks = run.counters.get("ticks")
    if s is None or not ticks:
        return None
    return s / ticks * 1e3
