"""Device milliseconds of the microbatch ``value_and_grad`` programs per
committed step, from the trace."""
from chipbench.trace import program_seconds

PROGRAMS = ("loss_fn",)


def read(run):
    s = program_seconds(run.trace_summary, PROGRAMS)
    steps = run.counters.get("steps")
    if s is None or not steps:
        return None
    return s / steps * 1e3
