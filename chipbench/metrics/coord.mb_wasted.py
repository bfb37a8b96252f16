"""Microbatches executed beyond those needed, over the window:
sum of ``StepReport.mb_executed - mb_needed``."""


def read(run):
    return run.counters.get("mb_wasted")
