"""90th percentile of the window's committed step walls
(``StepReport.wall_s``), in ms. The steps that wait for the straggler
tick while the lost host is not yet declared failed are about a tenth
of the window, so this quantile lies at their edge and swings from run
to run: it is read here, beside ``tokens_per_s``, and not bounded."""


def read(run):
    return run.counters.get("step_p90_ms")
