"""repro.obs — flight recorder, metrics plane, exporters, scorecards
(DESIGN.md §18).

One trace schema, two worlds: the simulator and the live runtime emit
identical structured-numpy records through a :class:`TraceRecorder`
(near-zero cost when absent — one ``is not None`` branch per site),
the :class:`MetricsRegistry` replaces scattered benchmark timers, and
the exporters/scorecard turn traces into Perfetto timelines and
detection-quality numbers. :func:`span` names host spans in the JAX
profiler's trace, beside the device's operations.
"""
from repro.obs.export import to_chrome_trace, trace_diff, write_chrome_trace
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Timer,
    instrument_drain,
    span,
)
from repro.obs.scorecard import attempt_outcomes, comparable_core, scorecard
from repro.obs.trace import (
    ACT_KILL,
    ACT_MARK_FAILED,
    ACT_SPECULATE,
    END_COMPLETED,
    END_FAILED,
    END_KILLED,
    FAULT_CODES,
    K_ACTION,
    K_ATT_END,
    K_ATT_START,
    K_BUDGET,
    K_CHECKPOINT,
    K_DETECT,
    K_DISPATCH,
    K_DRAIN,
    K_FAULT,
    K_FETCH_FAIL,
    K_FLOW_BULK,
    K_FLOW_CLOSE,
    K_FLOW_OPEN,
    K_GLANCE_FAIL,
    K_GLANCE_SPATIAL,
    K_GLANCE_TEMPORAL,
    K_LATE,
    K_PREDICT,
    K_RAMP,
    K_ROLLBACK,
    K_THRESH,
    KIND_NAMES,
    NODE_FAULT_CODES,
    TRACE_DTYPE,
    TraceRecorder,
)

__all__ = [
    "TraceRecorder", "TRACE_DTYPE", "KIND_NAMES", "FAULT_CODES",
    "NODE_FAULT_CODES",
    "K_ACTION", "K_DETECT", "K_GLANCE_SPATIAL", "K_GLANCE_TEMPORAL",
    "K_GLANCE_FAIL", "K_THRESH", "K_LATE", "K_ATT_START", "K_ATT_END",
    "K_DRAIN", "K_FLOW_OPEN", "K_FLOW_CLOSE", "K_FLOW_BULK", "K_FAULT",
    "K_ROLLBACK", "K_CHECKPOINT", "K_RAMP", "K_DISPATCH", "K_FETCH_FAIL",
    "K_BUDGET", "K_PREDICT",
    "ACT_MARK_FAILED", "ACT_SPECULATE", "ACT_KILL",
    "END_COMPLETED", "END_FAILED", "END_KILLED",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Timer",
    "instrument_drain", "span",
    "to_chrome_trace", "write_chrome_trace", "trace_diff",
    "scorecard", "comparable_core", "attempt_outcomes",
]
