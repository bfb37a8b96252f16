"""Share of the memory-bound roofline the assessment programs reach:
the window's algorithmic bytes (``costs.window_assess_bytes``, from the
rows and jobs each tick really held) over the chip's HBM bandwidth,
divided by their device time from the trace."""
from chipbench import costs
from chipbench.trace import program_seconds

PROGRAMS = ("pallas_spatial", "pallas_temporal", "pallas_winning",
            "pallas_reap", "pallas_late", "failure_core", "spatial_core",
            "temporal_core", "winning_core", "reap_core", "late_core")


def read(run):
    s = program_seconds(run.trace_summary, PROGRAMS)
    c = run.counters
    if not s or not c.get("tick_work"):
        return None
    bw = costs.peaks(run.device_kind)["hbm_bytes_per_s"]
    nbytes = costs.window_assess_bytes(c["tick_work"], c["n_nodes"])
    return nbytes / bw / s * 100.0
