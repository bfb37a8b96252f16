"""Share of the window in which no operation ran on the device:
1 - busy / window, from the trace."""


def read(run):
    t = run.trace_summary
    if not t or t.get("idle_share") is None or not t.get("devices"):
        return None
    return t["idle_share"] * 100.0
