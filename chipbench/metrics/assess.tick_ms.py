"""Host milliseconds per assessment tick: window ``assess_wall`` over
window ``assess_ticks`` (the policy's ``assess``, device work inside)."""


def read(run):
    c = run.counters
    if not c.get("ticks"):
        return None
    return c["assess_wall_s"] / c["ticks"] * 1e3
