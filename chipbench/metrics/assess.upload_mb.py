"""Megabytes uploaded per tick that uploads the attempt mirror: the
backend's ``upload_bytes`` summed over the window's ticks."""


def read(run):
    c = run.counters
    if not c.get("upload_ticks"):
        return None
    return c["upload_bytes_total"] / c["upload_ticks"] / 1e6
