"""95th percentile of the scheduler's queue wait: the ``queue_wait`` spans of
the server's request traces (``GET /traces/recent``) that were created between
the window's open and its close."""

from perfbench.traffic import percentile


def read(ctx):
    load = ctx["load"]
    lo, hi = load.get("wall_open"), load.get("wall_close")
    if lo is None or hi is None:
        return None
    waits = [wait for created, wait in load.get("queue_waits") or () if lo <= created < hi]
    if not waits:
        return None
    return percentile(waits, 95)
