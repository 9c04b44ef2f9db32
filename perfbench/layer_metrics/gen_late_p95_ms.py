"""How late the load generator ran: 95th percentile of send time minus due
time over the requests due in the window. A starved generator shows here, not
as a fast server."""

from perfbench.traffic import percentile


def read(ctx):
    late = ctx["e2e"].get("late_ms")
    if not late:
        return None
    return percentile(late, 95)
