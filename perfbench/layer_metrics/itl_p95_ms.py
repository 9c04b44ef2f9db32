"""95th percentile over all gaps between consecutive streamed tokens of all
requests in the window, at the client. The gaps come in steps (a decode step
plus a whole number of admission waves), so this percentile sits on a plateau
and jumps a whole wave when a hundredth of the gaps moves: read it beside
``itl_tail_mean_ms``."""


def read(ctx):
    return ctx["e2e"].get("itl_p95_ms")
