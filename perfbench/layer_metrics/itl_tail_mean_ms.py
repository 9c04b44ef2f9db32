"""Mean of the largest tenth of all gaps between consecutive streamed tokens of
all requests in the window, at the client: the tail of the gaps, in a form that
moves a little when the tail does (the gaps come in steps, so a percentile of
them jumps). In a cell at capacity it is read, not judged by a bound."""


def read(ctx):
    return ctx["e2e"].get("itl_tail_mean_ms")
