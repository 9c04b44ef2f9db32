"""What one ``fit`` call costs beside its steady loop: the program's
``fit.start`` span (entry to the timed loop: loader, first batch, first step
and the wait for it) plus its ``fit.finish`` span (checkpoint flush, loader
close; the wait for the device's backlog before it is ``fit.drain`` and is not
counted), mean over the calls that lie whole in the profiler's trace. Silent
when there is no such call, and when the trace reader's cap on host events was
reached."""

from perfbench import phases


def read(ctx):
    calls, opened = [], None
    for start, end, name in phases.host_spans(ctx["trace"], ("fit.start", "fit.finish")) or ():
        if name == "fit.start":
            opened = end - start
        elif opened is not None:  # the finish of the call whose start was seen
            calls.append(opened + (end - start))
            opened = None
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
