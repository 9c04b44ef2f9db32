"""Share of the traced interval that ``fit``'s loop thread spent obtaining its
next batch: the summed durations of the program's ``fit.input_wait`` spans in
the profiler's trace over the trace's length. Silent on a program that emits no
such span, and when the trace reader's cap on host events was reached (spans
may then be missing)."""

from perfbench import phases


def read(ctx):
    trace = ctx["trace"]
    waits = phases.host_spans(trace, ("fit.input_wait",))
    if not waits or trace.window_s <= 0:
        return None
    return 100.0 * sum(end - start for start, end, _ in waits) / trace.window_s
