"""Host work of the serving loop per decode step: seconds the loop thread spent
in its working phases (``phases.LOOP_WORK``; not idle, not blocked in the token
fetch) over the step dispatches, both as ``/stats`` counted them
(``generation.pipeline.phases``) between the window's open and its close.

The harness reads per-layer metrics in the ``--trace 1`` run only, so the
ledger's value is the value with the profiler on, when every phase change also
records a span and the runtime's own host tracing runs beside the loop: about
half as much again as the same counters give in an untraced run (PERF.md
section 5 has both). Compare it with other traced runs only. Silent on a
program without the phase counters."""

from perfbench import phases


def read(ctx):
    before = ctx["load"]["stats_open"]["generation"]["pipeline"]
    after = ctx["load"]["stats_close"]["generation"]["pipeline"]
    if "phases" not in before or "phases" not in after:
        return None
    steps = after["step_dispatches"] - before["step_dispatches"]
    if steps <= 0:
        return None
    seconds = sum(
        after["phases"][p]["seconds"] - before["phases"][p]["seconds"] for p in phases.LOOP_WORK
    )
    return 1e3 * seconds / steps
