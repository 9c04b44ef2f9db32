"""Longest single stay of the serving loop in one of its working phases
(``phases.LOOP_WORK``: admit, prefill, plan, dispatch, apply, fan_out) inside
the window, to within a factor of two (``phases.longest_stay_ms`` on ``/stats``
at the window's open and close). Host work only: unlike ``loop_phase_max_ms``
it leaves out ``fetch_wait``, so it does not move with the device's step and a
host stall far shorter than a step shows. Read in the ``--trace 1`` run, so
with the profiler on. Silent on a program without the phase counters."""

from perfbench import phases


def read(ctx):
    counters = phases.window_phases(ctx)
    if counters is None:
        return None
    return phases.longest_stay_ms(*counters, phases.LOOP_WORK)
