"""Longest single stay of the serving loop in one phase inside the window, to
within a factor of two, over every phase but ``idle``
(``phases.longest_stay_ms`` on ``/stats`` at the window's open and close). A
stall of the whole server lands here, whichever phase swallowed it.

``fetch_wait`` is among the phases, and a stay there is the device's step, not
host work: while the step takes hundreds of milliseconds this reads the step's
bucket (1048.576 at a 410 ms step and a wave), shows only a stall longer than
that, and falls when a kernel PR shortens the step. ``loop_work_max_ms`` is the
same reading over the working phases alone. Silent on a program without the
phase counters."""

from perfbench import phases


def read(ctx):
    counters = phases.window_phases(ctx)
    if counters is None:
        return None
    before, after = counters
    return phases.longest_stay_ms(before, after, [p for p in after if p != "idle"])
