"""Device time of the operations a trace names by the START of their short
name (``trace.short_op_name``), inside given program runs.

A Mosaic call is named by its innermost scope (``%ssm_step.18 = ...
custom-call(``), so the start of the short name finds the kernel and nothing
else; a substring of the whole instruction would also find whatever takes the
kernel's result as an operand."""

from __future__ import annotations

from typing import Sequence

from perfbench import trace as traces


def seconds_within(trace, names: Sequence[str], runs: Sequence[traces.Interval], device: int = 0) -> float:
    """Seconds of the operations whose short name starts with one of ``names``
    and that begin inside one of ``runs``; 0.0 on a trace without devices."""
    if not trace.devices or not runs:
        return 0.0
    runs, names = sorted(runs), tuple(names)
    total, i = 0.0, 0
    for name, start, end in sorted(trace.devices[device].ops, key=lambda op: op[1]):
        # a call that returns a tuple is not cut by ``short_op_name`` and keeps its ``%``
        if not traces.short_op_name(name).lstrip("%").startswith(names):
            continue
        while i < len(runs) and runs[i][1] <= start:
            i += 1
        if i < len(runs) and runs[i][0] <= start:
            total += end - start
    return total
