"""What the readers of the program's phase timeline share
(``unionml_tpu.profiling.PhaseTimeline``: spans ``loop.*`` and ``fit.*`` on the
profiler's clock, counters in ``/stats`` ``generation.pipeline.phases``)."""

import inspect
from typing import Any, Dict, Iterable, List, Optional, Tuple

from perfbench import trace as trace_reader

#: the serving loop's working phases: everything but ``idle`` (no work to do)
#: and ``fetch_wait`` (blocked on the device's step: device time, not host work)
LOOP_WORK = ("admit", "prefill", "plan", "dispatch", "apply", "fan_out")


def host_spans(trace: Any, names: Iterable[str]) -> Optional[List[Tuple[float, float, str]]]:
    """``(start, end, name)`` of the trace's host events called one of
    ``names``, in time order. ``None`` without a trace, and where the trace
    reader stopped keeping host events at its cap (``trace.read``'s
    ``max_host_events``): spans may then be missing. ``None`` too where that
    cap cannot be read off the reader, so that no number rests on a guess."""
    cap = inspect.signature(trace_reader.read).parameters.get("max_host_events")
    if trace is None or cap is None or len(trace.host) >= cap.default:
        return None
    names = frozenset(names)
    return sorted((start, end, name) for _, name, start, end in trace.host if name in names)


def window_phases(ctx: Dict[str, Any]) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """The serving loop's phase counters at the window's open and at its close,
    or ``None`` on a program whose ``/stats`` has none."""
    before = ctx["load"]["stats_open"]["generation"]["pipeline"].get("phases")
    after = ctx["load"]["stats_close"]["generation"]["pipeline"].get("phases")
    if not before or not after:
        return None
    return before, after


def longest_stay_ms(before: Dict[str, Any], after: Dict[str, Any],
                    phases: Iterable[str]) -> Optional[float]:
    """Upper edge, in ms, of the highest duration bucket of ``phases`` that
    gained an entry between two reads (bucket ``k`` holds stays of
    ``[2**(k-1), 2**k)`` microseconds): the longest stay to a factor of two."""
    highest = None
    for phase in phases:
        for k, (now, then) in enumerate(zip(after[phase]["buckets"], before[phase]["buckets"])):
            if now > then and (highest is None or k > highest):
                highest = k
    return None if highest is None else 2.0 ** highest / 1e3
