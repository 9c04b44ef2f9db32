"""Tracing & profiling: per-stage timing plus jax/XLA profiler capture.

Reference state: none — observability is delegated to the Flyte console (SURVEY.md §5).
Here the framework owns it: every :class:`~unionml_tpu.stage.Stage` records its last
wall-clock duration (surfaced via :func:`workflow_timings` and the CLI's
``train --profile-dir``), and this module adds xprof trace capture around any block
(viewable with TensorBoard/xprof) plus device-memory statistics.
"""

import contextlib
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from unionml_tpu._logging import logger


@contextlib.contextmanager
def xprof_trace(log_dir: str, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax profiler trace (XLA ops, TPU activity) into ``log_dir``."""
    import jax

    logger.info("Starting profiler trace -> %s", log_dir)
    with jax.profiler.trace(log_dir, create_perfetto_link=False):
        yield
    logger.info("Profiler trace written to %s", log_dir)


def annotate(name: str, **attrs: Any) -> Any:
    """Name a region in profiler traces (shows up in xprof timelines): a context
    manager; ``attrs`` become the event's stats. With no profiler session
    running it costs about a microsecond."""
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


#: duration buckets of a phase: index = bit length of the duration in whole
#: microseconds, so bucket 0 holds what took under 1 us, bucket ``k`` holds
#: ``[2**(k-1), 2**k)`` us, and the last one everything from ``2**30`` us (18 min)
PHASE_BUCKETS = 32


def phase_bucket(seconds: float) -> int:
    """The duration bucket (see :data:`PHASE_BUCKETS`) that ``seconds`` falls in."""
    return min(int(seconds * 1e6).bit_length(), PHASE_BUCKETS - 1)


class PhaseTimeline:
    """What ONE loop thread is doing, as a flat timeline of named phases.

    The thread is in exactly one phase at a time: :meth:`enter` ends the
    phase before, so phases never nest or overlap and every instant between
    the first :meth:`enter` and :meth:`leave` belongs to one of them. Each
    occurrence is

    - a span on the profiler's clock (``<loop>.<phase>`` through
      :func:`annotate`, with the attributes given to :meth:`enter`), which lands
      in the same trace as the device's operations, and
    - always-on counters per phase: ``seconds`` (``time.perf_counter``),
      ``entries``, and ``buckets`` of power-of-two durations
      (:func:`phase_bucket`). They only grow, so two reads difference into a
      window: sums give shares and means, the bucket deltas give the window's
      longest occurrence to within a factor of two.

    The loop thread is the only writer and changes the counters in place, a
    few additions a transition. It counts every transition's beginning and its
    end in one sequence number, odd while the counters are being rewritten, so
    :meth:`snapshot` from any thread takes no lock: it copies, and copies again
    if the number moved meanwhile, and so never sees a phase half-updated.
    """

    def __init__(self, loop: str, phases: Sequence[str]) -> None:
        self._names = {phase: f"{loop}.{phase}" for phase in phases}
        #: per phase [seconds, entries, buckets] of its finished stays
        self._totals: Dict[str, List[Any]] = {
            phase: [0.0, 0, [0] * PHASE_BUCKETS] for phase in phases
        }
        #: [sequence number, the running phase, when it began]
        self._clock: List[Any] = [0, None, 0.0]
        self._span: Any = None

    @property
    def current(self) -> Optional[str]:
        """The phase the loop thread is in (``None`` before the first
        :meth:`enter` and after :meth:`leave`)."""
        return self._clock[1]

    def enter(self, phase: Optional[str], **attrs: Any) -> float:
        """End the running phase and begin ``phase``; returns the stamp
        (``time.perf_counter``) that ends the one and begins the other."""
        clock = self._clock
        _, running, since = clock
        now = time.perf_counter()
        # the spans change hands right at the stamp, the arithmetic comes after:
        # span and counter then bound the same interval to a fraction of a us
        if running is not None:
            self._span.__exit__(None, None, None)
        if phase is not None:
            self._span = annotate(self._names[phase], **attrs)
            self._span.__enter__()
        clock[0] += 1
        if running is not None:
            totals = self._totals[running]
            totals[0] += now - since
            totals[1] += 1
            totals[2][phase_bucket(now - since)] += 1
        clock[1], clock[2] = phase, now
        clock[0] += 1
        return now

    def leave(self) -> float:
        """End the running phase and begin none: the loop thread is going away."""
        return self.enter(None)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """``{phase: {"seconds", "entries", "buckets"}}`` as of now, from any
        thread. ``seconds`` holds the running occurrence up to this read, so the
        sum over phases is the thread's wall time; ``entries`` and ``buckets``
        count finished occurrences."""
        clock = self._clock
        while True:
            sequence, running, since = clock
            now = time.perf_counter()
            copy = {
                phase: {"seconds": seconds, "entries": entries, "buckets": list(buckets)}
                for phase, (seconds, entries, buckets) in self._totals.items()
            }
            if sequence % 2 == 0 and clock[0] == sequence:
                break  # else a transition was written meanwhile (a microsecond): again
        if running is not None:
            copy[running]["seconds"] += now - since
        return copy


def workflow_timings(workflow: Any) -> Dict[str, Optional[float]]:
    """Last-run durations of every stage in a workflow (None = not yet run)."""
    return {node.stage.name: node.stage.last_duration for node in workflow.nodes}


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device memory statistics (bytes in use / limit) where the backend reports them."""
    import jax

    stats = []
    for device in jax.devices():
        try:
            raw = device.memory_stats() or {}
        except Exception:  # backend without memory_stats: empty stats are the fallback
            raw = {}
        stats.append(
            {
                "device": str(device),
                "bytes_in_use": raw.get("bytes_in_use"),
                "bytes_limit": raw.get("bytes_limit"),
                "peak_bytes_in_use": raw.get("peak_bytes_in_use"),
            }
        )
    return stats
