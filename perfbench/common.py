"""What both drivers share: the clock's origin, compile counting, the profiler
around a part of the window, the device as JAX reports it."""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax

from perfbench import trace as trace_reader


def process_start() -> float:
    """``time.perf_counter()`` at which this process was started, so that
    ``setup_s`` counts the interpreter's start and the imports too."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()
        started_ticks = float(fields[19])  # field 22 of the whole line
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        age = uptime - started_ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return now - age
    except (OSError, ValueError, IndexError):
        pass
    return now


class CompileCounter:
    """Persistent-cache hits and misses, as JAX reports them. A miss is a
    compilation; a hit or a miss is a program looked up for the first time."""

    def __init__(self) -> None:
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def lookups(self) -> int:
        return self.hits + self.misses


class Phases:
    """The run's timeline: seconds since the process started at each mark, with
    the compile cache's hits and misses so far. Printed on standard error and
    kept in the result line under ``phases`` (the driver ignores it)."""

    def __init__(self, t0: float, counter: CompileCounter) -> None:
        self.t0 = t0
        self.counter = counter
        self.marks: list = []

    def mark(self, name: str) -> None:
        entry = [name, round(time.perf_counter() - self.t0, 3), self.counter.hits, self.counter.misses]
        self.marks.append(entry)
        print("phase %s: %.1f s (cache hits %d, misses %d)" % tuple(entry), file=sys.stderr, flush=True)


class Tracer:
    """The JAX profiler around a part of the window. The trace is written
    under ``TMPDIR``, reduced at once and deleted."""

    def __init__(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.summary: Optional[trace_reader.TraceSummary] = None
        self.interval: Optional[Tuple[float, float]] = None
        self._started: Optional[float] = None
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._started = time.perf_counter()

    def stop_after(self, seconds: float) -> None:
        self._timer = threading.Timer(float(seconds), self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        with self._lock:
            if self._started is None or self.interval is not None:
                return
            stopped = time.perf_counter()
            jax.profiler.stop_trace()
            self.interval = (self._started, stopped)

    def finish(self) -> None:
        """Stop if still running, read the trace, delete its files."""
        if self._timer is not None:
            self._timer.cancel()
        self.stop()
        try:
            if self.interval is not None:
                self.summary = trace_reader.read(self.dir)
                keep = os.environ.get("PERFBENCH_KEEP_TRACE")  # for a look by hand, and the tests' fixture
                if keep:
                    trace_reader.dump(self.summary, keep)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip: the allocator's peak of live arrays
    plus, where the backend reports it apart (the TPU does), the peak it
    reserved for the loaded programs' scratch memory."""
    peaks = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        print(f"memory_stats {device}: {stats}", file=sys.stderr, flush=True)
        peaks.append(int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def device_info() -> Dict[str, Any]:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
