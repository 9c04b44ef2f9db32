"""From a JAX profiler trace (``.xplane.pb``) to device times.

``jax.profiler.ProfileData`` reads the file: planes, their lines, events with a
start and a duration in nanoseconds. A TPU's plane is ``/device:TPU:<n>``; its
``XLA Modules`` line holds one event per execution of a compiled program and
its ``XLA Ops`` line one per operation inside it. Host threads are lines of the
``/host:CPU`` plane. Everything the per-layer metrics read from a trace is
worked out here, so that every PR computes it the same way.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start, end in seconds on the trace's clock

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_length(intervals: Iterable[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for start, stop in sorted(intervals):
        if stop <= end:
            continue
        total += stop - max(start, end)
        end = stop
    return total


def gaps_between(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval covers."""
    out, cursor = [], window[0]
    for start, stop in sorted(intervals):
        if start > cursor:
            out.append((cursor, min(start, window[1])))
        cursor = max(cursor, stop)
        if cursor >= window[1]:
            break
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return [(a, b) for a, b in out if b > a]


@dataclass
class DeviceTrace:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)  # name, start, end
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    host: List[Tuple[str, str, float, float]]  # thread, event, start, end
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        per_device = [union_length((s, e) for _, s, e in d.ops or d.modules) for d in self.devices]
        return sum(per_device) / len(per_device)

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def module_runs(self, patterns: Sequence[str], device: int = 0) -> List[Interval]:
        """Executions of the programs whose name holds one of ``patterns``."""
        if not self.devices:
            return []
        return [
            (s, e) for name, s, e in self.devices[device].modules
            if any(p in name for p in patterns)
        ]

    def op_seconds(self, patterns: Sequence[str], device: int = 0) -> float:
        if not self.devices:
            return 0.0
        return sum(
            e - s for name, s, e in self.devices[device].ops if any(p in name for p in patterns)
        )

    def op_seconds_within(
        self, patterns: Sequence[str], runs: Sequence[Interval], device: int = 0
    ) -> float:
        """Device seconds of matching operations that lie inside ``runs``."""
        if not self.devices or not runs:
            return 0.0
        runs = sorted(runs)
        total, i = 0.0, 0
        for name, s, e in sorted(self.devices[device].ops, key=lambda op: op[1]):
            if not any(p in name for p in patterns):
                continue
            while i < len(runs) and runs[i][1] <= s:
                i += 1
            if i < len(runs) and runs[i][0] <= s:
                total += e - s
        return total

    def top_ops(self, n: int = 10) -> List[List]:
        totals: Dict[str, float] = {}
        for device in self.devices[:1]:
            for name, s, e in device.ops:
                key = short_op_name(name)
                totals[key] = totals.get(key, 0.0) + (e - s)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds] for name, seconds in ranked]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest device-idle gaps, each named by the host thread and event
        that covered most of it."""
        if not self.devices:
            return []
        device = self.devices[0]
        gaps = gaps_between(((s, e) for _, s, e in device.ops or device.modules), self.window)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            best, best_overlap = "no host event", 0.0
            for thread, event, s, e in self.host:
                overlap = min(b, e) - max(a, s)
                if overlap > best_overlap:
                    best, best_overlap = f"{thread}: {event}", overlap
            out.append([best[:120], b - a])
        return out


_HLO = re.compile(r"^%?(?P<name>[^ =]+) = (?:\([^)]*\)|\S+) (?P<opcode>[a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op_name(event: str) -> str:
    """A device operation's event name, cut to what tells operations apart.

    The TPU's ``XLA Ops`` events are named by the whole HLO instruction
    (``%layer_20.2 = bf16[...] custom-call(...), custom_call_target="..."``).
    This keeps the instruction's name without its trailing numbers (so that the
    24 layers' copies of one operation add up), its opcode and a custom call's
    target: ``layer_ custom-call tpu_custom_call``."""
    match = _HLO.match(event)
    if not match:
        return re.sub(r"[.\d]+$", "", event)[:100] or event[:100]
    name = re.sub(r"[.\d]+$", "", match.group("name")) or match.group("name")
    target = _TARGET.search(event)
    parts = [name, match.group("opcode")] + ([target.group(1)] if target else [])
    return " ".join(parts)[:100]


#: host events that say nothing about what the host was doing
_HOST_NOISE = ("$", "ThreadpoolListener", "ProfilerSession")


def read(log_dir: str, max_host_events: int = 200_000) -> TraceSummary:
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    devices: List[DeviceTrace] = []
    host: List[Tuple[str, str, float, float]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name not in (MODULE_LINE, OP_LINE):
                    continue
                target = device.modules if line.name == MODULE_LINE else device.ops
                for event in line.events:
                    start = event.start_ns * 1e-9
                    end = start + event.duration_ns * 1e-9
                    target.append((event.name, start, end))
                    lo, hi = min(lo, start), max(hi, end)
            devices.append(device)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    start = event.start_ns * 1e-9
                    end = start + event.duration_ns * 1e-9
                    lo, hi = min(lo, start), max(hi, end)
                    if len(host) < max_host_events and not event.name.startswith(_HOST_NOISE):
                        host.append((line.name, event.name, start, end))
    devices.sort(key=lambda d: d.name)
    if lo > hi:
        lo = hi = 0.0
    return TraceSummary(devices=devices, host=host, window=(lo, hi))


def dump(summary: TraceSummary, path: str, module_runs: int = 6, min_op_s: float = 1e-6,
         host_events: int = 300) -> None:
    """Write the start of a trace as JSON: the first ``module_runs`` program
    executions of each device with the operations of ``min_op_s`` or longer
    inside them (names cut by :func:`short_op_name`) and the host events beside
    them, times in whole nanoseconds from the window's start. Small enough to
    keep with the tests; :func:`load` reads it back."""
    import json

    origin = summary.window[0]

    def ns(t: float) -> float:
        return round(t - origin, 9)

    devices, end = [], summary.window[0]
    for device in summary.devices:
        modules = sorted(device.modules, key=lambda m: m[1])[:module_runs]
        stop = modules[-1][2] if modules else summary.window[0]
        end = max(end, stop)
        ops = [[short_op_name(n), ns(s), ns(e)] for n, s, e in device.ops
               if e <= stop and e - s >= min_op_s]
        devices.append({"name": device.name, "modules": [[n, ns(s), ns(e)] for n, s, e in modules],
                        "ops": ops})
    host = [[t, n[:80], ns(s), ns(e)] for t, n, s, e in summary.host if e <= end][:host_events]
    with open(path, "w") as fh:
        json.dump({"window": [0.0, ns(end)], "devices": devices, "host": host}, fh,
                  separators=(",", ":"))


def load(path: str) -> TraceSummary:
    import json

    with open(path) as fh:
        kept = json.load(fh)
    devices = [
        DeviceTrace(d["name"], [tuple(m) for m in d["modules"]], [tuple(o) for o in d["ops"]])
        for d in kept["devices"]
    ]
    return TraceSummary(devices, [tuple(h) for h in kept["host"]], tuple(kept["window"]))
