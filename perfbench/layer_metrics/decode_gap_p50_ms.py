"""Median device-idle gap between one decode-step program and the next one, in
the traced part of the window: what the engine's host side leaves unhidden."""

from perfbench.trace import union_length
from perfbench.traffic import percentile


def read(ctx):
    trace = ctx["trace"]
    runs = sorted(trace.module_runs(ctx["config"]["perfbench"]["programs"]["decode_step"]))
    if len(runs) < 2:
        return None
    busy = sorted((s, e) for _, s, e in trace.devices[0].modules)
    gaps = []
    for (_, end), (start, _) in zip(runs, runs[1:]):
        between = [(max(s, end), min(e, start)) for s, e in busy if s < start and e > end]
        gaps.append(max(0.0, (start - end) - union_length(b for b in between if b[1] > b[0])))
    return 1e3 * percentile(gaps, 50)
