"""Deterministic fault injection + the structured engine-failure taxonomy.

The serving core's failure handling used to be untestable: a device fault only
ever appeared as whatever exception a wedged runtime happened to raise, so the
recovery paths (engine rebuild, per-request quarantine, pool-exhaustion
fallback) shipped unexercised. This module makes every fault class the
supervisor must survive *injectable on a CPU mesh, deterministically*:

- :class:`FaultPlan` is a seeded, schedule-addressable fault script — "fail
  the 3rd step dispatch", "NaN slot 1's logits after dispatch 5", "raise on
  the 2nd prefill", "stall the 4th token fetch 300 ms", "exhaust the block
  pool on the 2nd admission", "fail the first 2 engine rebuilds". The engine
  (:class:`~unionml_tpu.serving.continuous.DecodeEngine`), batcher, and
  speculative facade consult the plan at each site behind a
  ``if self._faults is not None`` guard, so a plan-less engine pays ONE host
  branch per site and no device work — the hooks are zero-cost when disabled
  and add no host syncs to the hot path (graftlint holds that line).
- :class:`FaultError` is what an injected fault raises — a stand-in for the
  runtime's own device errors, taken through the SAME except paths real
  failures take (the handlers never special-case it).
- :class:`EngineFailure` is the structured error the serving stack reports
  UPWARD: every request that dies on an engine-side failure carries a
  machine-readable ``reason`` slug (and a retryability hint) instead of a
  stringified traceback, so the HTTP layer can map it to the unified error
  contract and clients can branch without parsing prose.

Determinism: schedules address global per-site counters (1-based), so the same
plan against the same request schedule injects at exactly the same operations;
``seed`` drives the optional Bernoulli storm rates (``step_failure_rate``),
which are reproducible for a fixed seed + site ordering. A plan is owned by ONE
engine/facade (the worker thread that drives it); counters are not
cross-thread-safe by design.
"""

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["EngineFailure", "FaultError", "FaultPlan"]


class FaultError(RuntimeError):
    """An injected device-side fault (see :class:`FaultPlan`).

    Raised at the injection site exactly where the runtime's own error would
    surface; the serving stack's failure handlers treat it like any other
    device exception (nothing downstream special-cases injection).
    """

    def __init__(self, message: str, *, site: str) -> None:
        super().__init__(message)
        #: which injection site fired (``step_dispatch``/``step_fetch``/...)
        self.site = site


class EngineFailure(RuntimeError):
    """A structured engine-side failure delivered to a request.

    ``reason`` is a machine-readable slug (``device_failure``,
    ``nan_logits``, ``request_unrecoverable``, ``engine_failed``,
    ``speculative_round_failed``, ...) the HTTP layer forwards in the unified
    error envelope; ``retryable`` states whether a client retry can plausibly
    succeed (it maps to 503-vs-500 at the route).
    """

    def __init__(self, message: str, *, reason: str, retryable: bool = True) -> None:
        super().__init__(message)
        self.reason = reason
        self.retryable = retryable


@dataclasses.dataclass
class FaultPlan:
    """A deterministic, schedule-addressable fault-injection script.

    Every index is **1-based** against a global per-site counter the engine
    advances as it runs (dispatches, fetches, prefills, admissions, rebuild
    attempts), so a plan addresses operations, not wall time:

    :param step_dispatch_failures: decode-step dispatch indexes that raise
        :class:`FaultError` *instead of* dispatching (device state intact, but
        the engine conservatively treats any step failure as poisoning).
    :param step_fetch_failures: token-fetch (burst) indexes that raise at the
        fused ``device_get`` — the deferred-error shape, where the step's
        donated outputs were already reassigned.
    :param prefill_failures: prefill-dispatch indexes that raise — the
        per-request-attributable admission failure.
    :param nan_logits: ``(step_dispatch_index, slot)`` pairs — after that
        dispatch, the slot's ``last_logits`` row is overwritten with NaN, so
        the NEXT step samples from poisoned logits and the engine's in-step
        finiteness flag trips (per-request quarantine, not batch failure).
    :param fetch_stalls: ``(fetch_index, stall_ms)`` pairs — sleep that long
        before the fetch, simulating a wedged device queue for the
        supervisor's fetch-stall watchdog.
    :param pool_exhausted_admits: admission (``admit_many`` call) indexes
        during which the prefix-cache block pool behaves fully referenced:
        no new block can be indexed, exercising the graceful cache-less
        fallback.
    :param rebuild_failures: fail this many engine rebuild attempts before
        letting one succeed (drives the supervisor's bounded-backoff loop).
    :param speculative_round_failures: speculative-generation round indexes
        that raise (the facade's structured-failure path).
    :param step_failure_rate: seeded Bernoulli dispatch-failure probability —
        the "chaos storm" mode, on top of the scheduled sites.
    :param seed: seeds the storm-rate RNG (scheduled sites need no RNG).
    """

    step_dispatch_failures: Sequence[int] = ()
    step_fetch_failures: Sequence[int] = ()
    prefill_failures: Sequence[int] = ()
    nan_logits: Sequence[Tuple[int, int]] = ()
    fetch_stalls: Sequence[Tuple[int, float]] = ()
    pool_exhausted_admits: Sequence[int] = ()
    rebuild_failures: int = 0
    speculative_round_failures: Sequence[int] = ()
    step_failure_rate: float = 0.0
    seed: int = 0

    def __post_init__(self) -> None:
        #: optional Telemetry mirror for injected-fault counters (attach with
        #: ``plan.telemetry = tel``); consulted behind ``is not None`` only
        self.telemetry = None
        self._rng = np.random.default_rng(self.seed)
        self._dispatches = 0
        self._fetches = 0
        self._prefills = 0
        self._admits = 0
        self._rebuilds = 0
        self._spec_rounds = 0
        self._admit_depth = 0
        self._nan_by_step: Dict[int, List[int]] = {}
        for step, slot in self.nan_logits:
            self._nan_by_step.setdefault(int(step), []).append(int(slot))
        self._stall_by_fetch = {int(i): float(ms) for i, ms in self.fetch_stalls}
        #: faults that FIRED, by site slug (the /stats "injected" block)
        self.injected: Dict[str, int] = {}
        #: faults the serving stack OBSERVED AND HANDLED (quarantines taken,
        #: exhausted allocations absorbed, ...) — recovery accounting writes
        #: here via :meth:`note_observed`
        self.observed: Dict[str, int] = {}

    # ------------------------------------------------------------ engine sites

    def _fire(self, site: str, message: str) -> None:
        self.injected[site] = self.injected.get(site, 0) + 1
        if self.telemetry is not None:
            self.telemetry.faults_injected_total.inc(1.0, site)
        raise FaultError(message, site=site)

    def check_step_dispatch(self) -> None:
        """Advance the dispatch counter; raise when this dispatch is scheduled
        to fail (or the storm rate fires)."""
        self._dispatches += 1
        if self._dispatches in set(self.step_dispatch_failures):
            self._fire("step_dispatch", f"injected step-dispatch failure #{self._dispatches}")
        if self.step_failure_rate > 0 and self._rng.random() < self.step_failure_rate:
            self._fire("step_dispatch", f"injected storm step failure #{self._dispatches}")

    def take_nan_slots(self) -> List[int]:
        """Slots whose ``last_logits`` the engine must poison after the
        dispatch just counted (empty almost always)."""
        slots = self._nan_by_step.pop(self._dispatches, [])
        if slots:
            self.injected["nan_logits"] = self.injected.get("nan_logits", 0) + len(slots)
            if self.telemetry is not None:
                self.telemetry.faults_injected_total.inc(float(len(slots)), "nan_logits")
        return slots

    def check_fetch(self) -> None:
        """Advance the fetch counter; raise when this fetch is scheduled to
        fail (the deferred-error shape)."""
        self._fetches += 1
        if self._fetches in set(self.step_fetch_failures):
            self._fire("step_fetch", f"injected token-fetch failure #{self._fetches}")

    def take_fetch_stall_ms(self) -> Optional[float]:
        """Stall (ms) scheduled for the fetch just counted, or ``None``."""
        ms = self._stall_by_fetch.pop(self._fetches, None)
        if ms is not None:
            self.injected["fetch_stall"] = self.injected.get("fetch_stall", 0) + 1
            if self.telemetry is not None:
                self.telemetry.faults_injected_total.inc(1.0, "fetch_stall")
        return ms

    def check_prefill(self) -> None:
        """Advance the prefill counter; raise when this prefill is scheduled
        to fail."""
        self._prefills += 1
        if self._prefills in set(self.prefill_failures):
            self._fire("prefill", f"injected prefill failure #{self._prefills}")

    def begin_admit(self) -> None:
        """Enter an ``admit_many`` call (advances the admission counter at the
        outermost entry; :meth:`pool_exhausted` is scoped to this window)."""
        if self._admit_depth == 0:
            self._admits += 1
            if self._admits in set(self.pool_exhausted_admits):
                self.injected["pool_exhausted"] = self.injected.get("pool_exhausted", 0) + 1
                if self.telemetry is not None:
                    self.telemetry.faults_injected_total.inc(1.0, "pool_exhausted")
        self._admit_depth += 1

    def end_admit(self) -> None:
        self._admit_depth = max(0, self._admit_depth - 1)

    def pool_exhausted(self) -> bool:
        """Whether the block pool must behave fully referenced right now (only
        inside an admission window this plan scheduled)."""
        return self._admit_depth > 0 and self._admits in set(self.pool_exhausted_admits)

    def check_rebuild(self) -> None:
        """Advance the rebuild counter; raise while scheduled rebuild failures
        remain (the supervisor's backoff loop consumes them one per attempt)."""
        self._rebuilds += 1
        if self._rebuilds <= int(self.rebuild_failures):
            self._fire("rebuild", f"injected rebuild failure #{self._rebuilds}")

    def check_speculative_round(self) -> None:
        """Advance the speculative-round counter; raise when scheduled."""
        self._spec_rounds += 1
        if self._spec_rounds in set(self.speculative_round_failures):
            self._fire(
                "speculative_round", f"injected speculative-round failure #{self._spec_rounds}"
            )

    # -------------------------------------------------------------- accounting

    def note_observed(self, kind: str) -> None:
        """Count one injected fault the serving stack handled (quarantine
        taken, exhausted allocation absorbed, stall survived, ...)."""
        self.observed[kind] = self.observed.get(kind, 0) + 1

    def stats(self) -> Dict[str, Dict[str, int]]:
        """The ``/stats`` → ``generation.robustness.faults`` block."""
        return {"injected": dict(self.injected), "observed": dict(self.observed)}
