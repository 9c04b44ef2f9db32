"""Trace-driven fleet observatory (ISSUE 15 tier-1 gate).

The contracts pinned here:

- **Golden replay.** A seeded 2-replica fleet serves a contended mix
  (preemption, queue-full sheds, resumes) with the journal sink on; then
  :func:`replay_journal` re-derives every policy counter and the SLO ledger
  from the journal file ALONE and must match the live scheduler/telemetry
  counters exactly — the journal is a sufficient record of what the
  policies did, bit for bit.
- **Journal versioning.** v1 records (no ``"v"``) load; v2 adds
  session_id + admission block arithmetic; FUTURE versions are rejected
  loudly (misreading one would poison a replay validation).
- **Simulator.** Same requests + config → byte-identical report, the
  request ledger always balances (completed + shed == submitted), and the
  failover drill adopts orphans; the policies inside are the REAL
  ``Router``/``SLOScheduler``/``block_demand`` objects.
- **Autoscaler.** Scale-up on any pressure source, the frozen-idle-EMA
  trap (an idle replica's queue-wait EMA must not pin the fleet "behind"),
  cooldown/hysteresis, and the shed-waives-cooldown escape; on one seeded
  diurnal workload it beats a fleet provisioned for the peak on attainment
  per average replica and keeps interactive attainment at 0.9 or above.
- **Cost model.** The affine prefill fit recovers planted parameters from
  journal records and falls back to defaults when starved of data.
"""

import asyncio
import json

import pytest

from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
from unionml_tpu.serving.fleet import EngineFleet, Router
from unionml_tpu.serving.scheduler import SchedulerConfig
from unionml_tpu.serving.telemetry import JOURNAL_SCHEMA_VERSION, Telemetry
from unionml_tpu.sim import (
    Autoscaler,
    AutoscalerConfig,
    CostModel,
    FleetSimulator,
    ReplicaDeath,
    SimConfig,
    SyntheticConfig,
    fit_cost_model,
    generate_requests,
    load_journal,
    parse_journal_record,
    replay_journal,
)


# ---------------------------------------------------------------- journal I/O


def _v1_record(**over):
    rec = {
        "request_id": "r1",
        "created_unix": 1.0,
        "class": "standard",
        "status": "ok",
        "tokens_in": 8,
        "tokens_out": 4,
        "decode_bursts": 1,
        "ttft_ms": 12.5,
        "spans": [],
    }
    rec.update(over)
    return rec


def test_journal_loader_v1_compat_v2_fields_and_future_rejection(tmp_path):
    rec = parse_journal_record(_v1_record())  # no "v" at all -> v1
    assert rec.version == 1 and rec.session_id is None and rec.block_demand is None
    with pytest.raises(ValueError, match="unsupported journal schema v99"):
        parse_journal_record(_v1_record(v=99))
    with pytest.raises(ValueError, match="missing required field"):
        parse_journal_record({"v": 2})
    v2 = _v1_record(
        v=2, request_id="r2", session_id="sess-1",
        spans=[
            {"kind": "admission", "attrs": {
                "block_demand": 5, "available_blocks": 40, "deadline_ms": 250.0}},
            {"kind": "queue_wait", "dur_ms": 3.25, "attrs": {"resume": False}},
        ],
    )
    path = tmp_path / "journal.jsonl"
    path.write_text(json.dumps(_v1_record()) + "\n\n" + json.dumps(v2) + "\n")
    records = load_journal(str(path))
    assert [r.version for r in records] == [1, 2]  # blank line skipped
    assert records[1].session_id == "sess-1"
    assert records[1].block_demand == 5 and records[1].available_blocks == 40
    assert records[1].deadline_ms == 250.0 and records[1].queue_wait_ms == 3.25
    path.write_text("{not json\n")
    with pytest.raises(ValueError, match=r"journal\.jsonl:1"):
        load_journal(str(path))


def test_replay_discriminates_queued_vs_running_deadline_misses():
    queued = _v1_record(
        v=2, status="shed", reason="deadline_exceeded", ttft_ms=None,
        spans=[{"kind": "admission", "attrs": {}}],
    )
    running = _v1_record(
        v=2, request_id="r2", status="shed", reason="deadline_exceeded",
        spans=[{"kind": "admission", "attrs": {}},
               {"kind": "admitted", "attrs": {"slot": 0}}],
    )
    report = replay_journal([parse_journal_record(r) for r in (queued, running)])
    assert report["deadline_misses_queued"] == 1
    assert report["deadline_misses_running"] == 1
    assert report["shed"] == {"deadline_exceeded": 2}
    assert report["slo_totals"]["standard"] == {"good": 0, "total": 2}


# ----------------------------------------------------------------- cost model


def test_fit_cost_model_recovers_planted_affine_fit():
    base, slope, itl = 4.0, 0.25, 6.0
    records = []
    for i, tokens_in in enumerate([8] * 10 + [64] * 10):
        wait = float(i)  # journaled queue wait is subtracted before fitting
        records.append(parse_journal_record(_v1_record(
            v=2, request_id=f"r{i}", tokens_in=tokens_in, itl_ms=itl,
            ttft_ms=round(wait + base + slope * tokens_in, 3),
            spans=[{"kind": "queue_wait", "dur_ms": wait, "attrs": {}}],
        )))
    fitted = fit_cost_model(records, default=CostModel(dispatch_ms=0.0))
    assert fitted.prefill_ms_per_token == pytest.approx(slope, abs=1e-6)
    assert fitted.prefill_base_ms == pytest.approx(base, abs=1e-6)
    assert fitted.itl_ms == pytest.approx(itl)
    assert fitted.itl_ms_by_class == {"standard": itl}
    # starved of usable records -> the default, never a fit of noise
    assert fit_cost_model(records[:3]) == CostModel()


# ----------------------------------------------------------------- autoscaler


def test_autoscaler_scale_up_triggers_cooldown_and_shed_waiver():
    scaler = Autoscaler(AutoscalerConfig(min_replicas=1, max_replicas=3))
    pressured = {"depth": 0, "queue_wait_ema_ms": None,
                 "pool": {"pressure": 0.95}}
    assert scaler.decide(0.0, [pressured]) == 1  # pool-bound: scale up
    assert scaler.decide(5.0, [pressured]) == 0  # cooldown holds
    assert scaler.decide(6.0, [pressured], shed_rate_per_s=2.0) == 1  # sheds waive it
    assert scaler.decide(40.0, [pressured, pressured, pressured]) == 0  # at ceiling
    assert scaler.stats() == {"ups": 2, "downs": 0, "holds": 2}


def test_autoscaler_ignores_frozen_idle_emas_and_scales_down():
    # queue-wait EMAs only move on pops: a replica the router stopped
    # feeding keeps the last storm's EMA forever. Scoring it would pin the
    # fleet "behind" and scale-down would never fire.
    scaler = Autoscaler(AutoscalerConfig(
        min_replicas=1, max_replicas=4, cooldown_s=0.0, calm_ticks=2))
    idle_after_storm = {"depth": 0, "queue_wait_ema_ms": 2400.0, "pool": None}
    busy = {"depth": 3, "queue_wait_ema_ms": 2400.0, "pool": None}
    assert scaler.decide(0.0, [busy, idle_after_storm]) == 1  # genuine backlog
    assert scaler.decide(5.0, [idle_after_storm] * 3) == 0  # calm 1/2
    assert scaler.decide(10.0, [idle_after_storm] * 3) == -1  # calm 2/2
    assert scaler.decide(15.0, [idle_after_storm] * 2) == 0  # streak reset by the action
    assert scaler.decide(20.0, [idle_after_storm] * 2) == -1
    assert scaler.decide(25.0, [idle_after_storm]) == 0  # at the floor: hold
    assert scaler.decide(30.0, [idle_after_storm]) == 0


# ------------------------------------------------------------------ simulator


def _small_workload(seed=3, users=250):
    return generate_requests(SyntheticConfig(
        users=users, duration_s=60.0, seed=seed, mean_turns=1.3,
        burst_every_s=30.0, prompt_len_median=10.0, budget_median=8.0,
        hot_prefix_blocks=2,
    ))


def test_synthetic_workload_is_deterministic_and_shaped():
    reqs = _small_workload()
    assert reqs == _small_workload()
    assert all(a.arrival_s <= b.arrival_s for a, b in zip(reqs, reqs[1:]))
    assert {r.cls for r in reqs} == {"interactive", "standard", "batch"}
    assert len({r.session_id for r in reqs}) <= 250
    assert any(r.deadline_ms is None for r in reqs if r.cls == "batch")
    assert all(r.deadline_ms == 2000.0 for r in reqs if r.cls == "interactive")


def test_sim_determinism_and_ledger_balance():
    reqs = _small_workload()
    config = SimConfig(
        num_replicas=2, max_replicas=4,
        autoscaler=AutoscalerConfig(min_replicas=1, max_replicas=4),
    )
    first = FleetSimulator(config, reqs).run()
    second = FleetSimulator(config, reqs).run()
    assert first == second  # same requests + config -> byte-identical report
    assert first["requests"] == len(reqs)
    assert first["completed"] + sum(first["shed"].values()) == len(reqs)
    assert 0.0 <= first["attainment"] <= 1.0
    assert first["scheduler"]["admitted"] >= first["completed"]
    assert first["router"]["lookups"] >= len(reqs)
    assert first["slo"]["per_class"].keys() == first["slo_totals"].keys()
    # pools drain clean: a pinned-block leak here wedges admission forever
    sim = FleetSimulator(config, reqs)
    sim.run()
    for rep in sim.replicas:
        assert rep.pinned_blocks == 0 and rep.live_blocks == 0


def test_sim_failover_drill_adopts_orphans():
    reqs = _small_workload(seed=9)
    config = SimConfig(
        num_replicas=3, max_replicas=3,
        deaths=(ReplicaDeath(at_s=20.0, replica=0),),
    )
    report = FleetSimulator(config, reqs).run()
    assert report["dead_replicas"] == [0]
    assert report["failover_adoptions"] >= 1  # mid-run kill orphans someone
    assert report["completed"] + sum(report["shed"].values()) == len(reqs)


def test_autoscaler_beats_peak_provisioning_per_replica_and_holds_interactive():
    """The same 6,000 requests (a diurnal curve with bursts whose peak needs six
    one-slot replicas) through a fleet provisioned for the peak and through one
    that starts at two and autoscales: the autoscaled fleet must win SLO
    attainment per average replica while nine in ten interactive requests
    still meet their objective (0.912 here; the peak-provisioned fleet's 0.999
    is what the replica-seconds it saves are traded against)."""
    reqs = generate_requests(SyntheticConfig(
        users=6000, duration_s=600.0, seed=7, mean_turns=1.0, burst_every_s=150.0,
        prompt_len_median=12.0, budget_median=12.0, hot_prefix_blocks=2,
        diurnal_amplitude=0.8,
    ))
    auto = FleetSimulator(
        SimConfig(num_replicas=2, max_replicas=8, num_slots=1,
                  autoscaler=AutoscalerConfig(min_replicas=1, max_replicas=8)),
        reqs,
    ).run()
    static = FleetSimulator(
        SimConfig(num_replicas=6, max_replicas=6, num_slots=1), reqs
    ).run()
    for report in (auto, static):
        assert report["completed"] + sum(report["shed"].values()) == len(reqs)
    # the static arm is a fair opponent: six replicas do carry the peak
    assert static["attainment"] >= 0.99 and static["replicas"]["avg"] == 6.0
    # the autoscaler followed the curve in both directions ...
    assert auto["replicas"]["min"] == 1 and auto["replicas"]["max"] > 2
    assert auto["autoscaler"]["ups"] >= 1 and auto["autoscaler"]["downs"] >= 1
    # ... and bought its replica-seconds back without giving up interactive
    # (0.395 against 0.167 a replica; the simulator is deterministic)
    assert auto["attainment_per_replica"] > 2 * static["attainment_per_replica"]
    assert auto["slo"]["per_class"]["interactive"]["attainment"] >= 0.9


def test_router_hot_digests_warm_a_scaled_up_replica():
    router = Router(2, block_size=4)
    prompt = list(range(16))
    chosen, decision = router.route(prompt, [(0, 1.0, 0.0), (1, 1.0, 0.0)])
    assert decision["digest_blocks"] == 4
    hot = router.hot_digests(8)
    assert hot and len(hot) == len(set(hot))
    other = 1 - chosen
    router.warm_replica(other, hot)
    # the warmed index advertises the full chained match immediately
    _, warmed = router.route(prompt, [(other, 1.0, 0.0)])
    assert warmed["matched_blocks"] == 4
    assert router.hot_digests(0) == []


# -------------------------------------------------------------- golden replay


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def _engine(model, variables, **kw):
    kw.setdefault("num_slots", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("prefix_cache_blocks", 64)
    kw.setdefault("prefix_block_size", 4)
    return DecodeEngine(model, variables, **kw)


def _supervisor():
    from unionml_tpu.serving.supervisor import EngineSupervisor

    return EngineSupervisor(watchdog_interval_s=0, backoff_s=0.005,
                            backoff_max_s=0.02)


def test_golden_replay_matches_live_fleet_counters(gpt, tmp_path):
    """Record a seeded 2-replica fleet journal in-test, then prove the
    journal alone reproduces the live counters exactly: sheds by reason,
    preemptions, resumes, deadline misses, failover adoptions, and the SLO
    good/total ledger."""
    model, variables = gpt
    path = tmp_path / "journal.jsonl"
    tel = Telemetry(journal_path=str(path))
    fleet = EngineFleet(
        [_engine(model, variables), _engine(model, variables)],
        supervisors=[_supervisor(), _supervisor()],
        telemetry=tel,
        scheduler=SchedulerConfig(max_queue=3, aging_s=120.0),
    )
    # pin every session to replica 0 so one slot is genuinely contended:
    # the batch head admits, the flood overflows the bounded queue, and the
    # late interactive both displaces a queued batch and preempts the runner
    for sid in ("s0", "s1", "s2"):
        fleet.router._sessions[sid] = (0, fleet.router._time())

    async def drive():
        first = asyncio.create_task(fleet.generate(
            [3, 1, 4, 1, 5], 32, session_id="s0", priority="batch",
            request_id="req-head"))
        await asyncio.sleep(0.15)  # head admitted and decoding
        flood = [
            asyncio.create_task(fleet.generate(
                [2, 7, 1], 8, session_id="s1", priority="batch",
                request_id=f"req-b{i}"))
            for i in range(5)
        ]
        await asyncio.sleep(0.05)  # queue holds 3, overflow shed
        vip = asyncio.create_task(fleet.generate(
            [6, 2], 6, session_id="s2", priority="interactive",
            request_id="req-vip"))
        return await asyncio.gather(first, *flood, vip, return_exceptions=True)

    try:
        results = asyncio.run(drive())
        live_sched = [r.batcher.scheduler.stats() for r in fleet._replicas]
        live_slo = tel.slo.totals()
        live_ok = int(tel.requests_total.value("ok"))
        live_shed = int(tel.requests_total.value("shed"))
    finally:
        fleet.close()
    assert any(isinstance(r, Exception) for r in results)  # the overflow shed
    assert any(isinstance(r, list) for r in results)

    records = load_journal(str(path))
    replay = replay_journal(records)
    assert all(r.version == JOURNAL_SCHEMA_VERSION for r in records)
    assert replay["records"] == len(results)
    # the contended mix actually exercised the policies being replayed
    assert replay["shed"].get("queue_full", 0) >= 1
    assert replay["preemptions"] >= 1 and replay["resumes"] >= 1
    # --- exact equality: journal-derived vs live counters ---
    assert replay["status"].get("ok", 0) == live_ok
    assert sum(replay["shed"].values()) == live_shed
    # the scheduler's queue_full counter folds in displacement sheds; the
    # journal keeps the reasons distinct ("displaced" carries more blame)
    assert replay["shed"].get("queue_full", 0) + replay["shed"].get(
        "displaced", 0) == sum(s["shed_queue_full"] for s in live_sched)
    assert replay["preemptions"] == sum(s["preemptions"] for s in live_sched)
    assert replay["resumes"] == sum(s["resumes"] for s in live_sched)
    assert replay["deadline_misses_queued"] == sum(
        s["deadline_misses_queued"] for s in live_sched)
    assert replay["deadline_misses_running"] == sum(
        s["deadline_misses_running"] for s in live_sched)
    assert replay["failover_adoptions"] == 0
    assert replay["slo_totals"] == live_slo
    # v2 block arithmetic is internally consistent on every admitted record
    assert replay["block_demand_violations"] == 0
    admitted = [r for r in records if r.first_span("admitted")]
    assert admitted and all(r.block_demand is not None for r in admitted)
    # session ids journaled at the top level (v2) for every request
    assert {r.session_id for r in records} <= {"s0", "s1", "s2"}
