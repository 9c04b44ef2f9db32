"""Telemetry subsystem: span traces, metrics registry, and the event journal.

Tier-1 gate for ISSUE 11 (serving observability). The contract pinned here:

- **Metrics.** ``log_buckets`` geometry, cumulative histogram bucket math at
  the boundary (``v <= bound``), the implicit ``+Inf`` bucket, and a golden
  Prometheus text exposition (format 0.0.4) — rendered without any client
  library, so the exact line shapes ARE the API.
- **Traces.** A request's trace opens at admission, survives preemption,
  quarantine-of-siblings, engine death, and fleet failover, and ends exactly
  once with a terminal status; aggregates (TTFT/ITL) derive from the decode
  stamps the engine already takes. Unknown ids never raise (recording must
  never take down serving) and the per-trace span cap drops, not grows.
- **Zero-cost hooks.** A telemetry-ENABLED engine's steady-state decode stays
  ``jax.transfer_guard`` clean: the per-burst hooks piggyback on the fused
  deferred fetch's existing host stamps, paying zero new host↔device syncs —
  the same fence ``test_pipeline_parity`` pins for the disabled path.
- **Failover continuity.** A replica death mid-decode leaves ONE trace per
  request: the fleet's ``route`` span, the doomed replica's admission and
  prefill spans, the ``failover_adopt`` hand-off, and the adoptive replica's
  suffix prefill + decode all land under the same ``request_id``.
"""

import asyncio
import json
import time

import jax
import numpy as np
import pytest

from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
from unionml_tpu.serving.faults import EngineFailure, FaultPlan
from unionml_tpu.serving.fleet import EngineFleet
from unionml_tpu.serving.metrics import MetricsRegistry, log_buckets
from unionml_tpu.serving.telemetry import JOURNAL_SCHEMA_VERSION, Telemetry


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


@pytest.fixture(autouse=True)
def _balanced_traces(monkeypatch):
    """Every Telemetry a test creates must leave a balanced ring behind.

    The dynamic twin of graftlint's static ``trace`` resource rule: at
    teardown, each completed trace holds exactly one terminal ``end`` span
    (``allow_active`` tolerates traces a test deliberately leaves open).
    """
    created = []
    orig_init = Telemetry.__init__

    def _recording_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        created.append(self)

    monkeypatch.setattr(Telemetry, "__init__", _recording_init)
    yield
    for tel in created:
        tel.assert_balanced(allow_active=True)


def _engine(model, variables, faults=None, telemetry=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("prefix_cache_blocks", 64)
    kw.setdefault("prefix_block_size", 4)
    return DecodeEngine(model, variables, faults=faults, telemetry=telemetry, **kw)


def _supervisor(**kw):
    from unionml_tpu.serving.supervisor import EngineSupervisor

    kw.setdefault("watchdog_interval_s", 0)
    kw.setdefault("backoff_s", 0.005)
    kw.setdefault("backoff_max_s", 0.02)
    return EngineSupervisor(**kw)


PROMPT_A, BUDGET_A = [3, 1, 4, 1, 5], 12
PROMPT_B, BUDGET_B = [2, 7, 1], 10


# ------------------------------------------------------------------- metrics


def test_log_buckets_geometry_and_validation():
    bounds = log_buckets(0.25, 2.0, 17)
    assert len(bounds) == 17
    assert bounds[0] == 0.25
    for lo, hi in zip(bounds, bounds[1:]):
        assert hi == pytest.approx(lo * 2.0)
    # 0.25 ms .. ~16 s covers the whole serving latency range
    assert bounds[-1] == pytest.approx(0.25 * 2.0**16)
    for bad in [(0.0, 2.0, 4), (1.0, 1.0, 4), (1.0, 2.0, 0)]:
        with pytest.raises(ValueError):
            log_buckets(*bad)


def test_histogram_bucket_math_and_boundaries():
    reg = MetricsRegistry()
    h = reg.histogram("t_ms", "test", (1.0, 2.0, 4.0))
    # boundary semantics are Prometheus's: a value equal to a bound lands in
    # that bucket (le = less-or-equal)
    for v in [0.5, 1.0, 1.5, 2.0, 4.0, 100.0]:
        h.observe(v)
    snap = h._snapshot()
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(109.0)
    text = reg.render()
    assert 't_ms_bucket{le="1"} 2' in text  # 0.5, 1.0
    assert 't_ms_bucket{le="2"} 4' in text  # + 1.5, 2.0 (cumulative)
    assert 't_ms_bucket{le="4"} 5' in text  # + 4.0
    assert 't_ms_bucket{le="+Inf"} 6' in text  # + 100.0
    assert "t_ms_count 6" in text
    with pytest.raises(ValueError):
        reg.histogram("dup_bounds", "test", (1.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("no_bounds", "test", ())


def test_prometheus_exposition_golden():
    """The exact text-format payload /metrics serves — families sorted by
    name, HELP+TYPE headers, labeled children sorted, histogram cumulative
    buckets then _sum/_count. A renderer change breaks scrapers; pin it."""
    reg = MetricsRegistry()
    c = reg.counter("app_requests_total", "Requests by outcome", ("outcome",))
    c.inc(2.0, "ok")
    c.inc(1.0, "error")
    g = reg.gauge("app_active", "In-flight requests")
    g.set(3)
    h = reg.histogram("app_wait_ms", "Queue wait", (1.0, 10.0), ("cls",))
    h.observe(0.5, "interactive")
    h.observe(25.0, "interactive")
    assert reg.render() == (
        "# HELP app_active In-flight requests\n"
        "# TYPE app_active gauge\n"
        "app_active 3\n"
        "# HELP app_requests_total Requests by outcome\n"
        "# TYPE app_requests_total counter\n"
        'app_requests_total{outcome="error"} 1\n'
        'app_requests_total{outcome="ok"} 2\n'
        "# HELP app_wait_ms Queue wait\n"
        "# TYPE app_wait_ms histogram\n"
        'app_wait_ms_bucket{cls="interactive",le="1"} 1\n'
        'app_wait_ms_bucket{cls="interactive",le="10"} 1\n'
        'app_wait_ms_bucket{cls="interactive",le="+Inf"} 2\n'
        'app_wait_ms_sum{cls="interactive"} 25.5\n'
        'app_wait_ms_count{cls="interactive"} 2\n'
    )


def test_registry_families_are_idempotent_with_type_checks():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", ("k",))
    assert reg.counter("x_total", "x", ("k",)) is a  # modules declare independently
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x", ("k",))  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("other",))  # label conflict
    with pytest.raises(ValueError):
        a.inc(1.0)  # missing label value


# -------------------------------------------------------------------- traces


def test_trace_lifecycle_and_latency_aggregates():
    tel = Telemetry()
    rid = tel.new_trace(cls="interactive")
    tel.span(rid, "admission", prompt_tokens=5)
    tel.note_tokens_in(rid, 5)
    # decode stamps are the fetch's own perf_counter values: feed controlled
    # ones so TTFT/ITL are deterministic
    t = time.perf_counter()
    tel.decode_tokens(rid, 1, at=t, block_ms=0.8)
    tel.decode_tokens(rid, 3, at=t + 0.030, block_ms=0.9)
    tel.end_trace(rid, "ok")
    trace = tel.get_trace(rid)
    assert trace["v"] == JOURNAL_SCHEMA_VERSION
    assert trace["status"] == "ok" and trace["class"] == "interactive"
    assert trace["tokens_in"] == 5 and trace["tokens_out"] == 4
    assert trace["decode_bursts"] == 2
    # ITL spreads the burst gap over the 3 post-first tokens: 30ms / 3
    assert trace["itl_ms"] == pytest.approx(10.0, abs=0.01)
    kinds = [s["kind"] for s in trace["spans"]]
    assert kinds == ["admission", "decode", "end"]
    decode = trace["spans"][1]
    assert decode["attrs"] == {"tokens": 4, "bursts": 2}
    assert decode["dur_ms"] == pytest.approx(30.0, abs=0.5)
    assert trace["spans"][-1]["attrs"]["status"] == "ok"
    # the ended trace moved to the ring; aggregates mirrored into metrics
    assert tel.stats()["active_traces"] == 0
    assert tel.stats()["completed_traces"] == 1
    assert tel.requests_total.value("ok") == 1.0
    assert tel.tokens_out_total.value() == 4.0
    assert tel.decode_fetch_ms._snapshot()["count"] == 2
    assert tel.itl_ms._snapshot()["interactive"]["count"] == 1


def test_unknown_ids_never_raise_and_span_cap_drops():
    tel = Telemetry(max_spans=3)
    # recording against unknown/ended ids is a designed no-op
    tel.span("nope", "admission")
    tel.decode_tokens("nope", 1)
    tel.end_trace("nope")
    assert tel.stats()["completed_traces"] == 0
    rid = tel.new_trace()
    for i in range(5):
        tel.span(rid, "prefill_chunk", i=i)
    tel.end_trace(rid, "ok")
    trace = tel.get_trace(rid)
    # 3 kept + the synthesized end marker; 2 dropped and counted
    assert [s["kind"] for s in trace["spans"]] == ["prefill_chunk"] * 3 + ["end"]
    assert trace["attrs"]["spans_dropped"] == 2
    assert tel.stats()["spans_dropped"] == 2


def test_new_trace_is_idempotent_join_for_failover():
    tel = Telemetry()
    rid = tel.new_trace("abc123", cls="interactive")
    assert rid == "abc123"
    tel.span(rid, "route", replica=0)
    # the replica batcher re-opens the same id on adoption: same trace
    assert tel.new_trace("abc123") == "abc123"
    tel.span(rid, "admission")
    assert tel.stats()["active_traces"] == 1
    tel.end_trace(rid, "ok")
    assert [s["kind"] for s in tel.get_trace(rid)["spans"]] == ["route", "admission", "end"]


def test_ring_bounds_and_recent_order():
    tel = Telemetry(journal_size=2)
    for name in ("r1", "r2", "r3"):
        tel.new_trace(name)
        tel.end_trace(name, "ok")
    recent = tel.recent()
    assert [t["request_id"] for t in recent] == ["r2", "r3"]  # newest last
    assert tel.get_trace("r1") is None  # evicted from the ring
    assert tel.stats()["completed_traces"] == 3  # counter outlives the ring


def test_journal_jsonl_sink_schema_v2(tmp_path):
    path = tmp_path / "journal.jsonl"
    tel = Telemetry(journal_path=str(path))
    for name, status, reason in [("ra", "ok", None), ("rb", "shed", "queue_full")]:
        tel.new_trace(name, session_id="sess-7" if name == "ra" else None)
        tel.span(name, "admission", block_demand=4, available_blocks=64)
        tel.note_tokens_in(name, 4)
        tel.end_trace(name, status, reason=reason)
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    records = [json.loads(line) for line in lines]
    for rec in records:
        assert rec["v"] == JOURNAL_SCHEMA_VERSION == 2
        assert set(rec) >= {
            "request_id", "created_unix", "class", "status",
            "tokens_in", "tokens_out", "decode_bursts", "spans",
        }
        # v2: the admission span journals the pool arithmetic the batcher
        # gated on, so a simulator replay needs no side channels
        admission = next(s for s in rec["spans"] if s["kind"] == "admission")
        assert admission["attrs"]["block_demand"] == 4
        assert admission["attrs"]["available_blocks"] == 64
    assert records[0]["request_id"] == "ra" and records[0]["status"] == "ok"
    # v2: session id lands top-level AND on the admission span (the replay
    # loader reads either); a sessionless request journals neither
    assert records[0]["session_id"] == "sess-7"
    admission = next(s for s in records[0]["spans"] if s["kind"] == "admission")
    assert admission["attrs"]["session_id"] == "sess-7"
    assert "session_id" not in records[1]
    assert records[1]["status"] == "shed" and records[1]["reason"] == "queue_full"


# ------------------------------------------------------- engine integration


def test_batcher_end_to_end_trace_and_metrics(gpt, gpt_tiny_solo):
    """One traced request through the full solo stack: the span tree covers
    admission → queue wait → prefill → decode → end, aggregates land in the
    shared registry, and the Prometheus render carries the headline series."""
    model, variables = gpt
    tel = Telemetry()
    batcher = ContinuousBatcher(_engine(model, variables), telemetry=tel)
    try:
        out = asyncio.run(batcher.generate(PROMPT_A, BUDGET_A, request_id="req-e2e"))
    finally:
        batcher.close()
    assert out == gpt_tiny_solo(PROMPT_A, BUDGET_A)
    trace = tel.get_trace("req-e2e")
    assert trace["status"] == "ok"
    assert trace["tokens_in"] == len(PROMPT_A) and trace["tokens_out"] == BUDGET_A
    kinds = [s["kind"] for s in trace["spans"]]
    assert kinds[0] == "admission" and kinds[-1] == "end"
    for required in ("queue_wait", "prefill", "admitted", "decode"):
        assert required in kinds, f"missing {required} in {kinds}"
    assert kinds.index("queue_wait") < kinds.index("prefill") < kinds.index("decode")
    assert trace["ttft_ms"] > 0 and trace["decode_bursts"] >= 1
    assert tel.requests_total.value("ok") == 1.0
    assert tel.tokens_out_total.value() == float(BUDGET_A)
    assert tel.prefill_tokens_total.value() >= float(len(PROMPT_A))
    text = tel.metrics.render()
    assert "# TYPE unionml_requests_total counter" in text
    assert "# TYPE unionml_ttft_ms histogram" in text
    assert 'unionml_requests_total{outcome="ok"} 1' in text
    assert "unionml_decode_fetch_ms_bucket" in text
    # SLO surface (ISSUE 15): one on-time ok request -> full attainment,
    # zero burn in every configured window — golden exposition lines
    assert "# TYPE unionml_slo_attainment gauge" in text
    assert 'unionml_slo_attainment{cls="standard"} 1' in text
    assert 'unionml_slo_burn_rate{cls="standard",window="5m"} 0' in text
    assert 'unionml_slo_burn_rate{cls="standard",window="1h"} 0' in text


def test_decode_with_telemetry_is_transfer_guard_clean(gpt):
    """ISSUE-11 acceptance: the per-burst telemetry hooks ride the fused
    deferred fetch's existing host stamps — a telemetry-ENABLED engine's
    steady state pays the same zero host→device transfers the disabled path
    pins in test_pipeline_parity, for both depth-1 and fused bursts."""
    model, variables = gpt
    tel = Telemetry()
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64,
                          prefill_buckets=(8,), pipeline=True, telemetry=tel)
    engine.admit_many([([3, 1, 4, 1, 5], 30), ([2, 7], 30)])
    engine.step()  # compile + warm the depth-1 program
    engine.step()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            engine.step()
    engine.step(4)  # compile the fused-burst program outside the guard
    with jax.transfer_guard_host_to_device("disallow"):
        engine.step(4)
    # the hooks actually fired under the guard (this isn't testing a no-op)
    assert tel.decode_fetch_ms._snapshot()["count"] >= 4
    assert tel.tokens_out_total.value() > 0


def test_quarantine_trace_is_terminal_with_reason(gpt):
    """A NaN-quarantined request's trace ends with status=error and carries
    the quarantine span; the surviving sibling's trace stays clean."""
    model, variables = gpt
    tel = Telemetry()
    engine = _engine(model, variables, faults=FaultPlan(nan_logits=((5, 0),)),
                     telemetry=tel)
    batcher = ContinuousBatcher(engine, supervisor=_supervisor())

    async def main():
        return await asyncio.gather(
            batcher.generate(PROMPT_A, BUDGET_A),
            batcher.generate(PROMPT_B, BUDGET_B),
            return_exceptions=True,
        )

    try:
        results = asyncio.run(main())
    finally:
        batcher.close()
    failed = [r for r in results if isinstance(r, EngineFailure)]
    assert len(failed) == 1 and failed[0].reason == "nan_logits"
    assert tel.stats()["completed_traces"] == 2
    by_status = {t["status"]: t for t in tel.recent()}
    errored = by_status["error"]
    assert errored["reason"] == "nan_logits"
    kinds = [s["kind"] for s in errored["spans"]]
    assert "quarantine" in kinds and kinds[-1] == "end"
    assert "quarantine" not in [s["kind"] for s in by_status["ok"]["spans"]]
    assert tel.quarantines_total.value() == 1.0
    assert tel.requests_total.value("error") == 1.0
    assert tel.requests_total.value("ok") == 1.0


def test_fleet_failover_keeps_one_trace_per_request(gpt, gpt_tiny_solo):
    """ISSUE-11 acceptance: replica 0 dies mid-decode with both requests
    pinned to it; each request finishes token-identical on replica 1 under
    ONE request_id whose span tree shows the whole story — route to the
    doomed replica, its admission+prefill, the failover adoption, and the
    adoptive replica's suffix prefill feeding the same decode aggregate."""
    model, variables = gpt
    tel = Telemetry()
    engines = [
        _engine(model, variables,
                faults=FaultPlan(step_dispatch_failures=(4,), rebuild_failures=99)),
        _engine(model, variables),
    ]
    fleet = EngineFleet(
        engines,
        supervisors=[_supervisor(max_rebuild_attempts=2), _supervisor()],
        telemetry=tel,
    )
    fleet.router._sessions["a"] = (0, fleet.router._time())
    fleet.router._sessions["b"] = (0, fleet.router._time())

    async def main():
        return await asyncio.gather(
            fleet.generate(PROMPT_A, BUDGET_A, session_id="a", request_id="req-a"),
            fleet.generate(PROMPT_B, BUDGET_B, session_id="b", request_id="req-b"),
        )

    try:
        results = asyncio.run(main())
    finally:
        fleet.close()
    assert results == [gpt_tiny_solo(PROMPT_A, BUDGET_A), gpt_tiny_solo(PROMPT_B, BUDGET_B)]
    assert tel.stats()["completed_traces"] == 2 and tel.stats()["active_traces"] == 0
    for rid in ("req-a", "req-b"):
        trace = tel.get_trace(rid)
        assert trace["status"] == "ok"
        kinds = [s["kind"] for s in trace["spans"]]
        assert kinds[0] == "route" and kinds[-1] == "end"
        assert "failover_adopt" in kinds
        route = next(s for s in trace["spans"] if s["kind"] == "route")
        adopt = next(s for s in trace["spans"] if s["kind"] == "failover_adopt")
        assert route["attrs"]["replica"] == 0  # pinned to the doomed replica
        assert adopt["attrs"]["from_replica"] == 0 and adopt["attrs"]["to_replica"] == 1
        # the adoptive replica pays a (suffix) prefill after the adoption
        assert kinds.index("failover_adopt") < len(kinds) - 1
        assert kinds.count("prefill") >= 2  # replica 0's, then replica 1's
    assert tel.failover_adoptions_total.value() == 2.0
    assert tel.engine_failures_total._snapshot()  # classified reason recorded
    assert "unionml_failover_adoptions_total 2" in tel.metrics.render()


def test_http_metrics_trace_and_request_id_echo(gpt):
    """ISSUE-11 acceptance over HTTP: /generate echoes the route-minted
    request_id, /metrics serves valid Prometheus text (0.0.4 content type),
    /trace/{request_id} returns the completed span tree, /traces/recent lists
    it, and /stats carries the shared telemetry block. A 404 for an unknown
    trace rides the unified error envelope with its own request_id."""
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="obs-app", artifact=object())
    app = build_aiohttp_app(
        stub, resident=False, coalesce=False,
        generator=lambda: _engine(model, variables),
        generate_drain_s=2.0,
    )

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate", json={"prompt_ids": PROMPT_A, "max_new_tokens": 6}
            )
            assert resp.status == 200, await resp.text()
            body = await resp.json()
            rid = body["request_id"]
            assert len(body["tokens"]) == 6 and rid

            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
            text = await resp.text()
            assert "# TYPE unionml_requests_total counter" in text
            assert 'unionml_requests_total{outcome="ok"} 1' in text
            assert "unionml_ttft_ms_bucket" in text
            assert 'unionml_slo_attainment{cls="standard"}' in text

            trace = await (await client.get(f"/trace/{rid}")).json()
            assert trace["request_id"] == rid and trace["status"] == "ok"
            kinds = [s["kind"] for s in trace["spans"]]
            assert kinds[0] == "admission" and kinds[-1] == "end"

            recent = await (await client.get("/traces/recent?n=5")).json()
            assert [t["request_id"] for t in recent["traces"]] == [rid]

            stats = await (await client.get("/stats")).json()
            assert stats["telemetry"]["completed_traces"] == 1
            assert stats["telemetry"]["metrics"]["unionml_tokens_out_total"] == 6.0
            # generation.slo: the per-class attainment + burn-rate report,
            # identical solo/fleet (same SLOTracker behind /metrics gauges)
            slo = stats["generation"]["slo"]
            assert set(slo) == {"windows", "alert_burn", "per_class", "alerts"}
            standard = slo["per_class"]["standard"]
            assert standard["total"] == 1
            assert set(standard["windows"]) == set(slo["windows"])

            resp = await client.get("/trace/deadbeef00000000")
            assert resp.status == 404
            envelope = (await resp.json())["error"]
            assert envelope["reason"] == "trace_not_found"
            assert envelope["request_id"] == "deadbeef00000000"
        finally:
            await client.close()

    asyncio.run(main())


def test_stream_trailer_carries_the_request_id(gpt):
    """The stream's ``done`` line names the request like every other response,
    so a client can join its own stamps to ``GET /trace/{request_id}``."""
    import json
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="obs-app", artifact=object())
    app = build_aiohttp_app(
        stub, resident=False, coalesce=False,
        generator=lambda: _engine(model, variables), generate_drain_s=2.0,
    )

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate", json={"prompt_ids": PROMPT_A, "max_new_tokens": 4, "stream": True}
            )
            assert resp.status == 200, await resp.text()
            lines = [json.loads(line) for line in (await resp.text()).strip().splitlines()]
            trailer = lines[-1]
            assert trailer["done"] is True and len(trailer["tokens"]) == 4
            trace = await (await client.get(f"/trace/{trailer['request_id']}")).json()
            assert trace["request_id"] == trailer["request_id"] and trace["status"] == "ok"
            kinds = [s["kind"] for s in trace["spans"]]
            assert kinds[0] == "admission" and "decode" in kinds and kinds[-1] == "end"
        finally:
            await client.close()

    asyncio.run(main())


def test_engine_recovery_trace_has_salvage_span(gpt, gpt_tiny_solo):
    """A recoverable engine failure (rebuild succeeds) keeps the trace OPEN
    across the death: the salvaged span marks the checkpoint and the request
    still ends ok with full token parity."""
    model, variables = gpt
    tel = Telemetry()
    engine = _engine(model, variables, faults=FaultPlan(step_dispatch_failures=(4,)),
                     telemetry=tel)
    batcher = ContinuousBatcher(engine, supervisor=_supervisor())

    async def main():
        return await asyncio.gather(
            batcher.generate(PROMPT_A, BUDGET_A, request_id="req-salvage"),
            batcher.generate(PROMPT_B, BUDGET_B),
        )

    try:
        results = asyncio.run(main())
    finally:
        batcher.close()
    assert results == [gpt_tiny_solo(PROMPT_A, BUDGET_A), gpt_tiny_solo(PROMPT_B, BUDGET_B)]
    trace = tel.get_trace("req-salvage")
    assert trace["status"] == "ok" and trace["tokens_out"] == BUDGET_A
    kinds = [s["kind"] for s in trace["spans"]]
    assert "salvaged" in kinds
    assert kinds.index("salvaged") < kinds.index("decode")  # resumed, then decoded
    assert tel.rebuilds_total.value() >= 1.0
    assert tel.resumes_total.value() >= 1.0
