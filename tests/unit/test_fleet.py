"""Fleet serving tier: replicated engines behind the prefix-affinity router.

Tier-1 gate for ISSUE 9 (EngineFleet + Router + health-aware failover). The
contract pinned here:

- **Parity.** A 2-replica fleet on a split CPU mesh serves a fixed greedy
  request stream token-identical to a single engine serving the same
  prompts. Sampled parity is pinned at the strongest level the engine's PRNG
  contract allows: the engine advances ONE global key per any-active step,
  so a sampled stream is schedule-dependent — splitting a stream across two
  engines necessarily re-times each engine's key advances relative to a
  single engine serving everything. What the fleet layer CAN guarantee (and
  this suite pins bit-exactly) is that it is numerics-transparent: a
  1-replica fleet reproduces a bare supervised batcher's sampled streams,
  and each replica of a 2-replica fleet reproduces a fresh solo engine
  serving that replica's routed sub-stream.
- **Routing.** Prefix affinity beats the seeded-random baseline on a
  prefix-heavy mix (router-measured block hit rate); sessions stick, TTL- and
  capacity-evict, and fall back to the affinity winner when their replica is
  unroutable (re-sticking there).
- **Failover.** A replica whose rebuild budget exhausts hands every
  salvageable ticket to the fleet, which re-routes them to survivors —
  outputs stay token-identical, zero pinned blocks leak on ANY engine, and
  a mid-session death re-routes the session's next turn to the adoptive
  replica where it pays only a suffix prefill.
- **Shedding + HTTP.** The fleet-level queue bound sheds with the PR-5
  error contract BEFORE any replica queue is touched; ``/healthz`` and
  ``/stats`` expose per-replica state; the Retry-After jitter is seedable.
"""

import asyncio
import random

import jax
import numpy as np
import pytest

from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
from unionml_tpu.serving.faults import EngineFailure, FaultPlan
from unionml_tpu.serving.fleet import EngineFleet, FleetConfig, Router, split_mesh
from unionml_tpu.serving.prefix_cache import PrefixCache, block_key, prefix_digests
from unionml_tpu.serving.scheduler import (
    DeadlineInfeasibleError,
    QueueFullError,
    SLOScheduler,
)


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def _engine(model, variables, mesh=None, faults=None, cache=True, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    if cache:
        kw.setdefault("prefix_cache_blocks", 64)
        kw.setdefault("prefix_block_size", 4)
    return DecodeEngine(model, variables, mesh=mesh, faults=faults, **kw)


def _supervisor(**kw):
    from unionml_tpu.serving.supervisor import EngineSupervisor

    kw.setdefault("watchdog_interval_s", 0)  # tests drive check() synchronously
    kw.setdefault("backoff_s", 0.005)
    kw.setdefault("backoff_max_s", 0.02)
    return EngineSupervisor(**kw)


def _assert_no_pins_or_refs(engine):
    if engine.prefix_cache is None:
        return
    assert engine.prefix_cache.pinned_blocks == 0
    stack = list(engine.prefix_cache._root.children.values())
    while stack:
        node = stack.pop()
        assert node.refcount == 0, "leaked prefix-cache reference"
        stack.extend(node.children.values())


def _fleet_no_leaks(fleet):
    for rep in fleet.replicas:
        _assert_no_pins_or_refs(rep.engine)


def _recorder():
    class Sink:
        cancelled = False

        def __init__(self):
            self.tokens, self.done, self.error = [], False, None

        def emit(self, token):
            self.tokens.append(token)

        def finish(self):
            self.done = True

        def fail(self, exc):
            self.error = exc

    return Sink()


PROMPT_A, BUDGET_A = [3, 1, 4, 1, 5], 12
PROMPT_B, BUDGET_B = [2, 7, 1], 10


# ------------------------------------------------------ shared prefix hashing


def test_block_key_matches_prefix_cache_keys():
    """The router digests over the SAME block keys the radix tree uses: the
    shared helper and the cache's internal keying must never diverge, or
    affinity would route against phantom prefixes."""
    tokens = np.asarray(list(range(1, 20)), dtype=np.int32)
    cache = PrefixCache(num_blocks=8, block_size=4)
    for i in range(len(tokens) // 4):
        assert block_key(tokens, i, 4) == cache._key_at(tokens, i)


def test_prefix_digests_chain_and_determinism():
    digests = prefix_digests([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    assert len(digests) == 2  # two full blocks; the ragged tail has no digest
    # chained: an extended prompt shares the shorter prompt's digests exactly
    longer = prefix_digests([1, 2, 3, 4, 5, 6, 7, 8, 50, 51, 52, 53], 4)
    assert longer[:2] == digests and len(longer) == 3
    # any token change anywhere in a block flips that digest and all later ones
    mutated = prefix_digests([1, 2, 3, 99, 5, 6, 7, 8, 9], 4)
    assert mutated[0] != digests[0] and mutated[1] != digests[1]
    # deterministic across calls (FNV, not PYTHONHASHSEED-dependent hash())
    assert prefix_digests([1, 2, 3, 4, 5, 6, 7, 8, 9], 4) == digests
    assert prefix_digests([1, 2, 3], 4) == []  # sub-block prompt: no affinity
    assert prefix_digests([1, 2, 3, 4, 5, 6, 7, 8], 4, max_blocks=1) == digests[:1]


# -------------------------------------------------------------- router units

CANDS2 = [(0, 1.0, 0.0), (1, 1.0, 0.0)]


def test_router_affinity_beats_random_on_prefix_heavy_mix():
    """The A/B the fleet exists for: on a shared-prefix workload, affinity
    routing's block hit rate (measured identically for both arms, on the
    chosen replica) beats seeded-random routing. Load feedback is simulated
    so affinity must win through the full scoring formula, not a degenerate
    everything-on-replica-0 tie-break."""
    groups = [[g * 10 + k for k in range(8)] for g in range(3)]  # 2-block prefixes
    prompts = []
    for j in range(6):
        for g, prefix in enumerate(groups):
            prompts.append(prefix + [100 * (g + 1) + j] * 4)  # unique last block

    def run(policy):
        router = Router(2, block_size=4, config=FleetConfig(policy=policy, seed=0))
        assigned = [0, 0]
        for prompt in prompts:
            cands = [(i, 1.0, 0.5 * assigned[i]) for i in range(2)]
            chosen, _ = router.route(prompt, cands)
            assigned[chosen] += 1
        return router.stats()

    affinity, rnd = run("affinity"), run("random")
    assert affinity["prefix_hit_rate"] > rnd["prefix_hit_rate"]
    assert affinity["affinity_routes"] == len(prompts)
    assert rnd["random_routes"] == len(prompts)
    # both arms measured the same lookups — the comparison is like-for-like
    assert affinity["lookup_blocks"] == rnd["lookup_blocks"] > 0


def test_router_load_breaks_ties_and_downranks_busy_replicas():
    router = Router(2, block_size=4)
    # no digests anywhere: equal scores tie-break to the less-loaded replica
    chosen, how = router.route([1, 2, 3, 4], [(0, 1.0, 3.0), (1, 1.0, 0.0)])
    assert chosen == 1 and how["decision"] == "affinity"
    # a strong enough match overcomes moderate load
    chosen, how = router.route([1, 2, 3, 4], [(0, 1.0, 0.2), (1, 1.0, 0.0)])
    assert chosen == 1  # digests were recorded on 1 by the first route
    assert how["matched_blocks"] == 1


def test_router_session_sticks_then_ttl_expires():
    clock = {"t": 0.0}
    config = FleetConfig(session_ttl_s=10.0, max_sessions=2)
    router = Router(2, block_size=4, config=config, time_fn=lambda: clock["t"])
    chosen, _ = router.route([1, 2, 3, 4], CANDS2, session_id="s1")
    assert router.session_replica("s1") == chosen
    # sticks even when the other replica now looks strictly better
    clock["t"] = 5.0
    again, how = router.route(
        [9, 9, 9, 9], [(0, 1.0, 9.0), (1, 1.0, 9.0)], session_id="s1"
    )
    assert again == chosen and how["decision"] == "sticky"
    assert router.stats()["sticky_routes"] == 1
    # idle past the TTL: the mapping is gone and the next turn re-scores
    clock["t"] = 20.1
    router.route([2, 2, 2, 2], CANDS2, session_id="other")
    assert router.session_replica("s1") is None
    assert router.stats()["sessions_evicted"] == 1
    # capacity: the least-recently-routed session is evicted first
    router.route([3, 3, 3, 3], CANDS2, session_id="s2")
    router.route([4, 4, 4, 4], CANDS2, session_id="s3")
    assert router.session_replica("other") is None
    assert router.stats()["sessions_active"] == 2


def test_router_dead_session_falls_back_to_affinity_winner_and_resticks():
    router = Router(3, block_size=4)
    prompt = [5, 5, 5, 5, 6, 6, 6, 6]
    # session lands on replica 0; replica 2 independently holds the prefix
    assert router.route(prompt, [(0, 1.0, 0.0)], session_id="s")[0] == 0
    assert router.route(prompt, [(2, 1.0, 0.0)])[0] == 2
    # replica 0 rebuilding: digests cleared, sessions kept, route() excludes it
    router.on_replica_rebuilding(0)
    assert router.session_replica("s") == 0
    chosen, how = router.route(
        prompt, [(1, 1.0, 0.0), (2, 1.0, 0.0)], session_id="s"
    )
    assert chosen == 2 and how["decision"] == "affinity"  # fell back to the match
    assert how["matched_blocks"] == 2
    assert router.stats()["dead_session_fallbacks"] == 1
    assert router.session_replica("s") == 2  # re-stuck on the adoptive replica
    # terminal failure drops ONLY the dead replica's sessions
    router.route([7, 7, 7, 7], [(1, 1.0, 0.0)], session_id="on1")
    router.on_replica_failed(1)
    assert router.session_replica("on1") is None
    assert router.session_replica("s") == 2
    assert router.stats()["indexed_blocks"][1] == 0


# ------------------------------------------------------- per-class queue EMAs


def test_scheduler_per_class_ema_isolates_infeasible_estimate():
    """An interactive deadline is judged against INTERACTIVE queueing history,
    not the global EMA a burst of batch work inflated — the per-class signal
    the fleet router also consumes via load_signal()."""
    sched = SLOScheduler()
    fast = sched.make_ticket([1], 4, {}, _recorder(), priority="interactive", now=0.0)
    sched.submit(fast, now=0.0)
    assert sched.pop(1, now=0.01) == [fast]  # interactive EMA ~10ms
    slow = sched.make_ticket([1], 4, {}, _recorder(), priority="batch", now=1.0)
    sched.submit(slow, now=1.0)
    assert sched.pop(1, now=11.0) == [slow]  # batch EMA 10_000ms
    signal = sched.load_signal()
    assert signal["per_class"]["interactive"] == pytest.approx(10.0)
    assert signal["per_class"]["batch"] == pytest.approx(10_000.0)
    assert signal["queue_wait_ema_ms"] > 500  # global-only would shed below
    ok = sched.make_ticket(
        [1], 4, {}, _recorder(), priority="interactive", deadline_ms=500, now=20.0
    )
    sched.submit(ok, now=20.0)  # accepted: its own class waits ~10ms
    assert sched.remove(ok)
    doomed = sched.make_ticket(
        [1], 4, {}, _recorder(), priority="batch", deadline_ms=500, now=20.0
    )
    with pytest.raises(DeadlineInfeasibleError):
        sched.submit(doomed, now=20.0)
    stats = sched.stats()
    assert stats["per_class"]["batch"] == pytest.approx(10_000.0)
    assert stats["per_class"]["standard"] is None  # never popped: no estimate
    assert stats["shed_deadline_infeasible"] == 1


def test_supervisor_subscription_swallows_subscriber_errors():
    sup = _supervisor()
    seen = []
    sup.subscribe(lambda old, new: (_ for _ in ()).throw(RuntimeError("boom")))
    sup.subscribe(lambda old, new: seen.append((old, new)))
    sup._notify("ok", "degraded")  # a raising subscriber never blocks the rest
    assert seen == [("ok", "degraded")]
    sup._notify("degraded", "degraded")  # no-op transitions don't fire
    assert seen == [("ok", "degraded")]


# ----------------------------------------------------------------- mesh split


def test_split_mesh_shapes_and_errors():
    from unionml_tpu.parallel import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces 8 CPU devices)")
    mesh = make_mesh({"data": 2, "tensor": 4})
    subs = split_mesh(mesh, 2)
    assert len(subs) == 2
    for sub in subs:
        assert tuple(sub.axis_names) == ("data", "tensor")
        assert dict(zip(sub.axis_names, np.asarray(sub.devices).shape)) == {
            "data": 1, "tensor": 4,
        }
    flat = [d for sub in subs for d in np.asarray(sub.devices).flat]
    assert sorted(d.id for d in flat) == sorted(d.id for d in np.asarray(mesh.devices).flat)
    # a single-axis mesh shrinks that axis
    tensor8 = make_mesh({"tensor": 8})
    assert [
        dict(zip(s.axis_names, np.asarray(s.devices).shape)) for s in split_mesh(tensor8, 2)
    ] == [{"tensor": 4}, {"tensor": 4}]
    with pytest.raises(ValueError):
        split_mesh(mesh, 3)  # 8 devices don't split 3 ways
    with pytest.raises(ValueError):
        # 4 devices split 4 ways, but no single axis of {data:2, tensor:2} is
        # divisible by 4 — the shape can't shrink along one axis
        split_mesh(make_mesh({"data": 2, "tensor": 2}, devices=jax.devices()[:4]), 4)


# ----------------------------------------------------------- serving parity


def test_fleet_greedy_parity_on_split_mesh(gpt, gpt_tiny_solo):
    """The acceptance headline: two sharded replicas, each on half of the
    8-CPU-device mesh, serve a fixed greedy stream token-identical to a
    single engine — and both replicas really served."""
    from unionml_tpu.parallel import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces 8 CPU devices)")
    model, variables = gpt
    subs = split_mesh(make_mesh({"data": 2, "tensor": 4}), 2)
    engines = [_engine(model, variables, mesh=sub) for sub in subs]
    fleet = EngineFleet(
        engines,
        config=FleetConfig(policy="round_robin"),
        supervisors=[_supervisor(), _supervisor()],
    )
    prompts = [PROMPT_A, PROMPT_B, [9, 9, 1, 2], [4, 4, 4]]

    async def main():
        out = []
        for i, prompt in enumerate(prompts):
            out.append(await fleet.generate(prompt, 6, session_id=f"s{i}"))
        return out

    try:
        results = asyncio.run(main())
    finally:
        fleet.close()
    assert results == [gpt_tiny_solo(p, 6) for p in prompts]
    assert all(e.prefill_dispatches > 0 for e in engines)  # both replicas served
    stats = fleet.stats()
    assert stats["fleet"]["requests_routed"] == 4
    assert stats["num_slots"] == 4 and stats["fleet"]["replicas"] == 2
    _fleet_no_leaks(fleet)


def test_single_replica_fleet_sampled_parity(gpt):
    """The fleet layer is numerics-transparent: a 1-replica fleet reproduces
    a bare supervised batcher's fixed-seed sampled streams bit-exactly (same
    admissions, same schedule, same per-step subkeys)."""
    model, variables = gpt

    def run(make_generator):
        gen, closer = make_generator()

        async def main():
            return await asyncio.gather(
                gen.generate(PROMPT_A, BUDGET_A, temperature=0.8),
                gen.generate(PROMPT_B, BUDGET_B, temperature=0.8),
            )

        try:
            return asyncio.run(main())
        finally:
            closer()

    def bare():
        batcher = ContinuousBatcher(
            _engine(model, variables, temperature=0.8, seed=7), supervisor=_supervisor()
        )
        return batcher, batcher.close

    def fleet():
        f = EngineFleet(
            [_engine(model, variables, temperature=0.8, seed=7)],
            supervisors=[_supervisor()],
        )
        return f, f.close

    assert run(fleet) == run(bare)


def test_fleet_sampled_parity_per_replica_substream(gpt):
    """Each replica of a 2-replica fleet reproduces a fresh solo engine
    serving its routed sub-stream bit-exactly under fixed-seed sampling.

    (A 2-replica fleet cannot be sampled-identical to ONE engine serving the
    whole stream: the engine PRNG advances one global key per any-active
    step, so sampling is schedule-dependent by design — the recovery suite
    pins that contract. Transparency per replica is the exact guarantee the
    fleet layer owes.)"""
    model, variables = gpt
    fleet = EngineFleet(
        [_engine(model, variables, temperature=0.8, seed=7) for _ in range(2)],
        config=FleetConfig(policy="round_robin"),
        supervisors=[_supervisor(), _supervisor()],
    )
    prompts = [PROMPT_A, PROMPT_B, [9, 9, 1, 2], [4, 4, 4]]
    routed = []
    orig_route = fleet._route

    def spy(prompt_ids, session_id=None):
        rep = orig_route(prompt_ids, session_id)
        routed.append(rep.index)
        return rep

    fleet._route = spy

    async def serve_fleet():
        out = []
        for prompt in prompts:
            out.append(await fleet.generate(prompt, 6, temperature=0.8))
        return out

    try:
        results = asyncio.run(serve_fleet())
    finally:
        fleet.close()
    assert sorted(set(routed)) == [0, 1]  # round_robin really used both

    for index in (0, 1):
        sub = [(p, r) for (p, rep) in zip(prompts, routed) for r in [rep] if rep == index]
        batcher = ContinuousBatcher(_engine(model, variables, temperature=0.8, seed=7))

        async def serve_solo():
            return [await batcher.generate(p, 6, temperature=0.8) for p, _ in sub]

        try:
            reference = asyncio.run(serve_solo())
        finally:
            batcher.close()
        assert [results[i] for i, r in enumerate(routed) if r == index] == reference
    _fleet_no_leaks(fleet)


# ------------------------------------------------------------------ shedding


def test_fleet_sheds_queue_full_before_touching_replica_queues(gpt):
    model, variables = gpt
    fleet = EngineFleet(
        [_engine(model, variables) for _ in range(2)],
        config=FleetConfig(max_queue=1, retry_after_s=2.5),
        supervisors=[_supervisor(), _supervisor()],
    )
    try:
        rep0 = fleet.replicas[0]
        ticket = rep0.batcher.scheduler.make_ticket(
            np.asarray(PROMPT_A, dtype=np.int32), 4, {}, _recorder()
        )
        rep0.batcher.scheduler.submit(ticket)  # one queued request fleet-wide
        with pytest.raises(QueueFullError) as shed:
            asyncio.run(fleet.generate(PROMPT_B, 4))
        assert shed.value.retry_after_s == 2.5
        # the shed never reached any replica's scheduler
        assert rep0.batcher.scheduler.submitted == 1
        assert fleet.replicas[1].batcher.scheduler.submitted == 0
        assert fleet.stats()["fleet"]["shed_queue_full"] == 1
        rep0.batcher.scheduler.drain()
        # every replica unroutable -> the structured retryable 503
        for rep in fleet.replicas:
            with rep.supervisor._lock:
                rep.supervisor._state = "failed"
        with pytest.raises(EngineFailure) as unavailable:
            asyncio.run(fleet.generate(PROMPT_B, 4))
        assert unavailable.value.reason == "fleet_unavailable"
        assert unavailable.value.retryable
        for rep in fleet.replicas:
            with rep.supervisor._lock:
                rep.supervisor._state = "ok"
    finally:
        fleet.close()
    with pytest.raises(EngineFailure) as closed:
        asyncio.run(fleet.generate(PROMPT_B, 4))
    assert closed.value.reason == "batcher_closed"


# ------------------------------------------------------------------ failover


def test_replica_death_reroutes_salvageable_tickets_token_identical(gpt, gpt_tiny_solo):
    """Replica 0's rebuild budget exhausts mid-decode with both requests
    pinned to it: every ticket re-routes to replica 1 and completes
    token-identical to a fault-free run — zero recoverable requests lost,
    zero pinned blocks leaked on either engine, and the fleet reports the
    degraded-but-serving state."""
    model, variables = gpt
    engines = [
        _engine(
            model, variables,
            faults=FaultPlan(step_dispatch_failures=(4,), rebuild_failures=99),
        ),
        _engine(model, variables),
    ]
    sups = [_supervisor(max_rebuild_attempts=2), _supervisor()]
    fleet = EngineFleet(engines, supervisors=sups)
    # pin both sessions to the doomed replica (the chaos case: stickiness
    # concentrated a conversation on the replica that then dies)
    fleet.router._sessions["a"] = (0, fleet.router._time())
    fleet.router._sessions["b"] = (0, fleet.router._time())

    async def main():
        return await asyncio.gather(
            fleet.generate(PROMPT_A, BUDGET_A, session_id="a"),
            fleet.generate(PROMPT_B, BUDGET_B, session_id="b"),
        )

    try:
        results = asyncio.run(main())
    finally:
        fleet.close()
    assert results == [gpt_tiny_solo(PROMPT_A, BUDGET_A), gpt_tiny_solo(PROMPT_B, BUDGET_B)]
    assert sups[0].state == "failed" and sups[1].state == "ok"
    stats = fleet.stats()["fleet"]
    assert stats["rerouted_tickets"] == 2 and stats["reroute_failed"] == 0
    health = fleet.healthz()
    assert health["state"] == "degraded" and health["serving_replicas"] == 1
    assert health["replicas"][0]["state"] == "failed"
    assert health["replicas"][1]["state"] == "ok"
    # the dead replica's sessions were dropped: the next turn re-routes
    assert fleet.router.session_replica("a") is None
    _fleet_no_leaks(fleet)


def test_session_chaos_next_turn_pays_only_suffix_prefill(gpt, gpt_tiny_solo):
    """A session's replica dies mid-turn; the turn completes on the adoptive
    replica (exact), and because the re-route recorded the transcript's
    digests there — and the adoptive engine caches generated KV — the
    session's NEXT turn routes to it and prefills only the new suffix."""
    model, variables = gpt
    engines = [
        _engine(
            model, variables,
            prefix_cache_generated=True,
            faults=FaultPlan(step_dispatch_failures=(4,), rebuild_failures=99),
        ),
        _engine(model, variables, prefix_cache_generated=True),
    ]
    fleet = EngineFleet(
        engines, supervisors=[_supervisor(max_rebuild_attempts=2), _supervisor()]
    )
    prompt1 = [3, 1, 4, 1, 5, 9, 2, 6]
    fleet.router._sessions["s"] = (0, fleet.router._time())
    try:
        out1 = asyncio.run(fleet.generate(prompt1, 8, session_id="s"))
        assert out1 == gpt_tiny_solo(prompt1, 8)  # exact across the failover
        prompt2 = prompt1 + out1 + [7, 7, 7, 7]  # the user's next message
        computed_before = engines[1].prefill_tokens_computed
        out2 = asyncio.run(fleet.generate(prompt2, 6, session_id="s"))
        assert out2 == gpt_tiny_solo(prompt2, 6)
        assert fleet.router.session_replica("s") == 1  # re-stuck on the adopter
        suffix_cost = engines[1].prefill_tokens_computed - computed_before
        # full re-prefill would be len(prompt2)=20 tokens; the transcript's
        # blocks (prompt1 + out1 = 16 tokens) restore from the radix cache
        assert suffix_cost <= 8, f"turn 2 re-prefilled {suffix_cost} tokens"
    finally:
        fleet.close()
    _fleet_no_leaks(fleet)


# -------------------------------------------------------------- HTTP surface


def _fleet_app(model, variables, **kw):
    import types

    from unionml_tpu.serving import build_aiohttp_app

    stub = types.SimpleNamespace(name="fleet-app", artifact=object())
    kw.setdefault("generator", lambda replica: _engine(model, variables))
    kw.setdefault("generate_replicas", 2)
    kw.setdefault("generate_fleet_config", FleetConfig(seed=0))
    return build_aiohttp_app(
        stub, resident=False, coalesce=False, generate_drain_s=2.0, **kw
    )


def test_fleet_healthz_stats_and_sessions_over_http(gpt, gpt_tiny_solo):
    from aiohttp.test_utils import TestClient, TestServer

    model, variables = gpt
    app = _fleet_app(model, variables)

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            body = await (await client.get("/healthz")).json()
            assert body["state"] == "ok" and body["fleet"] is True
            assert body["serving_replicas"] == 2 and len(body["replicas"]) == 2

            payload = {"prompt_ids": PROMPT_A, "max_new_tokens": 6, "session_id": "chat"}
            for _ in range(2):
                resp = await client.post("/generate", json=payload)
                assert resp.status == 200, await resp.text()
                assert (await resp.json())["tokens"] == gpt_tiny_solo(PROMPT_A, 6)

            resp = await client.post(
                "/generate", json={**payload, "session_id": 123}
            )
            assert resp.status == 400  # session ids are non-empty strings

            stats = await (await client.get("/stats")).json()
            block = stats["generation"]["fleet"]
            assert block["replicas"] == 2 and block["requests_routed"] == 2
            assert block["router"]["sticky_routes"] >= 1  # turn 2 stuck
            assert block["router"]["sessions_active"] == 1
            assert len(block["per_replica"]) == 2
            for entry in block["per_replica"]:
                assert entry["state"] == "ok"
                assert "per_class" in entry["scheduler"]
        finally:
            await client.close()

    asyncio.run(main())
    _fleet_no_leaks(app["continuous_batcher"])


def test_fleet_shed_retry_after_jitter_is_seedable(gpt):
    """The 429 envelope's Retry-After jitter draws from the injected RNG:
    two identically-seeded apps produce the exact same envelope (the
    de-correlation stays, the test flakiness goes)."""
    from aiohttp.test_utils import TestClient, TestServer

    model, variables = gpt

    def shed_app(seed):
        fleet = EngineFleet(
            [_engine(model, variables) for _ in range(2)],
            config=FleetConfig(max_queue=1, retry_after_s=2.0),
            supervisors=[_supervisor(), _supervisor()],
        )
        rep0 = fleet.replicas[0]
        rep0.batcher.scheduler.submit(
            rep0.batcher.scheduler.make_ticket(
                np.asarray(PROMPT_A, dtype=np.int32), 4, {}, _recorder()
            )
        )
        return fleet, _fleet_app(
            model, variables, generator=fleet, generate_replicas=1,
            retry_jitter_rng=random.Random(seed),
        )

    async def first_shed(fleet, app):
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate", json={"prompt_ids": PROMPT_B, "max_new_tokens": 4}
            )
            assert resp.status == 429
            body = await resp.json()
            assert body["error"]["reason"] == "queue_full"
            retry_ms = body["error"]["retry_after_ms"]
            header = resp.headers["Retry-After"]
        finally:
            fleet.replicas[0].batcher.scheduler.drain()  # let cleanup drain fast
            await client.close()
        return retry_ms, header

    expected_jitter = 2.0 * (0.75 + 0.5 * random.Random(42).random())
    for _ in range(2):  # same seed -> exact same envelope, twice
        fleet, app = shed_app(42)
        retry_ms, header = asyncio.run(first_shed(fleet, app))
        assert retry_ms == int(expected_jitter * 1000)
        assert header == str(max(1, round(expected_jitter)))
        # the jittered hint stays inside the +-25% band around the base
        assert 1500 <= retry_ms <= 2500
