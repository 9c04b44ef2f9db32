"""SLO-aware request scheduler: priorities, deadlines, shedding, preemption.

Tier-1 gate for the scheduling subsystem (serving/scheduler.py plus its hooks
through the engine, batcher, speculative facade, and HTTP app):

1. **Queue policy** — priority ordering under contention, anti-starvation
   aging, bounded-queue shedding (displace-or-shed), deadline infeasibility.
2. **Deadline enforcement** — queued AND running requests cancel with the
   structured ``DeadlineExceededError`` when their wall budget expires.
3. **Preempt-to-prefix-cache parity** — a request preempted mid-decode and
   resumed via a prefix-cache hit emits token-identical output to the
   uninterrupted run (greedy and fixed-seed sampled, 1-device and 4-device
   CPU meshes), with the checkpoint pinned against eviction until resume and
   every pin/refcount released after completion — including when a preempt
   races a client disconnect.
4. **HTTP contract** — 400 invalid / 429 queue-full / 503 infeasible /
   504 deadline, each with a machine-readable ``reason`` (and ``Retry-After``
   on the sheds), plus the ``/stats`` scheduler block.
"""

import asyncio
import time

import jax
import numpy as np
import pytest

from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
from unionml_tpu.serving.scheduler import (
    DeadlineExceededError,
    DeadlineInfeasibleError,
    QueueFullError,
    SchedulerConfig,
    SLOScheduler,
    parse_priority,
)


class _NullSink:
    cancelled = False

    def __init__(self):
        self.failures = []

    def emit(self, token):
        pass

    def finish(self):
        pass

    def fail(self, exc):
        self.failures.append(exc)


def _ticket(sched, priority="standard", deadline_ms=None, now=None, budget=4):
    return sched.make_ticket(
        np.asarray([1, 2, 3], dtype=np.int32), budget, {}, _NullSink(),
        priority=priority, deadline_ms=deadline_ms, now=now,
    )


# ------------------------------------------------------------- queue policy


def test_parse_priority_names_and_ints():
    assert parse_priority("interactive") == 0
    assert parse_priority("standard") == 1
    assert parse_priority("batch") == 2
    assert parse_priority(2) == 2
    for bad in ("urgent", 7, -1, True, 1.5, None):
        with pytest.raises(ValueError):
            parse_priority(bad)


def test_pop_orders_by_class_then_deadline_then_arrival():
    sched = SLOScheduler(SchedulerConfig(aging_s=0))
    t_batch = _ticket(sched, "batch")
    t_std_late = _ticket(sched, "standard", deadline_ms=60_000)
    t_std_soon = _ticket(sched, "standard", deadline_ms=5_000)
    t_inter = _ticket(sched, "interactive")
    for t in (t_batch, t_std_late, t_std_soon, t_inter):
        sched.submit(t)
    order = sched.pop(10)
    assert order == [t_inter, t_std_soon, t_std_late, t_batch]
    assert all(t.queue_wait_ms is not None for t in order)
    assert sched.stats()["admitted"] == 4 and sched.depth == 0


def test_fifo_mode_ignores_priorities():
    sched = SLOScheduler(SchedulerConfig(fifo=True))
    first = _ticket(sched, "batch")
    second = _ticket(sched, "interactive")
    sched.submit(first)
    sched.submit(second)
    assert sched.pop(2) == [first, second]
    assert sched.best_waiting_priority() is None  # FIFO never drives preemption


def test_aging_promotes_starved_batch_work():
    """A batch request queued long enough outranks fresher, nominally-better
    work: sustained high-priority traffic cannot starve the low classes."""
    sched = SLOScheduler(SchedulerConfig(aging_s=1.0))
    now = time.monotonic()
    old_batch = _ticket(sched, "batch", now=now - 1.5)  # aged one level: 2 -> 1
    fresh_std = _ticket(sched, "standard", now=now)
    fresh_batch = _ticket(sched, "batch", now=now)
    sched.submit(fresh_std, now=now)
    sched.submit(fresh_batch, now=now)
    sched.submit(old_batch, now=now)
    # effective classes: old_batch 1 (submitted LAST, so arrival order alone
    # would put it dead last), fresh_std 1, fresh_batch 2 — aging lifted the
    # starved batch ticket into the standard band, where arrival breaks the tie
    assert sched.pop(3, now=now) == [fresh_std, old_batch, fresh_batch]
    # aged far enough it reaches the top class and overtakes fresh standard work
    sched2 = SLOScheduler(SchedulerConfig(aging_s=1.0))
    starved = _ticket(sched2, "batch", now=now - 5.0)  # 2 - 5 -> floor 0
    fresh = _ticket(sched2, "standard", now=now)
    sched2.submit(fresh, now=now)
    sched2.submit(starved, now=now)
    assert sched2.pop(1, now=now) == [starved]


def test_bounded_queue_sheds_new_request():
    sched = SLOScheduler(SchedulerConfig(max_queue=2, retry_after_s=3.0))
    sched.submit(_ticket(sched, "standard"))
    sched.submit(_ticket(sched, "standard"))
    with pytest.raises(QueueFullError) as err:
        sched.submit(_ticket(sched, "standard"))
    assert err.value.reason == "queue_full" and err.value.retry_after_s == 3.0
    assert sched.stats()["shed_queue_full"] == 1 and sched.depth == 2


def test_bounded_queue_displaces_worse_for_strictly_higher_class():
    sched = SLOScheduler(SchedulerConfig(max_queue=2))
    keep = _ticket(sched, "standard")
    worst = _ticket(sched, "batch")
    sched.submit(keep)
    sched.submit(worst)
    newcomer = _ticket(sched, "interactive")
    displaced = sched.submit(newcomer)
    assert displaced is worst
    assert isinstance(displaced.shed_exc, QueueFullError)
    assert sched.pop(10) == [newcomer, keep]


def test_deadline_infeasible_sheds_at_submit():
    sched = SLOScheduler(SchedulerConfig())
    with sched._lock:
        sched.queue_wait_ema_ms = 5_000.0  # observed queueing: ~5s
    with pytest.raises(DeadlineInfeasibleError) as err:
        sched.submit(_ticket(sched, "interactive", deadline_ms=100))
    assert err.value.reason == "deadline_infeasible"
    assert sched.stats()["shed_deadline_infeasible"] == 1
    # a feasible deadline still queues
    assert sched.submit(_ticket(sched, "interactive", deadline_ms=60_000)) is None
    with pytest.raises(ValueError):
        _ticket(sched, deadline_ms=0)
    with pytest.raises(ValueError):
        _ticket(sched, deadline_ms="soon")


def test_take_expired_removes_and_counts():
    sched = SLOScheduler(SchedulerConfig())
    now = time.monotonic()
    gone = _ticket(sched, deadline_ms=10, now=now - 1.0)
    live = _ticket(sched, deadline_ms=60_000, now=now)
    sched.submit(gone, now=now - 1.0)
    sched.submit(live, now=now)
    assert sched.take_expired(now) == [gone]
    assert sched.stats()["deadline_misses_queued"] == 1
    assert sched.pop(10, now=now) == [live]


def test_load_signal_and_stats_carry_the_pool_block():
    """The ``"pool"`` block (ISSUE 15): ``None`` without a provider (dense
    engines), else forwarded verbatim in BOTH load_signal (router +
    autoscaler surface) and stats (the ``/stats`` scheduler block)."""
    sched = SLOScheduler(SchedulerConfig())
    assert sched.load_signal()["pool"] is None
    assert sched.stats()["pool"] is None
    occupancy = {
        "num_blocks": 64, "free_frac": 0.5, "live_frac": 0.25,
        "cached_frac": 0.25, "pinned_frac": 0.0,
        "available_blocks": 48, "pressure": 0.25,
    }
    sched.pool_signal = lambda: occupancy
    signal = sched.load_signal()
    assert signal["pool"] == occupancy
    assert set(signal) == {"depth", "queue_wait_ema_ms", "per_class", "pool"}
    assert sched.stats()["pool"] == occupancy


# ------------------------------------------------ engine preempt / resume


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def _mesh4():
    from unionml_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 CPU devices)")
    return make_mesh({"tensor": 4}, devices=jax.devices()[:4])


def _engine(model, variables, mesh=None, pipeline=True, **kw):
    return DecodeEngine(
        model, variables, num_slots=2, max_len=64, prefill_buckets=(8, 16, 32),
        prefix_cache_blocks=64, prefix_block_size=4, mesh=mesh, pipeline=pipeline, **kw,
    )


def _drain(engine, collect):
    while engine.num_active or engine.has_pending_events or engine.has_pending_prefill:
        for ev in engine.step():
            if ev.emit:
                collect.append(ev.token)


@pytest.mark.parametrize("pipeline", [True, False], ids=["pipelined", "unpipelined"])
@pytest.mark.parametrize("mesh4", [False, True], ids=["1dev", "mesh4"])
def test_preempt_resume_token_parity_greedy(gpt, pipeline, mesh4):
    """Preempted mid-decode + resumed via prefix-cache hit == uninterrupted."""
    model, variables = gpt
    mesh = _mesh4() if mesh4 else None
    prompt, budget = [3, 1, 4, 1, 5], 14

    ref_engine = _engine(model, variables, mesh=mesh, pipeline=pipeline)
    expected = ref_engine.generate(prompt, budget)

    engine = _engine(model, variables, mesh=mesh, pipeline=pipeline)
    slot = engine.add_request(prompt, budget)
    out = []
    for _ in range(5):
        out.extend(ev.token for ev in engine.step() if ev.emit)
    state = engine.preempt(slot)
    assert state is not None and engine.free_slots  # the slot came free
    assert engine.prefix_cache.pinned_blocks == len(state.path) > 0
    hits_before = engine.prefix_cache.stats()["hits"]
    resumed = engine.add_request(
        state.tokens, budget - (len(state.tokens) - len(prompt))
    )
    engine.release_preempted(state)
    # the resume went through the prefix-hit path: only the transcript's
    # uncovered tail re-prefilled
    assert engine.prefix_cache.stats()["hits"] == hits_before + 1
    _drain(engine, out)
    assert out == expected
    assert engine.prefix_cache.pinned_blocks == 0


def test_preempt_resume_token_parity_fixed_seed_sampled(gpt):
    """Same-seed sampled streams survive preemption: the engine key advances
    once per decoded step either way, and the restored KV + suffix prefill
    reproduce the logits bit-exactly."""
    model, variables = gpt
    prompt, budget = [3, 1, 4, 1, 5], 12

    def run(preempt_after):
        engine = _engine(model, variables, temperature=0.8, seed=7)
        slot = engine.add_request(prompt, budget, temperature=0.8)
        out = []
        if preempt_after is None:
            _drain(engine, out)
            return out
        for _ in range(preempt_after):
            out.extend(ev.token for ev in engine.step() if ev.emit)
        state = engine.preempt(slot)
        engine.add_request(
            state.tokens, budget - (len(state.tokens) - len(prompt)), temperature=0.8
        )
        engine.release_preempted(state)
        _drain(engine, out)
        assert engine.prefix_cache.pinned_blocks == 0
        return out

    assert run(preempt_after=4) == run(preempt_after=None)


def test_preempt_refcounts_fully_released_after_completion(gpt):
    model, variables = gpt
    engine = _engine(model, variables)
    slot = engine.add_request([3, 1, 4, 1, 5], 10)
    for _ in range(4):
        engine.step()
    state = engine.preempt(slot)
    # pinned: every checkpoint node holds exactly the pin reference
    assert all(node.refcount == 1 for node in state.path)
    engine.add_request(state.tokens, 10 - (len(state.tokens) - 5))
    engine.release_preempted(state)
    _drain(engine, [])
    assert engine.prefix_cache.pinned_blocks == 0
    # after retirement NOTHING holds a reference: walk the whole tree
    stack = list(engine.prefix_cache._root.children.values())
    while stack:
        node = stack.pop()
        assert node.refcount == 0
        stack.extend(node.children.values())


def test_preempt_without_prefix_cache_raises(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,))
    slot = engine.add_request([3, 1, 4], 4)
    with pytest.raises(RuntimeError, match="prefix cache"):
        engine.preempt(slot)


def test_queue_wait_rides_first_step_event_only(gpt):
    model, variables = gpt
    from unionml_tpu.serving.telemetry import Telemetry

    telemetry = Telemetry()
    sched = SLOScheduler(telemetry=telemetry)
    ticket = _ticket(sched, now=100.0)
    sched.submit(ticket, now=100.0)
    assert sched.pop(1, now=100.0125) == [ticket]
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,))
    slot = engine.add_request([3, 1, 4], 4)
    engine.note_queue_wait(slot, ticket.queue_wait_ms)
    events = []
    while engine.num_active or engine.has_pending_events:
        events.extend(engine.step())
    waits = [ev.queue_wait_ms for ev in events]
    assert waits[0] == pytest.approx(12.5) and all(w is None for w in waits[1:])
    # the aggregate of queue waits is the histogram's sum and count
    waited = telemetry.metrics.snapshot()["unionml_queue_wait_ms"]["standard"]
    assert waited["count"] == 1 and waited["sum"] == pytest.approx(12.5)


# ------------------------------------------------------- batcher integration


def test_priority_ordering_under_contention(gpt, gpt_tiny_solo):
    """With one slot occupied and no preemption, a later interactive request
    jumps the queue ahead of an earlier batch request."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(
        engine, scheduler=SLOScheduler(SchedulerConfig(preempt=False))
    )

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 25))
        while not engine.num_active:  # hog must hold the slot before we queue
            await asyncio.sleep(0.01)
        batch_task = asyncio.ensure_future(batcher.generate([2, 7], 4, priority="batch"))
        await asyncio.sleep(0.05)  # batch is queued first...
        inter = await batcher.generate([3, 1, 4], 4, priority="interactive")
        batch_done_when_inter_finished = batch_task.done()
        return inter, await batch_task, await hog, batch_done_when_inter_finished

    try:
        inter, batch, hog, batch_done_first = asyncio.run(main())
    finally:
        batcher.close()
    assert not batch_done_first  # interactive overtook the earlier batch request
    assert inter == gpt_tiny_solo([3, 1, 4], 4)
    assert batch == gpt_tiny_solo([2, 7], 4)
    assert hog == gpt_tiny_solo([9, 9, 1, 2], 25)


def test_preempt_to_prefix_cache_end_to_end(gpt, gpt_tiny_solo):
    """A batch hog on the only slot is preempted for an interactive arrival,
    then resumes via the prefix cache — both outputs exact, counters ticked,
    no pinned blocks left."""
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8, 16, 32),
        prefix_cache_blocks=64, prefix_block_size=4,
    )
    batcher = ContinuousBatcher(engine)

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 40, priority="batch"))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.1)  # let the hog decode a few tokens
        inter = await batcher.generate([3, 1, 4], 4, priority="interactive")
        return inter, await hog

    try:
        inter, hog = asyncio.run(main())
    finally:
        batcher.close()
    assert inter == gpt_tiny_solo([3, 1, 4], 4)
    assert hog == gpt_tiny_solo([9, 9, 1, 2], 40)
    stats = batcher.scheduler.stats()
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    assert engine.preempted_requests >= 1
    assert engine.prefix_cache.pinned_blocks == 0


def test_batcher_wires_engine_pool_signal_into_scheduler(gpt):
    """A paged batcher hands the engine's pool-occupancy provider to its
    scheduler, so load_signal/stats surface the block-pool counters; a
    dense engine has no pool and the block stays None."""
    model, variables = gpt
    batcher = ContinuousBatcher(_engine(model, variables))
    try:
        pool = batcher.scheduler.load_signal()["pool"]
        assert set(pool) == {
            "num_blocks", "free_frac", "live_frac", "cached_frac",
            "pinned_frac", "available_blocks", "pressure",
        }
        # idle engine: everything free, nothing live/pinned, zero pressure
        assert pool["free_frac"] == 1.0 and pool["pressure"] == 0.0
        assert pool["available_blocks"] == pool["num_blocks"] > 0
        assert pool["live_frac"] == 0.0 and pool["pinned_frac"] == 0.0
        assert batcher.scheduler.stats()["pool"] == pool
    finally:
        batcher.close()

    dense = ContinuousBatcher(DecodeEngine(
        model, variables, num_slots=2, max_len=64, prefill_buckets=(8, 16, 32),
        paged=False,
    ))
    try:
        assert dense.scheduler.load_signal()["pool"] is None
    finally:
        dense.close()


def test_preempt_racing_disconnect_never_leaks_pinned_entry(gpt, gpt_tiny_solo):
    """A preempted-and-requeued request whose client disconnects before the
    resume re-admits must still drop its eviction pin."""
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8, 16, 32),
        prefix_cache_blocks=64, prefix_block_size=4,
    )
    batcher = ContinuousBatcher(engine)

    async def main():
        stream_it = batcher.stream([9, 9, 1, 2], 40, priority="batch")
        first = await anext(stream_it)  # the hog is decoding on the only slot
        # a LONG interactive request preempts the hog, and keeps the slot busy
        # so the hog sits re-queued with its checkpoint pinned
        inter_task = asyncio.ensure_future(
            batcher.generate([3, 1, 4], 30, priority="interactive")
        )
        for _ in range(500):
            if batcher.scheduler.stats()["preemptions"] >= 1:
                break
            await asyncio.sleep(0.01)
        pinned_while_queued = engine.prefix_cache.pinned_blocks
        # ...and the hog's client disconnects while it sits re-queued
        await stream_it.aclose()
        inter = await inter_task
        for _ in range(200):
            if engine.prefix_cache.pinned_blocks == 0:
                break
            await asyncio.sleep(0.02)
        return first, inter, pinned_while_queued

    try:
        first, inter, pinned_while_queued = asyncio.run(main())
    finally:
        batcher.close()
    assert inter == gpt_tiny_solo([3, 1, 4], 30)
    assert first == gpt_tiny_solo([9, 9, 1, 2], 40)[0]
    assert pinned_while_queued > 0  # the checkpoint really was pinned
    assert engine.prefix_cache.pinned_blocks == 0  # ...and never leaked
    assert batcher.scheduler.stats()["preemptions"] >= 1


def test_deadline_cancels_queued_request(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine, scheduler=SLOScheduler(SchedulerConfig(preempt=False)))

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 30))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        with pytest.raises(DeadlineExceededError):
            await batcher.generate([3, 1, 4], 4, deadline_ms=40)
        return await hog

    try:
        asyncio.run(main())
    finally:
        batcher.close()
    assert batcher.scheduler.stats()["deadline_misses_queued"] == 1


def test_deadline_cancels_running_request(gpt, gpt_tiny_solo):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=128, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine)

    async def main():
        with pytest.raises(DeadlineExceededError):
            # far more decode work than 40ms buys on this host: expires RUNNING
            await batcher.generate([9, 9, 1, 2], 120, deadline_ms=40)
        # the slot is reclaimed: the next request decodes exactly
        return await batcher.generate([3, 1, 4], 4)

    try:
        follow_up = asyncio.run(main())
    finally:
        batcher.close()
    assert follow_up == gpt_tiny_solo([3, 1, 4], 4)
    assert batcher.scheduler.stats()["deadline_misses_running"] == 1
    assert engine.num_active == 0


def test_close_fails_queued_sinks_promptly(gpt):
    """close() with a non-empty queue must reject every queued future with
    'batcher closed' instead of leaving it hanging forever."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine)

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 30))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        queued = asyncio.ensure_future(batcher.generate([3, 1, 4], 4))
        await asyncio.sleep(0.05)
        t0 = time.monotonic()
        batcher.close()
        with pytest.raises(RuntimeError, match="batcher closed"):
            await asyncio.wait_for(queued, timeout=2.0)
        elapsed = time.monotonic() - t0
        hog.cancel()
        return elapsed

    elapsed = asyncio.run(main())
    assert elapsed < 2.0  # rejected promptly, not at some drain timeout


def test_displaced_request_fails_with_queue_full(gpt, gpt_tiny_solo):
    """Under a full bounded queue, a higher-class arrival displaces the worst
    queued request, which fails fast with the structured shed error."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(
        engine, scheduler=SLOScheduler(SchedulerConfig(max_queue=1, preempt=False))
    )

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 25))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        queued_batch = asyncio.ensure_future(batcher.generate([2, 7], 4, priority="batch"))
        await asyncio.sleep(0.05)
        inter = await batcher.generate([3, 1, 4], 4, priority="interactive")
        with pytest.raises(QueueFullError):
            await queued_batch
        return inter, await hog

    try:
        inter, hog = asyncio.run(main())
    finally:
        batcher.close()
    assert inter == gpt_tiny_solo([3, 1, 4], 4)
    assert hog == gpt_tiny_solo([9, 9, 1, 2], 25)


# --------------------------------------------------------------- HTTP layer


def _app(model, variables, **engine_kw):
    import types

    from unionml_tpu.serving import build_aiohttp_app

    stub = types.SimpleNamespace(name="slo-app", artifact=object())
    return build_aiohttp_app(
        stub, resident=False, coalesce=False,
        generator=lambda: DecodeEngine(model, variables, **engine_kw),
        generate_scheduler=SchedulerConfig(max_queue=1, preempt=False),
    )


def test_http_status_codes_and_reasons(gpt):
    """The satellite contract: 400 invalid, 429 queue-full + Retry-After,
    503 infeasible + Retry-After, 504 deadline — machine-readable reasons."""
    from aiohttp.test_utils import TestClient, TestServer

    model, variables = gpt
    app = _app(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))

    def _set_wait_ema(sched, value):
        # the infeasibility check prefers the ticket's class EMA over the
        # global one, so pinning "observed queueing" means pinning both
        with sched._lock:
            sched.queue_wait_ema_ms = value
            for name in sched.queue_wait_ema_ms_by_class:
                sched.queue_wait_ema_ms_by_class[name] = value

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # --- 400: invalid payloads, each with reason
            for payload in (
                {},
                {"prompt_ids": [1, 2], "max_new_tokens": 0},
                {"prompt_ids": [], "max_new_tokens": 4},
                {"prompt_ids": [1, 2], "max_new_tokens": 4, "priority": "urgent"},
                {"prompt_ids": [1, 2], "max_new_tokens": 4, "deadline_ms": -5},
                {"prompt_ids": [1, 2], "max_new_tokens": 4, "top_p": 0},
            ):
                resp = await client.post("/generate", json=payload)
                assert resp.status == 400, (payload, await resp.text())
                body = (await resp.json())["error"]
                assert body["code"] == 400
                assert body["reason"] in ("invalid_request", "invalid_json"), body
            resp = await client.post("/generate", data=b"not json")
            assert resp.status == 400
            assert (await resp.json())["error"]["reason"] == "invalid_json"

            gen = app["continuous_batcher"]
            engine = gen.engine

            # --- 429: slot busy + queue (bound 1) full
            hog = asyncio.ensure_future(
                client.post(
                    "/generate", json={"prompt_ids": [9, 9, 1, 2], "max_new_tokens": 40}
                )
            )
            while not engine.num_active:
                await asyncio.sleep(0.01)
            filler = asyncio.ensure_future(
                client.post("/generate", json={"prompt_ids": [2, 7], "max_new_tokens": 4})
            )
            while gen.scheduler.load_signal()["depth"] < 1:  # filler holds the queue
                await asyncio.sleep(0.01)
            resp = await client.post(
                "/generate", json={"prompt_ids": [5, 5], "max_new_tokens": 4}
            )
            assert resp.status == 429, await resp.text()
            body = (await resp.json())["error"]
            assert body["reason"] == "queue_full" and body["code"] == 429
            # jittered retry advice: ±25% around the configured 1s, in BOTH
            # the header and the machine-readable envelope
            assert "Retry-After" in resp.headers
            assert 750 <= body["retry_after_ms"] <= 1250

            assert (await hog).status == 200
            assert (await filler).status == 200

            # --- 504: queued behind a fresh hog with an expiring deadline
            # (clear the observed-wait EMAs first: with history it would shed
            # 503-infeasible at submit instead of expiring in the queue)
            _set_wait_ema(gen.scheduler, None)
            # the hog must outlive the queued request's deadline even on a
            # warm engine: 60 decode steps vs a 25ms budget
            admitted = engine.requests_admitted
            hog2 = asyncio.ensure_future(
                client.post(
                    "/generate", json={"prompt_ids": [8, 8, 8], "max_new_tokens": 60}
                )
            )
            # wait for THIS hog to hold the slot: `num_active` alone can still
            # be the finished filler's slot, and the deadline request would then
            # be admitted ahead of the hog and expire while running
            while engine.requests_admitted == admitted or not engine.num_active:
                await asyncio.sleep(0.01)
            resp = await client.post(
                "/generate",
                json={"prompt_ids": [4, 4], "max_new_tokens": 4, "deadline_ms": 25},
            )
            assert resp.status == 504, await resp.text()
            assert (await resp.json())["error"]["reason"] == "deadline_exceeded"
            assert (await hog2).status == 200

            # --- 503: observed queueing makes the deadline infeasible
            _set_wait_ema(gen.scheduler, 60_000.0)
            resp = await client.post(
                "/generate",
                json={"prompt_ids": [1, 2], "max_new_tokens": 4, "deadline_ms": 50},
            )
            assert resp.status == 503, await resp.text()
            assert (await resp.json())["error"]["reason"] == "deadline_infeasible"
            assert "Retry-After" in resp.headers
            _set_wait_ema(gen.scheduler, None)

            # --- streaming shed surfaces as a real status (not in-band)
            _set_wait_ema(gen.scheduler, 60_000.0)
            resp = await client.post(
                "/generate",
                json={"prompt_ids": [1, 2], "max_new_tokens": 4, "stream": True,
                      "deadline_ms": 50},
            )
            assert resp.status == 503, await resp.text()
            _set_wait_ema(gen.scheduler, None)

            # --- /stats carries the scheduler block
            stats = await (await client.get("/stats")).json()
            block = stats["generation"]["scheduler"]
            assert block["policy"] == "priority"
            assert block["shed_queue_full"] >= 1
            assert block["shed_deadline_infeasible"] >= 2
            assert block["deadline_misses_queued"] >= 1
            assert set(block["depth_by_class"]) == {"interactive", "standard", "batch"}
        finally:
            await client.close()

    asyncio.run(main())


# ------------------------------------------------------ speculative facade


def test_speculative_routes_through_scheduler(gpt):
    """The speculative facade shares the scheduler surface: bounded-queue
    sheds raise the same structured errors and /stats sees the same block."""
    from unionml_tpu.serving import SpeculativeBatcher

    model, variables = gpt
    spec = SpeculativeBatcher(
        model, variables, model, variables, gamma=2, max_len=64,
        scheduler=SchedulerConfig(max_queue=0),
    )
    with pytest.raises(QueueFullError):
        asyncio.run(spec.generate([3, 1, 4], 4))
    stats = spec.scheduler.stats()
    assert stats["shed_queue_full"] == 1 and stats["policy"] == "priority"
    spec.close()

    spec = SpeculativeBatcher(model, variables, model, variables, gamma=2, max_len=64)
    tokens = asyncio.run(spec.generate([3, 1, 4], 5, priority="interactive"))
    assert len(tokens) == 5
    assert spec.scheduler.stats()["admitted"] == 1
    spec.close()


def test_speculative_priority_turn_taking(gpt):
    """Queued speculative requests take the single stream in priority order."""
    from unionml_tpu.serving import SpeculativeBatcher

    model, variables = gpt
    spec = SpeculativeBatcher(model, variables, model, variables, gamma=2, max_len=64)
    order = []

    async def main():
        async def one(name, priority):
            await spec.generate([3, 1, 4], 8, priority=priority)
            order.append(name)

        first = asyncio.ensure_future(one("warm", "standard"))
        await asyncio.sleep(0.05)  # the warm request holds the stream
        batch = asyncio.ensure_future(one("batch", "batch"))
        await asyncio.sleep(0.02)
        inter = asyncio.ensure_future(one("inter", "interactive"))
        await asyncio.gather(first, batch, inter)

    try:
        asyncio.run(main())
    finally:
        spec.close()
    assert order.index("inter") < order.index("batch")


# --------------------------------- preempt failure paths drop the pin


def test_preempt_bookkeeping_failure_drops_its_pin(gpt):
    """If the slot teardown inside ``preempt`` dies AFTER the checkpoint pin
    was taken, the pin must be dropped before the error propagates: the
    ``PreemptedSlot`` never reached the caller, so nobody could ever call
    ``release_preempted`` for it."""
    model, variables = gpt
    engine = _engine(model, variables)
    slot = engine.add_request([3, 1, 4, 1, 5], 14)
    for _ in range(5):
        engine.step()

    def boom(*args, **kwargs):
        raise RuntimeError("slot device update failed")

    engine._slot_device_update = boom
    with pytest.raises(RuntimeError, match="slot device update failed"):
        engine.preempt(slot)
    assert engine.prefix_cache.pinned_blocks == 0


def test_preempt_requeue_failure_releases_the_checkpoint(gpt):
    """If re-queuing the victim dies after ``preempt`` returned (the
    checkpoint is pinned but not yet owned by the queue), the batcher must
    release it before surfacing the failure — otherwise the victim's blocks
    stay fenced in the pool forever."""
    from unionml_tpu.serving.faults import EngineFailure

    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8, 16, 32),
        prefix_cache_blocks=64, prefix_block_size=4,
    )
    batcher = ContinuousBatcher(engine)
    requeues = []

    def failing_requeue(meta):
        requeues.append(meta)
        raise RuntimeError("scheduler requeue failed")

    batcher.scheduler.requeue = failing_requeue

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 40, priority="batch"))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.1)  # let the hog decode a few tokens
        inter = asyncio.ensure_future(
            batcher.generate([3, 1, 4], 4, priority="interactive")
        )
        results = await asyncio.gather(hog, inter, return_exceptions=True)
        return results

    try:
        results = asyncio.run(asyncio.wait_for(main(), timeout=30.0))
    finally:
        batcher.close()
    # the preemption really happened and really hit the failing requeue
    assert requeues, "the interactive arrival never drove a preemption"
    # the hog cannot survive (its re-queue failed); either structured engine
    # failure or a propagated requeue error is acceptable — hanging is not
    assert any(isinstance(r, (EngineFailure, RuntimeError)) for r in results)
    # the contract under test: the orphaned checkpoint's pin was dropped
    assert engine.prefix_cache.pinned_blocks == 0
