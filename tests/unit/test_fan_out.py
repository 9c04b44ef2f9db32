"""Fan-out: a step's deliveries reach a consumer's event loop in one hand-over.

``ContinuousBatcher._dispatch_events`` does its bookkeeping on the worker and
stages what crosses to an event loop; each loop then gets ONE
``call_soon_threadsafe`` a call (``sink_handoffs``), carrying every item
(``sink_deliveries``) in the events' order. Sinks with no loop are called in
place. A closed loop costs only its own requests.
"""

import asyncio
import time

import pytest

from unionml_tpu.serving.continuous import (
    _STREAM_DONE,
    ContinuousBatcher,
    DecodeEngine,
    _deliver_batch,
    _FutureSink,
    _QueueSink,
)

REQUESTS = [([3, 1, 4], 5), ([2, 7], 4), ([1, 8, 2, 8], 3)]


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def _engine(gpt, slots=3):
    model, variables = gpt
    return DecodeEngine(model, variables, num_slots=slots, max_len=64, prefill_buckets=(4, 8))


def _drain(queue):
    items = []
    while not queue.empty():
        items.append(queue.get_nowait())
    return items


class _SpyLoop:
    """Counts the worker's cross-thread calls into a real event loop."""

    def __init__(self, loop):
        self.loop, self.calls = loop, 0

    def is_closed(self):
        return self.loop.is_closed()

    def call_soon_threadsafe(self, *args):
        self.calls += 1
        return self.loop.call_soon_threadsafe(*args)


class _Recorder:
    """A sink with no event loop (the tests' and the fleet's kind)."""

    cancelled = False

    def __init__(self, log, name):
        self.log, self.name = log, name

    def emit(self, token):
        self.log.append((self.name, "emit", token))

    def finish(self):
        self.log.append((self.name, "finish", None))

    def fail(self, exc):
        self.log.append((self.name, "fail", exc))


def _run_to_completion(batcher, engine, after_step=None):
    """Drive the engine and the batcher's fan-out on this thread."""
    while engine.num_active or engine.has_pending_events:
        events = engine.step()
        batcher._dispatch_events(events)
        if after_step is not None:
            after_step(events)


def test_one_step_is_one_hand_over_for_every_stream_on_a_loop(gpt, gpt_tiny_solo):
    engine = _engine(gpt)
    batcher = ContinuousBatcher(engine)
    loop = asyncio.new_event_loop()
    spy = _SpyLoop(loop)
    queues = []
    for prompt, n in REQUESTS:
        queue = asyncio.Queue()
        queues.append(queue)
        sink = _QueueSink(loop, queue)
        sink.loop = spy
        batcher._sinks[engine.add_request(prompt, n)] = sink

    def one_per_step(events):
        assert spy.calls == (1 if events else 0), events
        spy.calls = 0

    try:
        _run_to_completion(batcher, engine, one_per_step)
        loop.run_until_complete(asyncio.sleep(0))
        streams = [_drain(q) for q in queues]
    finally:
        loop.close()
    for (prompt, n), items in zip(REQUESTS, streams):
        assert items == gpt_tiny_solo(prompt, n) + [_STREAM_DONE]
    tokens = sum(n for _, n in REQUESTS)
    assert engine.sink_deliveries == tokens + len(REQUESTS)
    # the first step carries all three streams' first tokens, and the longest
    # stream's last step its finish: fewer hand-overs than tokens
    assert max(n for _, n in REQUESTS) <= engine.sink_handoffs < tokens
    assert engine.pipeline_stats()["sink_handoffs"] == engine.sink_handoffs


def test_concurrent_streams_and_futures_on_one_loop_match_solo(gpt, gpt_tiny_solo):
    engine = _engine(gpt)
    batcher = ContinuousBatcher(engine)

    async def collect(prompt, n):
        return [t async for t in batcher.stream(prompt, n)]

    async def main():
        return await asyncio.gather(
            collect(*REQUESTS[0]), batcher.generate(*REQUESTS[1]), collect(*REQUESTS[2])
        )

    try:
        results = asyncio.run(main())
    finally:
        batcher.close()
    assert results == [gpt_tiny_solo(p, n) for p, n in REQUESTS]
    # a future sink's tokens stay on the worker: only its list crosses
    assert engine.sink_deliveries == REQUESTS[0][1] + REQUESTS[2][1] + 3
    assert 0 < engine.sink_handoffs <= engine.sink_deliveries


class _HandOverRaises:
    """A loop that closes between the staging and the hand-over."""

    def is_closed(self):
        return False

    def call_soon_threadsafe(self, *args):
        raise RuntimeError("Event loop is closed")


def test_a_closed_loop_cancels_only_its_own_requests(gpt, gpt_tiny_solo):
    engine = _engine(gpt)
    batcher = ContinuousBatcher(engine)
    ends = []
    tel_end = batcher._tel_end
    batcher._tel_end = lambda ticket, status, reason=None: (
        ends.append(status), tel_end(ticket, status, reason)
    )
    closed = asyncio.new_event_loop()
    closed.close()
    gone = _QueueSink(closed, asyncio.Queue())
    racing = _QueueSink(_HandOverRaises(), asyncio.Queue())
    live_prompt, live_n = REQUESTS[2]

    async def main():
        batcher._submit([3, 1, 4], 30, gone)
        batcher._submit([2, 7], 30, racing)
        return [t async for t in batcher.stream(live_prompt, live_n)]

    try:
        live = asyncio.run(main())
        deadline = time.monotonic() + 10
        while (engine.num_active or len(ends) < 3) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert batcher._worker.is_alive()
    finally:
        batcher.close()
    assert live == gpt_tiny_solo(live_prompt, live_n)
    assert sorted(ends) == ["cancelled", "cancelled", "ok"]
    assert engine.num_active == 0 and len(engine.free_slots) == 3
    assert not batcher._sinks and not batcher._slot_meta


def test_a_delivery_that_raises_skips_none_after_it():
    loop = asyncio.new_event_loop()
    try:
        full = asyncio.Queue(maxsize=1)
        full.put_nowait(0)
        queue = asyncio.Queue()
        abandoned = loop.create_future()
        abandoned.cancel()
        resolved = loop.create_future()
        stream = _QueueSink(loop, queue)
        items = [
            (_QueueSink(loop, full), 1),  # QueueFull
            (stream, 7),
            (_FutureSink(loop, abandoned), [7]),  # already done: left as it is
            (_FutureSink(loop, resolved), [7, 8]),
            (stream, _STREAM_DONE),
        ]
        loop.call_soon_threadsafe(_deliver_batch, items)
        loop.run_until_complete(asyncio.sleep(0))
        assert _drain(queue) == [7, _STREAM_DONE]
        assert abandoned.cancelled() and resolved.result() == [7, 8]
    finally:
        loop.close()


def test_a_sink_without_a_loop_is_called_in_place_in_order(gpt, gpt_tiny_solo):
    engine = _engine(gpt, slots=2)
    batcher = ContinuousBatcher(engine)
    log = []
    names = {}
    for name, (prompt, n) in zip("ab", REQUESTS):
        slot = engine.add_request(prompt, n)
        names[slot] = name
        batcher._sinks[slot] = _Recorder(log, name)

    def in_place(events):
        # every event of the step is in the log when the fan-out returns
        expected = []
        for e in events:
            if e.emit:
                expected.append((names[e.slot], "emit", e.token))
            if e.finished:
                expected.append((names[e.slot], "finish", None))
        assert log[len(log) - len(expected):] == expected

    _run_to_completion(batcher, engine, in_place)
    for name, (prompt, n) in zip("ab", REQUESTS):
        mine = [(what, token) for who, what, token in log if who == name]
        assert mine == [("emit", t) for t in gpt_tiny_solo(prompt, n)] + [("finish", None)]
    assert engine.sink_handoffs == 0 and engine.sink_deliveries == 0
