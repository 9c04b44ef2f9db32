"""Continuous-batching decode engine: exactness vs the one-shot generate path.

The gold property (mirrors the ragged-prompt guarantee in test_gpt.py): a request
decoded through the slot engine — with OTHER requests inserted and evicted around
it mid-flight — emits exactly the tokens it would emit alone through
``models.gpt.generate``. Greedy, f32, tiny config, so equality is exact.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import GPTConfig, GPTLMHeadModel
from unionml_tpu.models.gpt import generate, init_params
from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine

CONFIG = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    # session-scoped model/params (shared with test_gpt and the sharded-engine
    # suite): one init + one set of reference-generate compiles for the whole run
    _, model, variables = gpt_tiny_session
    return model, variables


def solo(model, variables, prompt, n):
    """Reference: the one-shot batch-1 generate path."""
    ids = jnp.asarray(np.asarray(prompt, dtype=np.int32)[None])
    out = generate(model, variables, ids, n)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def test_engine_single_request_matches_generate(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(8, 16))
    prompt = [3, 1, 4, 1, 5]
    assert engine.generate(prompt, 6) == solo(model, variables, prompt, 6)


def test_staggered_insertion_does_not_perturb_neighbors(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=3, max_len=64, prefill_buckets=(4, 8, 16))
    requests = [([3, 1, 4, 1, 5], 6), ([2, 7], 5), ([1, 8, 2, 8, 1, 8, 2, 8], 4)]
    expected = [solo(model, variables, p, n) for p, n in requests]

    collected = {}
    slot_to_req = {}

    def drain(events):
        for ev in events:
            if ev.emit:
                collected.setdefault(slot_to_req[ev.slot], []).append(ev.token)

    # request 0 decodes alone for 2 steps, then 1 joins, then 2 — insertions land
    # BETWEEN steps of already-running requests
    slot_to_req[engine.add_request(*requests[0])] = 0
    drain(engine.step())
    drain(engine.step())
    slot_to_req[engine.add_request(*requests[1])] = 1
    drain(engine.step())
    slot_to_req[engine.add_request(*requests[2])] = 2
    while engine.num_active:
        drain(engine.step())

    assert [collected[i] for i in range(3)] == expected


def test_slot_reuse_after_finish(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,))
    first = engine.generate([5, 4, 3], 4)
    second = engine.generate([9, 9, 1, 2], 5)  # reuses the single slot
    assert first == solo(model, variables, [5, 4, 3], 4)
    assert second == solo(model, variables, [9, 9, 1, 2], 5)


def test_eos_stops_and_is_not_emitted(gpt):
    model, variables = gpt
    prompt = [3, 1, 4, 1, 5]
    expected = solo(model, variables, prompt, 6)
    eos = expected[2]
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8,), eos_token_id=eos
    )
    assert engine.generate(prompt, 6) == expected[: expected.index(eos)]


def test_capacity_force_finish(gpt):
    model, variables = gpt
    prompt = [1, 2, 3, 4]
    engine = DecodeEngine(model, variables, num_slots=1, max_len=16, prefill_buckets=(4, 8))
    out = engine.generate(prompt, 100)  # budget far beyond cache capacity
    budget = 16 - 1 - len(prompt)
    assert len(out) == budget
    assert out == solo(model, variables, prompt, budget)


def test_request_validation(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=16, prefill_buckets=(4,))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.add_request([], 4)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        engine.add_request(list(range(9)), 4)
    with pytest.raises(ValueError, match="max_len"):
        engine.add_request(list(range(40)), 4)
    engine.add_request([1, 2], 4)
    with pytest.raises(RuntimeError, match="no free decode slots"):
        engine.add_request([1, 2], 4)


def test_per_row_positions_reject_multi_token(gpt):
    model, variables = gpt
    from unionml_tpu.models.gpt import init_cache

    cache = init_cache(CONFIG, 2, 16)
    with pytest.raises(ValueError, match="seq=1"):
        model.apply(
            variables,
            jnp.zeros((2, 2), dtype=jnp.int32),
            cache=cache,
            position=jnp.zeros((2,), dtype=jnp.int32),
        )


def test_step_failure_resets_engine(gpt):
    """A device failure mid-step (donated buffers poisoned) must not brick the
    engine: step() resets device + host state, raises, and the next request
    decodes correctly from scratch."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(8,))
    engine.add_request([3, 1, 4], 5)

    def exploding(*args, **kwargs):
        raise RuntimeError("synthetic device failure")

    engine._step_fns = {(1, False): exploding, (1, True): exploding}
    with pytest.raises(RuntimeError, match="synthetic device failure"):
        engine.step()
    engine._step_fns = {}

    assert engine.num_active == 0  # in-flight request abandoned
    assert engine.generate([3, 1, 4], 5) == solo(model, variables, [3, 1, 4], 5)


def test_step_failure_after_state_assignment_recovers_key(gpt):
    """The deferred-error shape: the step's tuple assignment completes (every
    state var, including the PRNG key, now references poisoned outputs) before
    the token fetch raises. reset() must rebuild the key too."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,))
    engine.add_request([3, 1, 4], 5)

    def poisoning(*args, **kwargs):
        # state vars get assigned garbage, THEN the fetch path raises
        engine._key = object()  # stands in for a poisoned device array
        raise RuntimeError("deferred device failure")

    engine._step_fns = {(1, False): poisoning, (1, True): poisoning}
    with pytest.raises(RuntimeError, match="deferred device failure"):
        engine.step()
    engine._step_fns = {}

    assert type(engine._key) is not object  # fresh jax key, not the poisoned stand-in
    assert engine.generate([3, 1, 4], 5) == solo(model, variables, [3, 1, 4], 5)


def test_cancel_mid_chunked_prefill_frees_slot_for_reuse(gpt):
    """Cancelling a slot with a chunked prefill IN PROGRESS (chunks already
    advanced, not merely queued) must drop the partial entirely: the slot
    returns to free_slots, a subsequent admit_many reuses it, and the new
    request's stream matches a fresh engine exactly."""
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(16,), prefill_chunk=4
    )
    (slot,) = engine.admit_many([(list(range(1, 11)), 5)])
    engine.step()  # advance ONE chunk: the partial now holds device state
    assert engine.has_pending_prefill and engine._partials[slot]["consumed"] > 0
    engine.cancel(slot)
    assert not engine.has_pending_prefill
    assert not engine._partials and engine.free_slots == [slot]

    (slot2,) = engine.admit_many([([3, 1, 4], 4)])
    assert slot2 == slot  # the cancelled partial's slot is genuinely reusable
    out = []
    while engine.num_active:
        out.extend(ev.token for ev in engine.step() if ev.emit)
    assert out == solo(model, variables, [3, 1, 4], 4)


def test_bucket_equal_to_max_len_is_usable(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=16, prefill_buckets=(16,))
    prompt = list(range(1, 11))  # length 10 needs the 16 bucket
    assert engine.generate(prompt, 3) == solo(model, variables, prompt, 3)


def test_generate_route_over_http(gpt):
    """POST /generate end to end: in-process aiohttp server + continuous batcher."""
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="gen-app", artifact=object())
    app = build_aiohttp_app(
        stub,
        resident=False,
        coalesce=False,
        generator=lambda: DecodeEngine(
            model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8)
        ),
    )
    expected_single = solo(model, variables, [3, 1, 4], 5)
    expected_batch = [solo(model, variables, p, 4) for p in ([2, 7], [5, 5, 5])]

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/generate", json={"prompt_ids": [3, 1, 4], "max_new_tokens": 5})
            assert resp.status == 200, await resp.text()
            single = (await resp.json())["tokens"]

            resp = await client.post(
                "/generate", json={"prompts": [[2, 7], [5, 5, 5]], "max_new_tokens": 4}
            )
            assert resp.status == 200, await resp.text()
            batch = (await resp.json())["completions"]

            resp = await client.post("/generate", json={})
            assert resp.status == 400
            assert (await resp.json())["error"]["reason"] == "invalid_request"

            resp = await client.post(
                "/generate", json={"prompt_ids": list(range(100)), "max_new_tokens": 4}
            )
            assert resp.status == 400

            resp = await client.post(
                "/generate", json={"prompt_ids": [1, 2], "max_new_tokens": [32]}
            )
            assert resp.status == 400  # malformed budget is a client error, not a 500

            resp = await client.post(
                "/generate", json={"prompt_ids": [1, None], "max_new_tokens": 4}
            )
            assert resp.status == 400  # non-numeric token is a client error

            resp = await client.post("/generate", json={"prompts": 123, "max_new_tokens": 4})
            assert resp.status == 400  # non-list prompts is a client error

            # one bad prompt rejects the whole batch BEFORE any slot is scheduled
            resp = await client.post(
                "/generate",
                json={"prompts": [[2, 7], list(range(100))], "max_new_tokens": 4},
            )
            assert resp.status == 400
            resp = await client.get("/stats")
            assert (await resp.json())["generation"]["active"] == 0

            resp = await client.get("/stats")
            stats = await resp.json()
            assert stats["generation"]["num_slots"] == 2
            # pipelined-decode observability: depth + host-gap/fetch EMAs +
            # device-idle counters ride along for the continuous engine
            pipeline = stats["generation"]["pipeline"]
            assert pipeline["depth"] == 1 and pipeline["step_dispatches"] > 0
            assert stats["generation"]["requests_admitted"] >= 3
            assert stats["generation"]["tokens_decoded"] >= 5
            return single, batch
        finally:
            await client.close()

    single, batch = asyncio.run(main())
    assert single == expected_single
    assert batch == expected_batch


def test_batcher_stream_yields_tokens_incrementally(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine)
    expected = solo(model, variables, [3, 1, 4], 5)

    async def main():
        seen = []
        # a completed-list request runs CONCURRENTLY with the stream on the
        # shared engine
        whole_task = asyncio.ensure_future(batcher.generate([2, 7], 4))
        async for token in batcher.stream([3, 1, 4], 5):
            seen.append(token)
        return seen, await whole_task

    try:
        streamed, whole = asyncio.run(main())
    finally:
        batcher.close()
    assert streamed == expected
    assert whole == solo(model, variables, [2, 7], 4)


def test_stream_route_ndjson(gpt):
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="gen-app", artifact=object())
    app = build_aiohttp_app(
        stub,
        resident=False,
        coalesce=False,
        generator=lambda: DecodeEngine(
            model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8)
        ),
    )
    expected = solo(model, variables, [3, 1, 4], 5)

    async def main():
        import json as _json

        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate", json={"prompt_ids": [3, 1, 4], "max_new_tokens": 5, "stream": True}
            )
            assert resp.status == 200
            assert resp.content_type == "application/x-ndjson"
            lines = [_json.loads(l) for l in (await resp.text()).strip().splitlines()]

            resp = await client.post(
                "/generate", json={"prompts": [[1, 2]], "max_new_tokens": 2, "stream": True}
            )
            assert resp.status == 400  # streaming is single-prompt only
            return lines
        finally:
            await client.close()

    lines = asyncio.run(main())
    assert [l["token"] for l in lines[:-1]] == expected
    assert lines[-1] == {"done": True, "tokens": expected, "request_id": lines[-1]["request_id"]}


def test_abandoned_stream_frees_slot_and_worker_survives(gpt):
    """Closing a stream early (client disconnect) must cancel its decode slot;
    other in-flight requests keep decoding correctly on the surviving worker."""
    import time as _time

    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine)
    expected = solo(model, variables, [2, 7], 4)

    async def main():
        stream_it = batcher.stream([3, 1, 4], 60)  # long budget on the ONLY slot
        first = [await anext(stream_it), await anext(stream_it)]
        await stream_it.aclose()  # abandon mid-decode
        # the slot must come free for the next request (worker still alive)
        return first, await batcher.generate([2, 7], 4)

    try:
        first, second = asyncio.run(main())
    finally:
        batcher.close()
    assert first == solo(model, variables, [3, 1, 4], 60)[:2]
    assert second == expected
    assert engine.num_active == 0


def test_batcher_concurrent_requests_match_solo(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8))
    batcher = ContinuousBatcher(engine)
    requests = [([3, 1, 4], 5), ([2, 7], 4), ([1, 8, 2, 8], 3), ([6], 6)]
    expected = [solo(model, variables, p, n) for p, n in requests]

    async def main():
        return await asyncio.gather(*(batcher.generate(p, n) for p, n in requests))

    try:
        results = asyncio.run(main())
    finally:
        batcher.close()
    assert results == expected


# ------------------------------------------------------------------- lookahead


def test_lookahead_matches_sequential_greedy(gpt):
    """A fused K-step burst emits exactly what K sequential steps would."""
    model, variables = gpt
    requests = [([3, 1, 4, 1, 5], 9), ([2, 7], 6), ([1, 8, 2, 8], 4)]

    def run(lookahead):
        engine = DecodeEngine(model, variables, num_slots=3, max_len=64, prefill_buckets=(8,))
        slots = {engine.add_request(p, n): i for i, (p, n) in enumerate(requests)}
        out = {i: [] for i in range(3)}
        while engine.num_active:
            for ev in engine.step(lookahead):
                if ev.emit:
                    out[slots[ev.slot]].append(ev.token)
        return out, engine._active.copy(), engine._lens_host.copy()

    seq_out, seq_active, seq_lens = run(1)
    for k in (3, 8, 64):
        burst_out, burst_active, burst_lens = run(k)
        assert burst_out == seq_out, f"lookahead={k}"
        np.testing.assert_array_equal(burst_active, seq_active)
        np.testing.assert_array_equal(burst_lens, seq_lens)


def test_lookahead_matches_sequential_sampled(gpt):
    """Key chaining inside the scan reproduces the sequential sample stream."""
    model, variables = gpt
    prompt = [3, 1, 4, 1, 5]
    a = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
                     temperature=0.8, seed=7)
    b = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
                     temperature=0.8, seed=7)
    assert a.generate(prompt, 10) == b.generate(prompt, 10, lookahead=4)


def test_lookahead_eos_retires_midburst(gpt):
    """A slot hitting eos inside a burst stops emitting and frees, exactly."""
    model, variables = gpt
    prompt = [3, 1, 4, 1, 5]
    expected = solo(model, variables, prompt, 6)
    eos = expected[2]
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
                          eos_token_id=eos)
    assert engine.generate(prompt, 6, lookahead=6) == expected[: expected.index(eos)]
    assert engine.num_active == 0


def test_lookahead_capacity_force_finish(gpt):
    """Cache-room clamp inside the scan force-finishes like the host rule."""
    model, variables = gpt
    prompt = [1, 2, 3, 4]
    engine = DecodeEngine(model, variables, num_slots=1, max_len=16, prefill_buckets=(4, 8))
    out = engine.generate(prompt, 100, lookahead=32)
    budget = 16 - 1 - len(prompt)
    assert len(out) == budget
    assert out == solo(model, variables, prompt, budget)


def test_lookahead_int8_quantized_engine(gpt):
    """Lookahead composes with int8 weight-only quantization."""
    model, variables = gpt
    prompt = [3, 1, 4, 1, 5]
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(8,),
                          quantize="int8")
    assert engine.generate(prompt, 8, lookahead=4) == engine.generate(prompt, 8, lookahead=1)


def test_batcher_lookahead_matches_solo(gpt):
    """End-to-end: a lookahead batcher resolves the same tokens as generate."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(8,))
    batcher = ContinuousBatcher(engine, lookahead=4)
    prompts = [([3, 1, 4, 1, 5], 7), ([2, 7], 5)]

    async def go():
        return await asyncio.gather(
            *(batcher.generate(p, n) for p, n in prompts)
        )

    results = asyncio.new_event_loop().run_until_complete(go())
    batcher.close()
    assert results == [solo(model, variables, p, n) for p, n in prompts]


def test_generate_route_sampling_params(gpt):
    """HTTP sampling controls: top_k=1 reduces to greedy; bad params 400."""
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="gen-app-sampling", artifact=object())
    app = build_aiohttp_app(
        stub,
        resident=False,
        coalesce=False,
        generator=lambda: DecodeEngine(
            model, variables, num_slots=2, max_len=64, prefill_buckets=(8,)
        ),
        generate_lookahead=4,
    )
    expected = solo(model, variables, [3, 1, 4], 5)

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate",
                json={"prompt_ids": [3, 1, 4], "max_new_tokens": 5,
                      "temperature": 0.9, "top_k": 1},
            )
            assert resp.status == 200, await resp.text()
            assert (await resp.json())["tokens"] == expected

            for bad in (
                {"temperature": -1},
                {"top_k": -2},
                {"top_p": 0},
                {"top_p": "high"},
            ):
                resp = await client.post(
                    "/generate", json={"prompt_ids": [3, 1, 4], "max_new_tokens": 2, **bad}
                )
                assert resp.status == 400, (bad, await resp.text())
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(main())


def test_last_chunk_of_a_chunked_prefill_takes_the_smallest_bucket_that_holds_it(gpt_tiny_session):
    """An 11-token prompt in chunks of 8: its last 3 tokens run through the
    4-token program, not through 8 of which 5 are padding; the tokens are those
    of the unchunked engine, paged and dense."""
    _, model, variables = gpt_tiny_session
    prompt = [(7 * i + 3) % 256 for i in range(11)]
    want = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(16,)).generate(prompt, 6)
    for paged in (True, False):
        engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8, 16),
                              prefill_chunk=8, paged=paged)
        assert engine.generate(prompt, 6) == want
        chunk_fn = engine._paged_chunk_fn if paged else engine._chunk_fn
        assert chunk_fn._cache_size() == 2  # the (1, 8) and the (1, 4) program


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_a_prompt_that_chunks_serve_needs_no_bucket_that_holds_it_whole(gpt_tiny_session, paged):
    """``prefill_chunk`` 8 with buckets of 4 and 8: a 19-token prompt runs as
    chunks of 8, 8 and 4 and asks for no 32-token bucket (none is configured, so
    no program of that width exists); its tokens are the unchunked engine's.
    What no path takes is still refused: the same prompt without
    ``prefill_chunk``, and one whose chunks overrun the slot."""
    _, model, variables = gpt_tiny_session
    prompt = [(5 * i + 1) % 256 for i in range(19)]
    want = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(32,)).generate(prompt, 5)
    engine = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8),
                          prefill_chunk=8, paged=paged)
    engine.check_prefillable(19)
    assert engine.generate(prompt, 5) == want
    with pytest.raises(ValueError, match="largest prefill bucket"):
        DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(4, 8)).check_prefillable(19)
    engine.check_prefillable(60)  # eight chunks of 8 end at max_len
    tight = DecodeEngine(model, variables, num_slots=1, max_len=60, prefill_buckets=(4, 8), prefill_chunk=8,
                         paged=paged)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        tight.check_prefillable(57)  # its eighth chunk would end at 64, past the slot's 60 rows
