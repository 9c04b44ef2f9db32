"""A bucketed admission wave of the paged engine is one upload and one program.

What is pinned here:

1. THE COUNT — a warmed ``admit_many`` of one bucket runs with implicit
   host→device transfers DISALLOWED, issues exactly one ``jax.device_put`` and
   one dispatch (``_prefill_wave_fn``) whatever its rows, never touches the
   one-slot programs (``_write_row_fn``, ``_slot_update_fn``), and raises
   ``wave_device_calls`` by 2; the pool gauges refresh once a wave.
2. PARITY — the wave writes the table rows, the KV, the lengths, the logits
   AND the slot mirrors: greedy streams equal ``gpt.generate``'s, fixed-seed
   sampled streams with per-request controls equal the dense engine's (whose
   mirrors the point-update sets), and on the int8 pool a wave of several rows
   equals the same requests admitted a row a wave.
3. THE FAILURE RULE — an injected prefill fault fires before anything is
   donated and unwinds cleanly (slots and blocks released, engine usable); an
   exception out of the dispatch itself is a full engine failure.
4. PIPELINING — a wave admitted while a decode burst is in flight does not
   disturb that burst's tokens.
"""

import jax
import numpy as np
import pytest

from unionml_tpu.serving.continuous import DecodeEngine
from unionml_tpu.serving.faults import FaultError, FaultPlan
from unionml_tpu.serving.telemetry import Telemetry

BUCKETS = (4, 8, 16)
#: mixed buckets: rows of 3 and 4 share bucket 4, 7 takes 8, 9 and 13 take 16
PROMPTS = [[3, 1, 4], [2, 7, 1, 8], [5, 9, 2, 6, 5, 3, 5], list(range(20, 29)), list(range(40, 53))]
SAMPLING = [
    dict(temperature=0.9, top_k=3),
    dict(temperature=0.7, top_p=0.8),
    dict(temperature=1.1, top_k=5, top_p=0.9),
    dict(temperature=0.0),
    dict(temperature=0.8),
]


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def make_engine(gpt, **kw):
    model, variables = gpt
    kw.setdefault("num_slots", 6)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", BUCKETS)
    kw.setdefault("prefix_block_size", 4)
    return DecodeEngine(model, variables, **kw)


def run(engine, requests, lookahead=1):
    """Admit ``requests`` in one call, decode to the end: streams by request."""
    slots = engine.admit_many(requests)
    streams = {slot: [] for slot in slots}
    while engine.num_active or engine.has_pending_events:
        for ev in engine.step(lookahead):
            if ev.emit:
                streams[ev.slot].append(ev.token)
    return [streams[slot] for slot in slots]


def assert_released(engine):
    assert engine.num_active == 0 and len(engine.free_slots) == engine.num_slots
    assert engine._allocator.slot_blocks == 0 and not engine._slot_block_map


# ---------------------------------------------------------------- the count


@pytest.mark.parametrize("rows", [1, 4], ids=["one-row", "prefill-batch-rows"])
def test_wave_is_one_explicit_upload_and_one_dispatch(gpt, monkeypatch, rows):
    engine = make_engine(gpt, prefill_batch=4, telemetry=Telemetry())
    prompts = [[7 + r, 1, 4, 1, 5] for r in range(rows)]  # one bucket (8)
    run(engine, [(p, 3) for p in prompts])  # compile the (rows, 8) wave and the step
    assert_released(engine)

    calls = {"device_put": 0, "wave": 0, "gauges": 0}

    def counting(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)

        return wrapper

    def never(*args, **kwargs):
        raise AssertionError("a bucketed wave ran a one-slot program")

    monkeypatch.setattr(jax, "device_put", counting("device_put", jax.device_put))
    engine._prefill_wave_fn = counting("wave", engine._prefill_wave_fn)
    engine._note_pool_gauges = counting("gauges", engine._note_pool_gauges)
    engine._write_row_fn = engine._slot_update_fn = engine._prefill_fn = never
    before = engine.pipeline_stats()
    with jax.transfer_guard_host_to_device("disallow"):
        slots = engine.admit_many([(p, 3) for p in prompts])
    engine.timeline.enter("admit")  # as the batcher does: a stay is counted when it ends
    after = engine.pipeline_stats()
    assert calls == {"device_put": 1, "wave": 1, "gauges": 1}
    assert after["wave_device_calls"] - before["wave_device_calls"] == 2
    assert after["phases"]["prefill"]["entries"] - before["phases"]["prefill"]["entries"] == 1
    assert engine.num_active == rows and engine.prefill_dispatches == 2
    # the program set what the point-updates used to: table rows and mirrors
    assert np.asarray(engine._active_dev)[slots].all()
    assert np.asarray(engine._remaining_dev)[slots].tolist() == [3] * rows
    tables = np.asarray(engine._tables)
    for slot in slots:
        owned = engine._slot_block_map[slot]
        assert [tables[slot, col] for col in sorted(owned)] == [owned[col] for col in sorted(owned)]
        assert (tables[slot, len(owned):] == engine._scratch_block).all()


def test_dense_engine_wave_is_not_counted(gpt):
    engine = make_engine(gpt, paged=False)
    run(engine, [([3, 1, 4], 2)])
    assert engine.prefill_dispatches == 1 and engine.pipeline_stats()["wave_device_calls"] == 0


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("rows", [1, 2, 3, 4])
def test_greedy_streams_equal_generate_for_mixed_buckets(gpt, gpt_tiny_solo, rows):
    engine = make_engine(gpt, prefill_batch=rows)
    streams = run(engine, [(p, 6) for p in PROMPTS])
    assert streams == [gpt_tiny_solo(p, 6) for p in PROMPTS]
    # 2 prompts in bucket 4, 1 in 8, 2 in 16
    waves = -(-2 // rows) + 1 + -(-2 // rows)
    assert engine.prefill_dispatches == waves and engine.wave_device_calls == 2 * waves
    assert_released(engine)


@pytest.mark.parametrize("rows", [1, 4])
def test_sampled_streams_with_per_request_controls_equal_the_dense_engines(gpt, rows):
    """The sampling program reads ``temp`` / ``top_k`` / ``top_p`` from the
    device mirrors alone: set by the wave here, by ``_slot_update`` there."""
    requests = [(p, 8, s) for p, s in zip(PROMPTS, SAMPLING)]
    paged = run(make_engine(gpt, prefill_batch=rows, seed=11, temperature=0.5), requests)
    dense = run(make_engine(gpt, prefill_batch=rows, seed=11, temperature=0.5, paged=False), requests)
    assert paged == dense
    greedy_row = SAMPLING.index(dict(temperature=0.0))
    assert len({tuple(s) for s in paged}) > 1 and len(paged[greedy_row]) == 8


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_int8_pool_wave_of_rows_equals_a_row_a_wave(gpt, sampled):
    """Rows are independent and a block's scale is its own row's: what a wave
    of four quantizes is what four waves of one do, token for token; and the
    int8 streams stay inside the pinned divergence budget of the bf16 pool's."""
    from unionml_tpu.ops.quant import KV_INT8_GREEDY_DIVERGENCE_BUDGET

    requests = [(p, 8, s if sampled else {}) for p, s in zip(PROMPTS, SAMPLING)]
    kw = dict(seed=5, temperature=0.6 if sampled else 0.0)
    together = run(make_engine(gpt, prefill_batch=4, kv_quantize="int8", **kw), requests)
    alone = run(make_engine(gpt, prefill_batch=1, kv_quantize="int8", **kw), requests)
    assert together == alone
    full = run(make_engine(gpt, prefill_batch=4, **kw), requests)
    total = diverged = 0
    for a, b in zip(together, full):
        m = min(len(a), len(b))
        first = next((i for i in range(m) if a[i] != b[i]), m)
        total += m
        diverged += m - first
    assert total > 0 and diverged / total <= KV_INT8_GREEDY_DIVERGENCE_BUDGET


# --------------------------------------------------------- the failure rule


def test_injected_prefill_fault_unwinds_cleanly(gpt, gpt_tiny_solo):
    """``check_prefill`` stands in front of the upload and the dispatch: the
    call's blocks are swept, nothing was donated, the engine serves on."""
    engine = make_engine(gpt, prefill_batch=2, faults=FaultPlan(prefill_failures=(2,)))
    survivor = engine.add_request([9, 9, 1], 6)  # prefill #1
    with pytest.raises(FaultError):
        engine.admit_many([([3, 1, 4], 5), ([2, 7, 5], 5)])  # prefill #2: injected
    assert engine.failure_count == 0 and not engine._device_poisoned
    assert engine.num_active == 1 and set(engine._slot_block_map) == {survivor}
    assert engine.wave_device_calls == 2  # the failed wave issued nothing
    slots = engine.admit_many([([3, 1, 4], 5), ([2, 7, 5], 5)])
    streams = {slot: [] for slot in [survivor] + slots}
    while engine.num_active or engine.has_pending_events:
        for ev in engine.step():
            if ev.emit:
                streams[ev.slot].append(ev.token)
    assert streams[survivor] == gpt_tiny_solo([9, 9, 1], 6)
    assert [streams[s] for s in slots] == [gpt_tiny_solo([3, 1, 4], 5), gpt_tiny_solo([2, 7, 5], 5)]
    assert_released(engine)


def test_dispatch_failure_escalates_to_an_engine_failure(gpt, gpt_tiny_solo):
    """The wave's program donates the pool, the tables, the lengths, the
    logits and the mirrors: whatever it raises, they count as consumed."""
    engine = make_engine(gpt)
    engine.add_request([9, 9, 1], 6)
    wave_fn = engine._prefill_wave_fn

    def boom(*args, **kwargs):
        raise RuntimeError("wave dispatch died")

    engine._prefill_wave_fn = boom
    with pytest.raises(RuntimeError, match="wave dispatch died"):
        engine.admit_many([([3, 1, 4], 5)])
    assert engine.failure_count == 1 and not engine._device_poisoned and not engine.failed
    salvage = engine.take_salvage()  # the decoding sibling, for its resume
    assert [(s.tokens, s.remaining) for s in salvage] == [([9, 9, 1], 6)]
    engine._prefill_wave_fn = wave_fn
    assert run(engine, [([3, 1, 4], 5)]) == [gpt_tiny_solo([3, 1, 4], 5)]  # rebuilt in place


# ---------------------------------------------------------------- pipelining


@pytest.mark.parametrize("lookahead", [1, 4])
def test_wave_under_an_inflight_burst_leaves_its_tokens_alone(gpt, gpt_tiny_solo, lookahead):
    """The wave donates the tables and the mirrors the dispatched-but-unfetched
    burst reads: that burst keeps the arrays it was handed."""
    engine = make_engine(gpt, pipeline=True, prefill_batch=4)
    first = [([3, 1, 4, 1, 5], 12), ([2, 7], 12)]
    slots = engine.admit_many(first)
    streams = {slot: [] for slot in slots}
    late = None
    for tick in range(64):
        if tick == 2:
            assert engine._inflight is not None
            late = engine.admit_many([([5, 9, 2, 6], 7), ([8, 8, 3], 7)])
            streams.update({slot: [] for slot in late})
        for ev in engine.step(lookahead):
            if ev.emit:
                streams[ev.slot].append(ev.token)
        if late and not (engine.num_active or engine.has_pending_events):
            break
    assert [streams[s] for s in slots] == [gpt_tiny_solo(p, n) for p, n in first]
    assert [streams[s] for s in late] == [gpt_tiny_solo([5, 9, 2, 6], 7), gpt_tiny_solo([8, 8, 3], 7)]
    assert_released(engine)
