"""The flat phase timeline (``profiling.PhaseTimeline``) alone, inside the
serving loop (``ContinuousBatcher`` over a CPU engine) and inside ``fit``."""

import asyncio
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.profiling import PHASE_BUCKETS, PhaseTimeline, phase_bucket
from unionml_tpu.serving.continuous import LOOP_PHASES, ContinuousBatcher, DecodeEngine


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


# ------------------------------------------------------------ the timeline alone


@pytest.mark.parametrize("seconds,bucket", [
    (0.0, 0), (0.9e-6, 0), (1e-6, 1), (1e-3, 10), (10.0, 24), (1e9, PHASE_BUCKETS - 1)])
def test_bucket_is_the_bit_length_of_the_duration_in_microseconds(seconds, bucket):
    assert phase_bucket(seconds) == bucket
    if 0 < bucket < PHASE_BUCKETS - 1:
        assert 2 ** (bucket - 1) <= seconds * 1e6 < 2 ** bucket


def test_one_phase_at_a_time_and_the_sums_are_the_wall_time():
    timeline = PhaseTimeline("loop", ("a", "b", "c"))
    assert timeline.current is None
    first = timeline.snapshot()
    assert all(v == {"seconds": 0.0, "entries": 0, "buckets": [0] * PHASE_BUCKETS}
               for v in first.values())
    began = timeline.enter("a")
    for _ in range(20):
        assert timeline.enter("b", rows=2) >= began and timeline.current == "b"
        time.sleep(0.002)
        timeline.enter("a")
        time.sleep(0.001)
    mid = timeline.snapshot()
    mid_at = time.perf_counter()
    time.sleep(0.01)
    ended = timeline.leave()
    last = timeline.snapshot()
    assert timeline.current is None
    # entering a phase ended the one before: 21 stays in a, 20 in b, none in c
    assert [last[p]["entries"] for p in "abc"] == [21, 20, 0]
    assert all(sum(v["buckets"]) == v["entries"] for v in last.values())
    assert last["c"]["seconds"] == 0.0
    # every instant between the first enter and leave belongs to one phase
    assert sum(v["seconds"] for v in last.values()) == pytest.approx(ended - began, rel=1e-6)
    # and a read while a phase runs counts it up to the read
    assert sum(v["seconds"] for v in mid.values()) == pytest.approx(mid_at - began, rel=0.01)
    assert mid["a"]["entries"] == 20 and last["a"]["seconds"] > mid["a"]["seconds"]
    # 2 ms sleeps land in the buckets of 2-4 ms or just above
    assert sum(last["b"]["buckets"][12:14]) >= 15
    # after leave nothing runs: a later read adds nothing
    time.sleep(0.005)
    assert timeline.snapshot() == last


def test_a_reader_thread_never_sees_a_torn_entry():
    timeline = PhaseTimeline("loop", ("a", "b"))
    stop = threading.Event()
    seen = []

    def reader():
        while not stop.is_set():
            snap = timeline.snapshot()
            seen.append((time.perf_counter(), snap))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    thread = threading.Thread(target=reader)
    try:
        began = timeline.enter("a")
        thread.start()
        deadline = began + 0.5
        while time.perf_counter() < deadline:
            timeline.enter("b")
            timeline.enter("a")
    finally:
        stop.set()
        thread.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not thread.is_alive() and len(seen) > 10
    previous = None
    for at, snap in seen:
        total = sum(v["seconds"] for v in snap.values())
        entries = [snap[p]["entries"] for p in ("a", "b")]
        for v in snap.values():
            assert sum(v["buckets"]) == v["entries"]
            # finished stays alone account for at least their buckets' lower edges
            floor = sum(n * 2 ** (k - 1) for k, n in enumerate(v["buckets"]) if k) * 1e-6
            assert v["seconds"] >= floor
        # a and b alternate, a first: a torn pair of phases would break this
        assert entries[0] - entries[1] in (0, 1)
        assert total <= at - began + 1e-4
        if previous is not None:
            assert total >= previous[0] and entries[0] >= previous[1][0] and entries[1] >= previous[1][1]
        previous = (total, entries)


# ------------------------------------------------------------- the serving loop


def test_serving_loop_phases_close_over_the_loop_threads_time(gpt):
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8))
    counted = []
    dispatch = engine._dispatch_step

    def counting_dispatch(lookahead):
        active = int(engine._active.sum())
        out = dispatch(lookahead)
        counted.append(active * out[3])
        return out

    engine._dispatch_step = counting_dispatch
    batcher = ContinuousBatcher(engine)

    async def main():
        first = await asyncio.gather(batcher.generate([3, 1, 4], 6), batcher.generate([2, 7], 4))
        await asyncio.sleep(0.05)  # the loop goes idle between the two rounds
        before, at = engine.pipeline_stats(), time.perf_counter()
        second = await asyncio.gather(*(batcher.generate([5, 9, 2, 6], 5) for _ in range(3)))
        await asyncio.sleep(0.05)
        return first, second, before, at

    try:
        first, second, before, before_at = asyncio.run(main())
        after, after_at = engine.pipeline_stats(), time.perf_counter()
    finally:
        batcher.close()
    assert [len(t) for t in first] == [6, 4] and [len(t) for t in second] == [5, 5, 5]
    assert not any(key.startswith("ema_") for key in after)
    phases = after["phases"]
    assert tuple(phases) == LOOP_PHASES
    for name in LOOP_PHASES:
        assert phases[name]["entries"] > 0, name
        assert sum(phases[name]["buckets"]) == phases[name]["entries"]
    steps = after["step_dispatches"] - before["step_dispatches"]
    assert steps > 0
    assert phases["dispatch"]["entries"] - before["phases"]["dispatch"]["entries"] == steps
    assert phases["dispatch"]["entries"] == after["step_dispatches"] == len(counted)
    assert after["active_slot_steps"] == sum(counted)
    assert 0 < after["active_slot_steps"] <= 2 * after["step_dispatches"]
    # every fetch is followed by its apply
    assert phases["fetch_wait"]["entries"] == phases["apply"]["entries"]
    # two reads difference into a window whose phases add up to its length
    window = sum(phases[p]["seconds"] - before["phases"][p]["seconds"] for p in LOOP_PHASES)
    assert window == pytest.approx(after_at - before_at, rel=0.01)
    # the worker thread left its loop: nothing runs, a later read adds nothing
    assert engine.timeline.current is None
    assert engine.pipeline_stats()["phases"] == engine.pipeline_stats()["phases"]


def test_direct_engine_drive_and_flush_return_to_the_asking_phase(gpt):
    """``generate`` and a cancel's out-of-band flush: the flush passes through
    ``fetch_wait`` and ``apply`` and the loop is back where it was."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=2, max_len=64, prefill_buckets=(4, 8))
    assert len(engine.generate([3, 1, 4], 5)) == 5
    phases = engine.pipeline_stats()["phases"]
    assert phases["prefill"]["entries"] == 1 and phases["idle"]["entries"] == 0
    assert phases["dispatch"]["entries"] == engine.step_dispatches
    slot = engine.add_request([2, 7], 8)
    engine.step()
    engine.timeline.enter("fan_out")
    fetched = engine.pipeline_stats()["phases"]["fetch_wait"]["entries"]
    engine.cancel(slot)  # a step is in flight: the cancel flushes it
    assert engine.timeline.current == "fan_out"
    assert engine.pipeline_stats()["phases"]["fetch_wait"]["entries"] == fetched + 1


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_live_block_steps_weights_each_active_slot_by_its_table_columns(gpt, paged):
    """Beside ``active_slot_steps``: every dispatch adds, for each active slot,
    the table columns its row holds keys in (``lens // block_size + 1``) — the
    columns the paged kernel's bounded walk visits. A dense engine has no table
    and adds nothing."""
    model, variables = gpt
    block = 4
    engine = DecodeEngine(model, variables, num_slots=3, max_len=64, prefill_buckets=(4, 8, 16),
                          prefix_block_size=block, paged=paged)
    expected, slots = [0], [0]
    dispatch = engine._dispatch_step

    def counting_dispatch(lookahead):
        lens = engine._lens_host[engine._active]
        out = dispatch(lookahead)
        expected[0] += int((lens // block + 1).sum()) * out[3]
        slots[0] += len(lens) * out[3]
        return out

    engine._dispatch_step = counting_dispatch
    engine.admit_many([([3, 1, 4, 1, 5, 9, 2, 6, 5], 9, {}), ([2, 7], 12, {})])
    while engine._active.any():
        engine.step()
    stats = engine.pipeline_stats()
    assert stats["active_slot_steps"] == slots[0] > 0
    if paged:
        assert stats["live_block_steps"] == expected[0]
        # rows of 2 to 17 keys in 4-token blocks: one to five columns each
        assert slots[0] < stats["live_block_steps"] <= 5 * slots[0]
    else:
        assert stats["live_block_steps"] == 0


# ------------------------------------------------------------------------- fit


def test_fit_result_holds_phase_sums_that_add_up_to_the_call(tmp_path):
    from unionml_tpu.models import MLPClassifier, create_train_state
    from unionml_tpu.models.training import FIT_PHASES, fit

    rng = np.random.default_rng(0)
    data = {
        "inputs": rng.normal(size=(256, 8)).astype(np.float32),
        "labels": rng.integers(0, 2, size=256).astype(np.int32),
    }
    mlp = MLPClassifier(hidden_sizes=(8,), num_classes=2)
    params = mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    state = create_train_state(mlp, params, learning_rate=1e-2)
    began = time.perf_counter()
    result = fit(state, data, batch_size=16, num_epochs=3, log_every=7,
                 checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=10)
    wall = time.perf_counter() - began
    assert tuple(result.phase_seconds) == FIT_PHASES
    assert all(seconds > 0 for seconds in result.phase_seconds.values())
    assert sum(result.phase_seconds.values()) == pytest.approx(wall, rel=0.02)
    # the timed loop is everything between the start and the finish
    loop = sum(result.phase_seconds[p] for p in ("input_wait", "dispatch", "log", "checkpoint", "drain"))
    assert loop == pytest.approx(result.wall_time_s, rel=1e-6)
