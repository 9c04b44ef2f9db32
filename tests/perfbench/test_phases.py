"""The program's phase spans (``loop.*``, ``fit.*``) in a profile made here on
the CPU, read by ``perfbench.trace.read``: they are on the trace's clock, they
agree with the program's own phase counters, and the five per-layer readers
that read either give a number from them — and nothing from a program that has
neither."""

import asyncio
import inspect
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, phases, trace

SERVE_CELL, TRAIN_CELL = "gpt2-medium.decode-closed", "gpt2-small-lm.packed-train"


def reader(cell, metric):
    return manifest.Cell(manifest.load(), cell).reader(metric)


def spans(summary, name):
    return sorted((start, end) for _, event, start, end in summary.host if event == name)


def agree(found, counted_s):
    """Spans against the counters' seconds: to 5%, or to the 5 us an occurrence
    that opening a traced span takes after the counter's stamp (the toy phases
    here last tens of microseconds; on the chip they last milliseconds)."""
    return abs(sum(e - s for s, e in found) - counted_s) <= 0.05 * counted_s + 5e-6 * len(found)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A toy engine behind its batcher and a toy ``fit``, both whole inside one
    profiler session: the trace, the engine's ``pipeline_stats`` before and
    after, and the ``fit`` calls' results."""
    from unionml_tpu.models import GPTConfig, GPTLMHeadModel, MLPClassifier, create_train_state
    from unionml_tpu.models.gpt import init_params
    from unionml_tpu.models.training import fit
    from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine

    config = GPTConfig.tiny(dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    engine = DecodeEngine(GPTLMHeadModel(config), init_params(config, seq_len=16),
                          num_slots=2, max_len=64, prefill_buckets=(8,))
    engine.generate([3, 1, 4], 3)  # compiled before the session
    engine.timeline.leave()
    rng = np.random.default_rng(0)
    data = {"inputs": rng.normal(size=(512, 8)).astype(np.float32),
            "labels": rng.integers(0, 2, size=512).astype(np.int32)}
    mlp = MLPClassifier(hidden_sizes=(8,), num_classes=2)
    state = create_train_state(mlp, mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))),
                               learning_rate=1e-2)
    state = fit(state, data, batch_size=16, num_epochs=1, log_every=1000).state

    async def serve(batcher):
        return await asyncio.gather(*(batcher.generate([5, 9, 2, i + 1], 12) for i in range(4)))

    log_dir = str(tmp_path_factory.mktemp("profile"))
    before = engine.pipeline_stats()
    jax.profiler.start_trace(log_dir)
    try:
        batcher = ContinuousBatcher(engine)
        try:
            answers = asyncio.run(serve(batcher))
        finally:
            batcher.close()  # the worker leaves its loop: every span is closed
        fits = []
        for _ in range(2):  # the step donates its state: each call takes the one before's
            fits.append(fit(state, data, batch_size=16, num_epochs=2, log_every=1000))
            state = fits[-1].state
    finally:
        jax.profiler.stop_trace()
    assert [len(a) for a in answers] == [12] * 4
    return {"trace": trace.read(log_dir), "before": before, "after": engine.pipeline_stats(),
            "fits": fits}


def test_program_spans_are_host_events_on_the_traces_clock(profiled):
    summary = profiled["trace"]
    names = {event for _, event, _, _ in summary.host}
    assert {"loop.dispatch", "loop.fetch_wait", "loop.apply", "loop.admit", "loop.prefill",
            "loop.plan", "loop.fan_out", "fit.start", "fit.input_wait", "fit.dispatch",
            "fit.drain", "fit.finish"} <= names
    # one phase at a time: the spans of a loop never overlap
    for loop in ("loop.", "fit."):
        ordered = sorted((s, e) for _, event, s, e in summary.host if event.startswith(loop))
        assert all(b[0] >= a[1] - 1e-9 for a, b in zip(ordered, ordered[1:]))
        assert all(summary.window[0] <= s <= e <= summary.window[1] for s, e in ordered)


@pytest.mark.parametrize("phase", ["dispatch", "fetch_wait", "apply"])
def test_loop_spans_agree_with_the_phase_counters(profiled, phase):
    before, after = profiled["before"]["phases"][phase], profiled["after"]["phases"][phase]
    found = spans(profiled["trace"], f"loop.{phase}")
    assert len(found) == after["entries"] - before["entries"]
    assert agree(found, after["seconds"] - before["seconds"])


@pytest.mark.parametrize("phase", ["input_wait", "dispatch"])
def test_fit_spans_agree_with_the_fit_results_sums(profiled, phase):
    counted = sum(result.phase_seconds[phase] for result in profiled["fits"])
    assert agree(spans(profiled["trace"], f"fit.{phase}"), counted)


def test_the_readers_give_numbers_from_this_context(profiled):
    stats = {key: {"generation": {"pipeline": profiled[which]}}
             for key, which in (("stats_open", "before"), ("stats_close", "after"))}
    ctx = {"load": stats, "trace": profiled["trace"]}
    after, before = profiled["after"], profiled["before"]
    steps = after["step_dispatches"] - before["step_dispatches"]

    host_ms = reader(SERVE_CELL, "loop_host_ms_per_step")(ctx)
    work = sum(after["phases"][p]["seconds"] - before["phases"][p]["seconds"]
               for p in ("admit", "prefill", "plan", "dispatch", "apply", "fan_out"))
    assert math.isfinite(host_ms) and host_ms == pytest.approx(1e3 * work / steps)

    longest_ms = reader(SERVE_CELL, "loop_phase_max_ms")(ctx)
    stays = [e - s for _, event, s, e in profiled["trace"].host
             if event.startswith("loop.") and event != "loop.idle"]
    # the upper edge of the longest stay's power-of-two bucket, in ms
    assert math.isfinite(longest_ms) and max(stays) * 1e3 < longest_ms * 1.05
    assert longest_ms <= max(2 * max(stays) * 1e3 * 1.05, 2e-3)
    # the same over the working phases alone: no fetch_wait, so never longer
    work_ms = reader(SERVE_CELL, "loop_work_max_ms")(ctx)
    worked = [e - s for _, event, s, e in profiled["trace"].host
              if event.startswith("loop.") and event[5:] in phases.LOOP_WORK]
    assert math.isfinite(work_ms) and work_ms <= longest_ms
    assert max(worked) * 1e3 < work_ms * 1.05 and work_ms <= max(2 * max(worked) * 1e3 * 1.05, 2e-3)

    share = reader(TRAIN_CELL, "fit_input_wait_share")(ctx)
    waited = sum(e - s for s, e in spans(profiled["trace"], "fit.input_wait"))
    assert math.isfinite(share) and 0 < share < 100
    assert share == pytest.approx(100 * waited / profiled["trace"].window_s)

    overhead_ms = reader(TRAIN_CELL, "fit_call_overhead_ms")(ctx)
    per_call = [r.phase_seconds["start"] + r.phase_seconds["finish"] for r in profiled["fits"]]
    assert math.isfinite(overhead_ms) and overhead_ms == pytest.approx(1e3 * np.mean(per_call), rel=0.05)


def test_the_readers_are_silent_without_the_programs_part(profiled):
    """The parent's ``/stats`` has no ``phases`` and its trace no ``fit.*`` span:
    each reader returns nothing and does not raise."""
    bare = {"step_dispatches": 7, "idle_dispatches": 0, "depth": 1, "inflight": False}
    stats = {"generation": {"pipeline": bare}}
    empty = trace.TraceSummary(devices=[], host=[("python", "PjitFunction(train_step)", 0.0, 0.1)],
                               window=(0.0, 1.0))
    for ctx in ({"load": {"stats_open": stats, "stats_close": stats}, "trace": empty},
                {"load": {"stats_open": stats, "stats_close": stats}, "trace": None}):
        assert reader(SERVE_CELL, "loop_host_ms_per_step")(ctx) is None
        assert reader(SERVE_CELL, "loop_phase_max_ms")(ctx) is None
        assert reader(SERVE_CELL, "loop_work_max_ms")(ctx) is None
        assert reader(TRAIN_CELL, "fit_input_wait_share")(ctx) is None
        assert reader(TRAIN_CELL, "fit_call_overhead_ms")(ctx) is None
    # a call cut by the trace's edge (a finish without its start) is no call
    cut = trace.TraceSummary([], [("python", "fit.finish", 0.0, 0.1), ("python", "fit.start", 0.2, 0.3)],
                             (0.0, 1.0))
    assert reader(TRAIN_CELL, "fit_call_overhead_ms")({"trace": cut}) is None
    # at the trace reader's cap on host events spans may be missing: no number
    cap = inspect.signature(trace.read).parameters["max_host_events"].default
    spans_of_a_call = [("python", "fit.start", 0.0, 0.1), ("python", "fit.input_wait", 0.1, 0.2),
                       ("python", "fit.finish", 0.2, 0.3)]
    for n, silent in ((cap - 3, False), (cap, True)):
        held = trace.TraceSummary([], spans_of_a_call + [("python", "other", 0.0, 0.1)] * (n - 3),
                                  (0.0, 1.0))
        assert (reader(TRAIN_CELL, "fit_input_wait_share")({"trace": held}) is None) == silent
        assert (reader(TRAIN_CELL, "fit_call_overhead_ms")({"trace": held}) is None) == silent


def test_the_span_readers_are_silent_where_the_cap_cannot_be_read(monkeypatch):
    """A trace reader without the ``max_host_events`` parameter: no number rests
    on a guessed cap, and no reader raises."""
    a_call = trace.TraceSummary([], [("python", "fit.start", 0.0, 0.1), ("python", "fit.input_wait", 0.1, 0.2),
                                     ("python", "fit.finish", 0.2, 0.3)], (0.0, 1.0))
    assert reader(TRAIN_CELL, "fit_call_overhead_ms")({"trace": a_call}) == pytest.approx(200.0)
    monkeypatch.setattr(trace, "read", lambda log_dir: None)
    assert reader(TRAIN_CELL, "fit_input_wait_share")({"trace": a_call}) is None
    assert reader(TRAIN_CELL, "fit_call_overhead_ms")({"trace": a_call}) is None
