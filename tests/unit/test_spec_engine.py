"""Production speculative decoding on the paged pool (ISSUE 16).

Tier-1 gate for the SpeculativeEngine. The contract pinned here:

1. EXACTNESS — speculative streams are token-identical to vanilla decode:
   greedy spec == the plain paged DecodeEngine, bitwise, on 1 device and a
   4-device tensor mesh; fixed-seed SAMPLED spec == the γ=0 arm of the same
   engine (vanilla-by-construction: identical round program, zero proposals),
   on fp32 AND int8 pools. Rejection never perturbs the pool: the verify pass
   is read-only and the commit writes exactly the emitted tokens.
2. ADAPTIVITY — acceptance drives γ: a draft that agrees (draft == target)
   ramps γ to ``gamma_max`` and multiplies accepted-tokens-per-target-step
   past 2.5; a hostile draft decays γ to 0 and the
   request degrades to vanilla decode instead of losing to it.
3. SHARED POOL — draft KV rides the SAME block tables/allocator as the
   target: admission arithmetic is unchanged, prefix-cache splices arm
   speculation with zero extra blocks, and every chaos teardown (dispatch
   death, fetch death, NaN quarantine, cancel) leaves zero leaked or
   double-freed blocks with speculation enabled.
4. NO NEW HOST SYNCS — the steady-state round loop pays ZERO host→device
   transfers (γ/EMA updates, acceptance, and tail fallback all resolve
   device-side), pinned with ``jax.transfer_guard``.
5. POLICY — the SLO scheduler chooses speculation per class
   (``SchedulerConfig.speculative_classes``): interactive traffic speculates,
   batch traffic decodes vanilla, through one mixed ContinuousBatcher.
"""

import asyncio

import jax
import numpy as np
import pytest

from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine
from unionml_tpu.serving.faults import FaultPlan
from unionml_tpu.serving.scheduler import SchedulerConfig
from unionml_tpu.serving.speculative import SpeculativeEngine
from unionml_tpu.serving.supervisor import EngineSupervisor

BS = 4


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


@pytest.fixture(scope="module")
def draft_tiny():
    """A genuinely different (smaller) draft over the same vocab."""
    import jax.numpy as jnp

    from unionml_tpu.models import GPTConfig, GPTLMHeadModel
    from unionml_tpu.models.gpt import init_params

    config = GPTConfig.tiny(
        dropout=0.0, dtype=jnp.float32, attention_impl="xla",
        num_layers=1, hidden_size=32, num_heads=2,
    )
    return GPTLMHeadModel(config), init_params(config, seq_len=16)


def _mesh4():
    from unionml_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 CPU devices)")
    return make_mesh({"tensor": 4}, devices=jax.devices()[:4])


ENGINE_KW = dict(
    num_slots=4, max_len=64, prefill_buckets=(4, 8, 16), prefill_chunk=4,
    prefix_cache_blocks=24, prefix_block_size=BS, seed=7, temperature=0.0,
)


def make_spec(gpt, draft_tiny, *, mesh=None, **kw):
    model, variables = gpt
    draft, dvars = draft_tiny
    merged = dict(ENGINE_KW, **kw)
    return SpeculativeEngine(model, variables, draft, dvars, mesh=mesh, **merged)


def make_plain(gpt, *, mesh=None, **kw):
    model, variables = gpt
    return DecodeEngine(model, variables, paged=True, mesh=mesh, **dict(ENGINE_KW, **kw))


def drive(engine, reqs, *, guard=False):
    """Admit ``reqs`` then run the engine dry; returns per-request streams.
    ``guard=True`` wraps the steady-state step loop in a host→device
    transfer guard (acceptance criterion 4)."""
    streams, slot_req = {}, {}
    for rid, (prompt, budget, sampling) in enumerate(reqs):
        (slot,) = engine.admit_many([(prompt, budget, sampling)])
        for ev in engine.take_pending_events():
            if ev.emit:
                streams[slot_req[ev.slot]].append(ev.token)
        slot_req[slot] = rid
        streams[rid] = []

    def loop():
        while engine.num_active or engine.has_pending_prefill or engine.has_pending_events:
            for ev in engine.step(1):
                if ev.emit:
                    streams[slot_req[ev.slot]].append(ev.token)

    if guard:
        with jax.transfer_guard_host_to_device("disallow"):
            loop()
    else:
        loop()
    return streams


def _assert_no_block_leaks(engine):
    assert engine._allocator.slot_blocks == 0, "leaked slot-owned KV blocks"
    stack = list(engine._allocator._root.children.values())
    while stack:
        node = stack.pop()
        assert node.refcount == 0, "leaked prefix-cache reference"
        stack.extend(node.children.values())


PROMPTS = [
    ([1, 2, 3, 4], 10, {}),          # bucket prefill, spec-armed
    ([7, 8, 9], 8, {}),              # bucket prefill, spec-armed
    ([1, 2, 3, 4, 5, 6, 7], 12, {}),  # chunked prefill: decodes vanilla
]


def _spec_reqs(base, **extra):
    return [(p, b, dict(s, speculative=True, **extra)) for p, b, s in base]


# ------------------------------------------------------------------ exactness


@pytest.mark.parametrize("mesh4", [False, True], ids=["1dev", "mesh4"])
def test_spec_greedy_identical_to_vanilla(gpt, draft_tiny, mesh4):
    """Greedy speculative streams == the plain paged engine's, bitwise, with
    mixed armed/chunked-vanilla admissions in one batch."""
    mesh = _mesh4() if mesh4 else None
    ref = drive(make_plain(gpt, mesh=mesh), PROMPTS)
    eng = make_spec(gpt, draft_tiny, mesh=mesh)
    got = drive(eng, _spec_reqs(PROMPTS))
    assert got == ref
    assert eng.spec_round_dispatches > 0, "rounds never ran"
    _assert_no_block_leaks(eng)


def test_spec_streams_identical_across_mesh_shapes(gpt, draft_tiny):
    """The same mixed greedy+sampled schedule emits identical streams on one
    device and on a 4-device tensor mesh (keyed selection is layout-free)."""
    reqs = _spec_reqs(
        [([1, 2, 3, 4], 10, {"temperature": 0.8, "seed": 11}), ([7, 8, 9], 8, {})]
    )
    solo = drive(make_spec(gpt, draft_tiny), reqs)
    meshed = drive(make_spec(gpt, draft_tiny, mesh=_mesh4()), reqs)
    assert solo == meshed


@pytest.mark.parametrize("kv", [None, "int8"], ids=["fp32", "int8"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_spec_on_vs_off_arm_identical(gpt, draft_tiny, kv, sampled):
    """The rejection-sampling equivalence: spec-on
    vs the γ=0 arm (same engine, zero proposals ≡ vanilla decode) emit
    identical streams — greedy and fixed-seed sampled, fp32 and int8 pools."""
    kw = {"temperature": 0.7, "seed": 5} if sampled else {}
    base = [([1, 2, 3, 4], 10, dict(kw)), ([9, 8, 7], 12, dict(kw))]
    on = drive(make_spec(gpt, draft_tiny, kv_quantize=kv), _spec_reqs(base))
    off = drive(make_spec(gpt, draft_tiny, kv_quantize=kv), _spec_reqs(base, gamma=0))
    assert on == off


def test_explicit_seed_reproduces_and_default_seeds_diverge(gpt, draft_tiny):
    req = [([1, 2, 3, 4], 10, {"temperature": 0.9, "seed": 42, "speculative": True})]
    a = drive(make_spec(gpt, draft_tiny), req)
    b = drive(make_spec(gpt, draft_tiny), req)
    assert a == b, "pinned seed must reproduce"
    unseeded = [([1, 2, 3, 4], 10, {"temperature": 0.9, "speculative": True})]
    eng = make_spec(gpt, draft_tiny)
    c = drive(eng, unseeded)
    d = drive(eng, unseeded)  # second admission: derived key differs
    assert c[0] != d[0], "distinct admissions must not replay each other"


# ------------------------------------------------------------------ adaptivity


def test_alpha_one_ramps_gamma_and_multiplies_tokens(gpt):
    """draft == target: γ ramps to gamma_max and accepted-tokens-per-target-
    step passes 2.5."""
    model, variables = gpt
    eng = SpeculativeEngine(
        model, variables, model, variables,
        **dict(ENGINE_KW, max_len=128, prefill_chunk=None, prefix_cache_blocks=48),
    )
    streams = drive(eng, [([1, 2, 3, 4, 5], 60, {"speculative": True})])
    assert len(streams[0]) == 60
    s = eng.speculation_stats()
    assert s["accepted_per_target_step"] > 2.5, s
    # every round before the budget-exhausted last one fully accepted
    assert s["proposed"] - s["accepted"] <= eng._gamma_max, s
    # 60 tokens in far fewer host steps than vanilla's 60
    assert s["round_dispatches"] < 20, s


def test_hostile_draft_decays_gamma_to_vanilla(gpt, draft_tiny):
    """A draft that never agrees drives the EMA down and γ to 0 (sticky):
    steady state stops paying for proposals at all — the never-lose gate."""
    eng = make_spec(gpt, draft_tiny, ema_beta=0.5)
    drive(eng, [([1, 2, 3, 4], 20, {"speculative": True})])
    s = eng.speculation_stats()
    assert s["fallback_rounds"] > 0, f"gamma never reached 0: {s}"
    # once γ hit 0 no further proposals were paid for
    assert s["proposed"] < s["rounds"] * eng._gamma_max, s


# ------------------------------------------------------------------ shared pool


def test_draft_prefix_splice_arms_speculation_on_cache_hit(gpt):
    """A prefix-cache-hit admission still arms: the draft re-prefills the full
    prompt through the SHARED spliced blocks (idempotent over the prefix, and
    it heals prefixes donated by non-speculative requests), so the hit path's
    stream equals the miss path's and speculation still multiplies tokens."""
    model, variables = gpt
    kw = dict(ENGINE_KW, max_len=128, prefill_chunk=None, prefix_cache_blocks=48)
    shared = [1, 2, 3, 4, 5, 6, 7, 8]  # two full blocks at BS=4

    eng = SpeculativeEngine(model, variables, model, variables, **kw)
    # donor is NON-speculative: its blocks carry no draft KV when donated
    first = drive(eng, [(shared, 6, {})])
    restores_before = eng.prefix_restore_dispatches
    second = drive(eng, [(shared, 6, {"speculative": True})])
    assert eng.prefix_restore_dispatches > restores_before, "no splice happened"
    assert second[0] == first[0], "hit-path spec stream diverged from vanilla"
    s = eng.speculation_stats()
    assert s["accepted"] > 0, f"splice admission never speculated: {s}"
    _assert_no_block_leaks(eng)


def test_admission_arithmetic_unchanged_and_draft_bytes_reported(gpt, draft_tiny):
    """Speculation adds ZERO per-request block demand (verify is pool-read-
    only; commit never exceeds emitted tokens; draft leaves ride the same
    ids) — and the pool stats charge the resident draft bytes."""
    plain, spec = make_plain(gpt), make_spec(gpt, draft_tiny)
    assert spec.block_demand(5, 10) == plain.block_demand(5, 10)
    stats = spec.kv_pool_stats()
    assert stats["draft_kv_pool_bytes"] > 0
    assert (
        stats["kv_pool_bytes"]
        == plain.kv_pool_stats()["kv_pool_bytes"] + stats["draft_kv_pool_bytes"]
    )


# ------------------------------------------------------------------ no host syncs


def test_round_loop_zero_host_to_device_transfers(gpt, draft_tiny):
    """Steady-state rounds — mixed speculative greedy + sampled slots — pay
    zero host→device uploads: acceptance, tail fallback, γ/EMA adaptation,
    and slot retirement all resolve device-side."""
    eng = make_spec(gpt, draft_tiny)
    reqs = _spec_reqs(
        [([1, 2, 3, 4], 10, {}), ([7, 8, 9], 8, {"temperature": 0.8, "seed": 3})]
    )
    streams = drive(eng, reqs, guard=True)
    assert all(streams.values())
    assert eng.spec_round_dispatches > 0


# ------------------------------------------------------------------ chaos matrix


@pytest.mark.parametrize(
    "plan_kw",
    [dict(step_dispatch_failures=(3,)), dict(step_fetch_failures=(3,))],
    ids=["dispatch-death", "fetch-death"],
)
def test_chaos_recovery_token_identical_with_speculation(gpt, draft_tiny, plan_kw):
    """The ISSUE-7 chaos matrix rerun with speculation: a mid-flight device
    death recovers token-identically (the rebuild zeroes the draft pool; the
    salvage re-admission re-arms and re-prefills it), zero leaked blocks."""
    model, variables = gpt
    draft, dvars = draft_tiny

    def run(faults):
        engine = SpeculativeEngine(
            model, variables, draft, dvars, faults=faults,
            **dict(ENGINE_KW, num_slots=2, prefill_buckets=(8, 16), prefill_chunk=None),
        )
        sup = EngineSupervisor(watchdog_interval_s=0, backoff_s=0.005, backoff_max_s=0.02)
        batcher = ContinuousBatcher(engine, supervisor=sup)

        async def main():
            return await asyncio.gather(
                batcher.generate([3, 1, 4, 1, 5], 12, speculative=True),
                batcher.generate([2, 7, 1], 10, speculative=True),
                return_exceptions=True,
            )

        try:
            results = asyncio.run(main())
        finally:
            batcher.close()
        return results, engine

    clean, _ = run(None)
    assert all(isinstance(r, list) for r in clean)
    faulty, engine = run(FaultPlan(**plan_kw))
    assert faulty == clean
    _assert_no_block_leaks(engine)


def test_nan_quarantine_isolates_one_spec_slot(gpt, draft_tiny):
    """NaN logits in a round quarantine exactly that slot; the speculative
    sibling's stream stays exact and nothing leaks."""
    model, variables = gpt
    draft, dvars = draft_tiny

    def run(faults):
        eng = SpeculativeEngine(
            model, variables, draft, dvars, faults=faults,
            **dict(ENGINE_KW, num_slots=2, prefill_buckets=(8, 16), prefill_chunk=None),
        )
        streams = drive(eng, _spec_reqs([([3, 1, 4, 1, 5], 10, {}), ([2, 7, 1], 8, {})]))
        return streams, eng

    clean, _ = run(None)
    faulty, eng = run(FaultPlan(nan_logits=((2, 0),)))
    assert faulty[1] == clean[1], "sibling diverged"
    assert len(faulty[0]) < len(clean[0]), "victim was not cut short"
    assert eng.quarantined_requests == 1
    _assert_no_block_leaks(eng)


def test_cancel_mid_round_no_leaks(gpt, draft_tiny):
    eng = make_spec(gpt, draft_tiny)
    slots = eng.admit_many(
        [(p, b, dict(s, speculative=True)) for p, b, s in PROMPTS]
    )
    eng.step(1)
    eng.cancel(slots[1])
    while eng.num_active or eng.has_pending_prefill or eng.has_pending_events:
        eng.step(1)
    _assert_no_block_leaks(eng)


# ------------------------------------------------------------------ policy + API


def test_scheduler_class_policy_mixes_spec_and_vanilla(gpt, draft_tiny):
    """One batcher, two classes: interactive speculates (per the default
    ``speculative_classes``), batch decodes vanilla — and both streams equal
    the plain engine's greedy output."""
    model, variables = gpt
    draft, dvars = draft_tiny
    ref = drive(make_plain(gpt), [([3, 1, 4, 1], 8, {}), ([2, 7, 1], 8, {})])

    engine = SpeculativeEngine(
        model, variables, draft, dvars,
        **dict(ENGINE_KW, num_slots=2, prefill_buckets=(8, 16), prefill_chunk=None),
    )
    batcher = ContinuousBatcher(engine, scheduler=SchedulerConfig())

    async def main():
        return await asyncio.gather(
            batcher.generate([3, 1, 4, 1], 8, priority="interactive"),
            batcher.generate([2, 7, 1], 8, priority="batch"),
        )

    try:
        inter, batch = asyncio.run(main())
    finally:
        batcher.close()
    assert inter == ref[0] and batch == ref[1]
    s = engine.speculation_stats()
    assert s["rounds"] > 0, "interactive request never speculated"
    # the batch-class request decoded vanilla: no round ever proposed for it
    # beyond the interactive slot's (can't be asserted per-slot post-hoc, but
    # the class gauge path exercised note_request_class)
    assert engine._slot_class, "batcher never labeled slots"


def test_engine_rejects_topk_topp_and_accepts_spec_keys(gpt, draft_tiny):
    eng = make_spec(gpt, draft_tiny)
    with pytest.raises(ValueError, match="temperature sampling only"):
        eng.admit_many([([1, 2, 3], 4, {"speculative": True, "top_k": 5})])
    with pytest.raises(ValueError, match="temperature sampling only"):
        eng.validate_request([1, 2, 3], 4, top_p=0.9)
    # spec keys pass validation untouched (batcher passes full dicts through)
    eng.validate_request([1, 2, 3], 4, speculative=True, seed=9, gamma=2)


def test_constructor_validation(gpt, draft_tiny):
    model, variables = gpt
    draft, dvars = draft_tiny
    with pytest.raises(ValueError, match="paged"):
        SpeculativeEngine(model, variables, draft, dvars, paged=False, **ENGINE_KW)
    with pytest.raises(ValueError, match="gamma_max"):
        make_spec(gpt, draft_tiny, gamma_max=0)
    with pytest.raises(ValueError, match="ema_lo"):
        make_spec(gpt, draft_tiny, ema_lo=0.9, ema_hi=0.5)


def test_stats_block_shape(gpt, draft_tiny):
    eng = make_spec(gpt, draft_tiny)
    drive(eng, _spec_reqs([([1, 2, 3, 4], 6, {})]))
    s = eng.speculation_stats()
    for key in (
        "enabled_slots", "gamma_max", "rounds", "proposed", "accepted",
        "fallback_rounds", "acceptance_ema", "gamma", "accepted_per_target_step",
    ):
        assert key in s
