"""Fully paged KV decode: the block pool is the ONLY KV storage.

Tier-1 gate for the paged tentpole. The contract pinned here:

1. PARITY — a paged engine (the default) emits exactly the token streams the
   dense-compat engine (``paged=False``) emits under identical schedules:
   prefix hit / miss / chunked prefill / mid-flight cancel / preempt-resume /
   engine rebuild, greedy AND fixed-seed sampled, on one device and on a
   4-device CPU mesh. Masked paged attention contributes exactly zero for
   out-of-range columns, so parity is bitwise, not approximate.
2. ACCOUNTING — a slot's blocks are a linear resource: after every schedule,
   including chaos teardowns (cancel, abort, failure-rebuild), the allocator
   reports zero slot-owned blocks and every tree refcount is zero. No leaks,
   no double frees.
3. NO NEW HOST SYNCS — the paged steady-state ``step()`` pays ZERO
   host→device transfers (the table gather rides inside the jitted program;
   slot lifecycle rides device mirrors), pinned with ``jax.transfer_guard``.
4. THE WIN — a pool sized well below the dense per-slot reservation serves
   MORE concurrent requests, token-identical; pool exhaustion is a
   structured, retryable failure, impossible demand a permanent one.
"""

import jax
import numpy as np
import pytest

from unionml_tpu.parallel import make_mesh
from unionml_tpu.serving.continuous import DecodeEngine
from unionml_tpu.serving.faults import EngineFailure, FaultError, FaultPlan

BS = 4  # prefix-cache block size: small enough to exercise partial blocks


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


def _mesh4():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 CPU devices)")
    return make_mesh({"tensor": 4}, devices=jax.devices()[:4])


def make_engine(gpt, *, paged, mesh=None, seed=0, temperature=0.0, **kw):
    model, variables = gpt
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (4, 8, 16))
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("prefix_cache_blocks", 24)
    kw.setdefault("prefix_block_size", BS)
    return DecodeEngine(
        model, variables, mesh=mesh, paged=paged, seed=seed,
        temperature=temperature, **kw,
    )


def _assert_no_block_leaks(engine):
    """Teardown invariant: every slot-acquired block was freed or adopted."""
    if not engine.paged:
        return
    assert engine._allocator.slot_blocks == 0, "leaked slot-owned KV blocks"
    stack = list(engine._allocator._root.children.values())
    while stack:
        node = stack.pop()
        assert node.refcount == 0, "leaked prefix-cache reference"
        stack.extend(node.children.values())


class Driver:
    """Scripted engine driver (same discipline as test_pipeline_parity):
    drain ``take_pending_events`` under the OLD mapping before re-keying."""

    def __init__(self, engine):
        self.engine = engine
        self.streams = {}
        self.req_of_slot = {}

    def _pump(self, events):
        for ev in events:
            if ev.emit:
                self.streams[self.req_of_slot[ev.slot]].append(ev.token)

    def admit(self, req_id, prompt, budget, **sampling):
        (slot,) = self.engine.admit_many([(prompt, budget, sampling)])
        self._pump(self.engine.take_pending_events())
        self.req_of_slot[slot] = req_id
        self.streams.setdefault(req_id, [])
        return slot

    def step(self, lookahead=1):
        self._pump(self.engine.step(lookahead))

    def cancel(self, slot):
        self.engine.cancel(slot)
        self._pump(self.engine.take_pending_events())

    def drain(self, lookahead=1):
        eng = self.engine
        while eng.num_active or eng.has_pending_prefill or eng.has_pending_events:
            self.step(lookahead)
        return self.streams


def mixed_schedule(engine, *, sampled=False):
    """Hit + miss + chunked prefill + mid-flight cancel, on a FIXED tick
    script so both engines see identical call sequences."""
    drv = Driver(engine)
    shared = list(range(1, 11))  # 2 full blocks + a partial at BS=4
    kw = dict(temperature=0.9, top_k=3) if sampled else {}
    drv.admit(0, shared + [20, 21], 6, **kw)       # miss: full prefill
    drv.step()
    drv.step()
    drv.admit(1, shared + [30], 5, **kw)           # prefix-cache hit (splice)
    drv.step()
    victim = drv.admit(2, [40, 41, 42], 12, **kw)  # unrelated miss
    drv.step()
    drv.admit(3, list(range(50, 64)), 4, **kw)     # 14 tokens: chunked prefill
    drv.step()
    drv.step()
    drv.cancel(victim)                             # races the in-flight step
    drv.admit(4, shared + [20, 21], 6, **kw)       # exact replay into freed slot
    drv.drain()
    return drv.streams, 2


# ------------------------------------------------------------------ parity gate


def test_blockwise_insert_of_a_prompt_ending_inside_a_block(gpt, gpt_tiny_solo):
    """The paged insert writes whole blocks, so the tail of a row's last block
    holds the bucket's padded columns (and a neighbour row's garbage never: the
    blocks are the row's own). The mask hides them, the decode appends
    overwrite them one by one: two prompts of one wave that end inside a block
    (6 and 5 tokens, 4-token blocks, bucket 8) decode token for token like the
    dense engine and alone, and after every step each row's pool rows are
    bitwise the dense engine's cache columns, up to the row's length."""
    import jax.numpy as jnp

    requests = [([3, 1, 4, 1, 5, 9], 7), ([2, 7, 1, 8, 2], 7)]
    kw = dict(prefix_cache_blocks=0, prefill_batch=2, prefill_chunk=16)  # whole prompts: one bucket wave
    paged, dense = make_engine(gpt, paged=True, **kw), make_engine(gpt, paged=False, **kw)
    layout = gpt[0].cache_layout()
    streams = {}
    for name, engine in (("paged", paged), ("dense", dense)):
        slots = engine.admit_many([(prompt, budget, {}) for prompt, budget in requests])
        assert slots == [0, 1]
        streams[name] = {slot: [] for slot in slots}
        for ev in engine.take_pending_events():
            if ev.emit:
                streams[name][ev.slot].append(ev.token)

    def assert_rows_equal():
        table = paged._tables
        width = table.shape[1]
        for name, layer in paged._pool.items():
            rows = layer["kv"][table]  # (slots, width, heads, bs, 2 * head_dim)
            rows = jnp.moveaxis(rows, 2, 1).reshape(rows.shape[0], rows.shape[2], width * BS, -1)
            got = layout.split({name: {"kv": rows}})[name]
            for slot in (0, 1):
                n = int(paged._lens_host[slot])
                assert n == int(dense._lens_host[slot])
                for key in ("k", "v"):
                    np.testing.assert_array_equal(
                        np.asarray(got[key][slot, :, :n]),
                        np.asarray(dense._cache[name][key][slot, :, :n]),
                    )

    assert paged.prefill_dispatches == 1 and paged.num_active == 2
    assert_rows_equal()
    while paged.num_active or paged.has_pending_events:
        for name, engine in (("paged", paged), ("dense", dense)):
            for ev in engine.step():
                if ev.emit:
                    streams[name][ev.slot].append(ev.token)
        assert_rows_equal()
    assert streams["paged"] == streams["dense"]
    assert [streams["paged"][slot] for slot in (0, 1)] == [gpt_tiny_solo(p, n) for p, n in requests]
    _assert_no_block_leaks(paged)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_paged_vs_dense_mixed_schedule_parity(gpt, gpt_tiny_solo, sampled):
    """Paged == dense across hit/miss/chunked/cancel, greedy and fixed-seed
    sampled; surviving greedy streams also == the solo reference. Zero
    leaked blocks afterwards."""
    paged_engine = make_engine(gpt, paged=True, seed=7)
    on, cancelled = mixed_schedule(paged_engine, sampled=sampled)
    off, _ = mixed_schedule(make_engine(gpt, paged=False, seed=7), sampled=sampled)
    survivors = [r for r in on if r != cancelled]
    assert {r: on[r] for r in survivors} == {r: off[r] for r in survivors}
    n = min(len(on[cancelled]), len(off[cancelled]))
    assert on[cancelled][:n] == off[cancelled][:n]
    if not sampled:
        expected = {
            0: gpt_tiny_solo(list(range(1, 11)) + [20, 21], 6),
            1: gpt_tiny_solo(list(range(1, 11)) + [30], 5),
            3: gpt_tiny_solo(list(range(50, 64)), 4),
            4: gpt_tiny_solo(list(range(1, 11)) + [20, 21], 6),
        }
        assert {r: on[r] for r in expected} == expected
    _assert_no_block_leaks(paged_engine)


def test_paged_vs_dense_parity_mesh4(gpt):
    """The same gate on a 4-device tensor mesh: the head-sharded pool's
    gathered reads match the dense slot cache stream for stream."""
    mesh = _mesh4()
    paged_engine = make_engine(gpt, paged=True, mesh=mesh)
    on, cancelled = mixed_schedule(paged_engine)
    off, _ = mixed_schedule(make_engine(gpt, paged=False))
    survivors = [r for r in on if r != cancelled]
    assert {r: on[r] for r in survivors} == {r: off[r] for r in survivors}
    _assert_no_block_leaks(paged_engine)


def test_preempt_resume_is_token_exact_and_splices(gpt):
    """Preempt hands the slot's blocks to the radix tree (adoption — no
    device copy); the resume splices them back and the joined stream equals
    an uninterrupted run."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    full = make_engine(gpt, paged=True).generate(prompt, 12)
    engine = make_engine(gpt, paged=True)
    slot = engine.add_request(prompt, 12)
    got = []
    for _ in range(4):
        got.extend(ev.token for ev in engine.step() if ev.emit and ev.slot == slot)
    state = engine.preempt(slot)
    assert state is not None
    got.extend(
        ev.token for ev in engine.take_pending_events()
        if ev.emit and ev.slot == slot
    )
    restores_before = engine.prefix_restore_dispatches
    slot2 = engine.add_request(state.tokens, 12 - len(got))
    engine.release_preempted(state)
    while engine._active[slot2] or slot2 in engine._partials:
        got.extend(ev.token for ev in engine.step() if ev.emit and ev.slot == slot2)
    assert got == full
    # the resume restored KV through the tree, not a recompute
    assert engine.prefix_restore_dispatches > restores_before
    while engine.busy or engine._inflight is not None:
        engine.step()
    _assert_no_block_leaks(engine)


def test_rebuild_schedule_parity_and_zero_leaks(gpt):
    """An injected device fault mid-decode: the paged engine rebuilds with an
    EMPTY pool (the failed step donated it), salvage is transcript-only, and
    the re-admitted request still finishes token-identical — with zero
    leaked blocks even though the rebuild dropped every grant."""
    from unionml_tpu.serving.continuous import PreemptedSlot

    prompt, budget = [3, 1, 4, 1, 5], 10
    expected = make_engine(gpt, paged=True).generate(prompt, budget)
    engine = make_engine(gpt, paged=True, faults=FaultPlan(step_dispatch_failures=(3,)))
    engine.add_request(prompt, budget)
    out = []
    with pytest.raises(FaultError):
        while True:
            out.extend(ev.token for ev in engine.step() if ev.emit)
    salvage = engine.take_salvage()
    assert len(salvage) == 1
    rec = salvage[0]
    assert rec.path == []  # paged salvage is transcript-only
    assert engine._allocator.slot_blocks == 0  # grants released at capture
    engine.add_request(rec.tokens, rec.remaining)
    engine.release_preempted(PreemptedSlot(tokens=rec.tokens, path=rec.path))
    while engine.num_active or engine.has_pending_prefill or engine.has_pending_events:
        out.extend(ev.token for ev in engine.step() if ev.emit)
    assert out == expected
    _assert_no_block_leaks(engine)


# ------------------------------------------------------------- accounting gate


def test_chaos_teardowns_leak_no_blocks(gpt):
    """Cancel mid-chunked-prefill, abort_all racing a dispatched step, and
    reset: after each, the allocator's slot-block counter is zero and the
    free list plus cached tree covers the whole pool."""
    engine = make_engine(gpt, paged=True, num_slots=3)
    # cancel mid-chunked-prefill (reserved slot holding a fresh grant)
    (slot,) = engine.admit_many([(list(range(1, 15)), 6)])
    assert engine.has_pending_prefill
    engine.cancel(slot)
    _assert_no_block_leaks(engine)
    # abort_all with a dispatched-but-unfetched step in flight
    engine.admit_many([([3, 1, 4], 20, {}), ([2, 7], 20, {})])
    engine.step()
    engine.step()
    engine.abort_all()
    _assert_no_block_leaks(engine)
    # the pool is whole again: free + cached == capacity
    stats = engine._allocator.stats()
    assert stats["free_blocks"] + stats["cached_blocks"] == engine._allocator.num_blocks
    # and the engine still serves exactly
    engine.reset()
    assert engine.generate([5, 6, 7], 4) == make_engine(gpt, paged=False).generate([5, 6, 7], 4)
    _assert_no_block_leaks(engine)


def test_pool_exhaustion_is_structured_and_retryable(gpt):
    """Transient shortfall (each request fits, both don't) raises the
    structured retryable failure and releases every partial grant;
    impossible demand is rejected permanently at validation."""
    # 12 usable blocks; each request demands ceil((3+40)/4) = 11
    engine = make_engine(
        gpt, paged=True, num_slots=8, pool_blocks=13, prefix_cache_blocks=0
    )
    with pytest.raises(EngineFailure) as err:
        engine.admit_many([([1, 2, 3], 40, {}), ([4, 5, 6], 40, {})])
    assert err.value.reason == "pool_exhausted" and err.value.retryable
    _assert_no_block_leaks(engine)
    # permanent: a single request that can NEVER fit the pool
    with pytest.raises(ValueError, match="KV blocks"):
        make_engine(
            gpt, paged=True, num_slots=2, pool_blocks=5, prefix_cache_blocks=0
        ).admit_many([([1, 2, 3], 40, {})])


# ----------------------------------------------------------- the measurable win


def test_small_pool_serves_more_concurrent_requests(gpt, gpt_tiny_solo):
    """The acceptance bar's CI stand-in: a pool holding 32 usable blocks —
    exactly TWO dense max_len=64 reservations — serves EIGHT concurrent short
    requests, each token-identical to the solo reference. Dense needs a full
    max_len row per slot; paged needs ceil((len+budget)/BS) blocks."""
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=8, max_len=64, prefill_buckets=(4, 8),
        paged=True, pool_blocks=33, prefix_block_size=BS, prefix_cache_blocks=0,
    )
    requests = [([i + 2, i + 3, i + 4], 5) for i in range(8)]
    slots = engine.admit_many([(p, n, {}) for p, n in requests])
    assert len(slots) == 8  # all admitted CONCURRENTLY on 2 slots' worth of KV
    outs = {s: [] for s in slots}
    while engine.busy or engine._inflight is not None or engine.has_pending_events:
        for ev in engine.step():
            if ev.emit:
                outs[ev.slot].append(ev.token)
    for (prompt, n), slot in zip(requests, slots):
        assert outs[slot] == gpt_tiny_solo(prompt, n)
    _assert_no_block_leaks(engine)


# ------------------------------------------------------- transfer-count fence


@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16-pool", "int8-pool"])
def test_paged_steady_state_step_pays_zero_uploads(gpt, kv):
    """The tentpole's no-new-host-syncs clause: once compiled, the paged
    ``step()`` — table gather included — runs entirely off device-resident
    state, quantized pool included (scales ride the donated pool tree).
    ``jax.transfer_guard`` turns any regression into a hard error."""
    engine = make_engine(gpt, paged=True, kv_quantize=kv)
    engine.admit_many([([3, 1, 4, 1, 5], 30, {}), ([2, 7], 30, {})])
    engine.step()  # compile + warm the greedy depth-1 program
    engine.step()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            engine.step()
    engine.step(4)  # compile the fused-burst program outside the guard
    with jax.transfer_guard_host_to_device("disallow"):
        engine.step(4)
    # sampling program: per-row controls ride as device mirrors too
    sampled = make_engine(gpt, paged=True, temperature=0.8, kv_quantize=kv)
    sampled.add_request([3, 1, 4], 30, temperature=0.7, top_k=5, top_p=0.9)
    sampled.step()
    sampled.step()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            sampled.step()


def test_paged_prefix_hit_admission_pays_only_explicit_transfers(gpt):
    """The paged splice path under the guard: a full-block hit admits with
    implicit host→device transfers DISALLOWED — the table-row write, suffix
    chunk, and slot point-update are all explicit ``device_put``s."""
    engine = make_engine(gpt, paged=True, num_slots=2, prefill_buckets=(8, 16))
    prompt = [5, 6, 7, 8, 1, 2, 3, 4, 9]  # two full blocks + a 1-token suffix
    engine.generate(prompt, 6)  # indexes the blocks; warms prefill/decode
    slot = engine.admit_many([(prompt, 6)])[0]  # warm the hit path programs
    while engine._active[slot] or engine.has_pending_events:
        engine.step()
    hits_before = engine.prefix_cache.hits
    with jax.transfer_guard_host_to_device("disallow"):
        slot = engine.admit_many([(prompt, 6)])[0]  # full-block hit: splice
        for _ in range(3):
            engine.step()
    assert engine.prefix_cache.hits == hits_before + 1


# --------------------------------------------------- int8 KV pool (ISSUE 14)


def _logsoftmax(x):
    x = x - x.max()
    return x - np.log(np.exp(x).sum())


def _greedy_trace(engine, prompt, n):
    """One request on an idle pipeline=False engine: per-token greedy stream
    plus, for token t, the logits it was sampled from (``_last_logits`` holds
    them between unpipelined steps)."""
    slot = engine.add_request(list(prompt), n)
    toks, logits = [], []
    for _ in range(n):
        logits.append(np.asarray(engine._last_logits)[slot].copy())
        toks.extend(ev.token for ev in engine.step() if ev.emit and ev.slot == slot)
    while engine.busy or engine._inflight is not None or engine.has_pending_events:
        engine.step()
    return toks, logits


def _divergence(a, b):
    """(comparable_tokens, tokens_past_first_split): once greedy streams split,
    the conditioning contexts differ, so only the common prefix is comparable."""
    m = min(len(a), len(b))
    first = next((i for i in range(m) if a[i] != b[i]), m)
    return m, m - first


@pytest.mark.parametrize("devices", [1, 4], ids=["1dev", "mesh4"])
def test_int8_pool_logprob_delta_budget(gpt, devices):
    """The pinned quality gate: on the common (pre-divergence) prefix, the
    int8 pool's logprob of the bf16-greedy token stays within
    KV_INT8_LOGPROB_DELTA_BUDGET, and the divergence rate within
    KV_INT8_GREEDY_DIVERGENCE_BUDGET."""
    from unionml_tpu.ops.quant import (
        KV_INT8_GREEDY_DIVERGENCE_BUDGET, KV_INT8_LOGPROB_DELTA_BUDGET,
    )

    mesh = None if devices == 1 else _mesh4()
    kw = dict(paged=True, mesh=mesh, pipeline=False, prefill_chunk=None, prefix_cache_blocks=0)
    ref = make_engine(gpt, **kw)
    quant = make_engine(gpt, kv_quantize="int8", **kw)
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], list(range(20, 29)), [7, 7, 7, 2, 1]]
    total = diverged = 0
    max_delta = 0.0
    for prompt in prompts:
        t_ref, l_ref = _greedy_trace(ref, prompt, 16)
        t_q, l_q = _greedy_trace(quant, prompt, 16)
        m, d = _divergence(t_ref, t_q)
        total += m
        diverged += d
        for i in range(m - d):
            delta = abs(_logsoftmax(l_ref[i])[t_ref[i]] - _logsoftmax(l_q[i])[t_ref[i]])
            max_delta = max(max_delta, float(delta))
    assert total > 0 and diverged / total <= KV_INT8_GREEDY_DIVERGENCE_BUDGET
    assert max_delta <= KV_INT8_LOGPROB_DELTA_BUDGET
    _assert_no_block_leaks(quant)


@pytest.mark.parametrize("devices", [1, 4], ids=["1dev", "mesh4"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_int8_pool_divergence_budget_mixed_schedule(gpt, devices, sampled):
    """int8-vs-bf16 token streams across the full mixed schedule (hit / miss /
    chunked prefill / cancel), greedy and fixed-seed sampled, 1- and 4-device:
    the per-stream divergence rate stays within the pinned budget."""
    from unionml_tpu.ops.quant import KV_INT8_GREEDY_DIVERGENCE_BUDGET

    mesh = None if devices == 1 else _mesh4()
    on, _ = mixed_schedule(
        make_engine(gpt, paged=True, mesh=mesh, seed=7, kv_quantize="int8"), sampled=sampled
    )
    off, _ = mixed_schedule(make_engine(gpt, paged=True, mesh=mesh, seed=7), sampled=sampled)
    total = diverged = 0
    for req in on:
        m, d = _divergence(on[req], off[req])
        total += m
        diverged += d
    assert total > 0 and diverged / total <= KV_INT8_GREEDY_DIVERGENCE_BUDGET


def test_int8_skip_all_layers_is_bitwise_bf16(gpt):
    """kv_quantize_skip_layers is a real bf16 fallback: skipping EVERY layer
    reproduces the full-precision stream bitwise, and a partial skip leaves
    exactly the listed layers' pool leaves unscaled."""
    import jax.numpy as jnp

    prompt = [3, 1, 4, 1, 5, 9]
    full = make_engine(gpt, paged=True).generate(prompt, 10)
    skip_all = make_engine(
        gpt, paged=True, kv_quantize="int8", kv_quantize_skip_layers=(0, 1)
    )
    assert skip_all.generate(prompt, 10) == full
    partial = make_engine(gpt, paged=True, kv_quantize="int8", kv_quantize_skip_layers=(0,))
    assert "k_scale" not in partial._pool["layer_0"]
    assert partial._pool["layer_1"]["k"].dtype == jnp.int8
    assert partial._pool["layer_1"]["k_scale"].dtype == jnp.float32


def test_int8_chaos_teardowns_leak_no_blocks(gpt):
    """Satellite: the chaos schedules under kv_quantize="int8" — cancel
    mid-chunked-prefill, abort_all racing a dispatched step, reset, the full
    mixed schedule — leave zero leaked / double-freed blocks (scales share the
    k/v block ids, so block accounting covers them by construction)."""
    engine = make_engine(gpt, paged=True, kv_quantize="int8")
    mixed_schedule(engine)
    _assert_no_block_leaks(engine)
    engine = make_engine(gpt, paged=True, num_slots=3, kv_quantize="int8")
    (slot,) = engine.admit_many([(list(range(1, 15)), 6)])
    assert engine.has_pending_prefill
    engine.cancel(slot)
    _assert_no_block_leaks(engine)
    engine.admit_many([([3, 1, 4], 20, {}), ([2, 7], 20, {})])
    engine.step()
    engine.step()
    engine.abort_all()
    _assert_no_block_leaks(engine)
    stats = engine._allocator.stats()
    assert stats["free_blocks"] + stats["cached_blocks"] == engine._allocator.num_blocks
    engine.reset()
    engine.generate([5, 6, 7], 4)
    _assert_no_block_leaks(engine)


def test_int8_preempt_resume_and_rebuild_leak_no_blocks(gpt):
    """Preempt (block adoption), resume (splice + suffix requantization), and
    a fault-injected rebuild all run on the quantized pool with zero leaks.
    Streams are budgeted elsewhere, not bit-pinned: a resume requantizes the
    suffix from a fresh forward, which may round differently than the
    incremental appends it replaces."""
    from unionml_tpu.serving.continuous import PreemptedSlot

    engine = make_engine(gpt, paged=True, kv_quantize="int8")
    slot = engine.add_request([3, 1, 4, 1, 5, 9, 2, 6], 12)
    for _ in range(4):
        engine.step()
    state = engine.preempt(slot)
    assert state is not None
    engine.take_pending_events()
    engine.add_request(state.tokens, 8)
    engine.release_preempted(state)
    while engine.busy or engine._inflight is not None or engine.has_pending_events:
        engine.step()
    _assert_no_block_leaks(engine)

    engine = make_engine(
        gpt, paged=True, kv_quantize="int8", faults=FaultPlan(step_dispatch_failures=(3,))
    )
    engine.add_request([3, 1, 4, 1, 5], 10)
    with pytest.raises(FaultError):
        while True:
            engine.step()
    salvage = engine.take_salvage()
    assert len(salvage) == 1 and engine._allocator.slot_blocks == 0
    engine.add_request(salvage[0].tokens, salvage[0].remaining)
    engine.release_preempted(PreemptedSlot(tokens=salvage[0].tokens, path=salvage[0].path))
    while engine.num_active or engine.has_pending_prefill or engine.has_pending_events:
        engine.step()
    _assert_no_block_leaks(engine)


def test_int8_equal_byte_pool_doubles_capacity_and_reports_it(gpt):
    """Equal KV bytes buy ≥2× the blocks: the int8 pool admits 4 concurrent
    requests where the byte-equivalent bf16 pool admits 1, and exhaustion's
    structured failure reports the doubled block count."""
    from unionml_tpu.models.gpt import kv_block_bytes

    model, _ = gpt
    cfg = model.config
    bf16_blocks = 13
    byte_budget = bf16_blocks * kv_block_bytes(cfg, BS)
    int8_blocks = byte_budget // kv_block_bytes(cfg, BS, kv_quantize="int8")
    assert int8_blocks >= 2 * bf16_blocks  # the doubling, from layout math alone
    engine = make_engine(
        gpt, paged=True, num_slots=8, pool_blocks=int(int8_blocks),
        prefix_cache_blocks=0, kv_quantize="int8",
    )
    # each request demands ceil((3+40)/4) = 11 blocks: one fills the 12-usable
    # bf16 pool (see test_pool_exhaustion_is_structured_and_retryable); four
    # fit the equal-byte int8 pool concurrently
    slots = engine.admit_many([([i, i + 1, i + 2], 40, {}) for i in range(1, 5)])
    assert len(slots) == 4
    with pytest.raises(EngineFailure) as err:
        engine.admit_many([([9, 9, 9], 40, {})])
    assert err.value.reason == "pool_exhausted" and err.value.retryable
    assert f"of {int(int8_blocks) - 1}" in str(err.value)  # the doubled count
    engine.abort_all()
    _assert_no_block_leaks(engine)


def test_weight_int8_composes_with_mesh(gpt):
    """Satellite: quantize="int8" + mesh are no longer mutually exclusive —
    the QuantizedArray {q, scale} leaves get param_shardings entries, and the
    meshed int8 engine is token-identical to the solo int8 engine."""
    mesh = _mesh4()
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    solo = make_engine(gpt, paged=True, quantize="int8").generate(prompt, 10)
    meshed = make_engine(gpt, paged=True, quantize="int8", mesh=mesh).generate(prompt, 10)
    assert meshed == solo


# ------------------------------------------------------------- re-layout parity


@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16", "int8kv"])
def test_post_construction_enable_relayout_parity(gpt, kv):
    """The serving-app path builds the engine WITHOUT a ctor prefix cache and
    calls ``enable_prefix_cache`` afterwards, re-laying-out the pool to a new
    block size. The paged programs must pick the new layout up at retrace —
    a stale __init__-captured block size silently corrupted tokens (bf16) or
    crashed _paged_insert's quantized scatter with a shape error (int8)."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    ctor = make_engine(gpt, paged=True, kv_quantize=kv)
    relayout = make_engine(
        gpt, paged=True, kv_quantize=kv, prefix_cache_blocks=0, prefix_block_size=16
    )
    relayout.enable_prefix_cache(24, BS)
    assert relayout._prefix_block_size == ctor._prefix_block_size == BS
    assert relayout.pool_blocks == ctor.pool_blocks
    assert relayout.generate(prompt, 12) == ctor.generate(prompt, 12)
    _assert_no_block_leaks(relayout)


# ------------------------------------------------------------------ compat flag


def test_dense_compat_flag_still_works(gpt, gpt_tiny_solo):
    """``paged=False`` keeps the dense per-slot cache path alive (migration
    escape hatch); the default engine is paged."""
    default = make_engine(gpt, paged=True)
    assert default.paged and default._cache is None and default._pool is not None
    dense = make_engine(gpt, paged=False)
    assert not dense.paged and dense._cache is not None
    prompt = [3, 1, 4, 1, 5]
    assert dense.generate(prompt, 6) == default.generate(prompt, 6) == gpt_tiny_solo(prompt, 6)
