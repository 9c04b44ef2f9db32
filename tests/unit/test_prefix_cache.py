"""Radix-tree KV prefix caching: token-identical reuse of shared prompt prefixes.

The gold property: an engine with the prefix cache ENABLED emits exactly the
token streams a cache-disabled engine (and the one-shot ``models.gpt.generate``
reference) emits — across hit / miss / partial-block / evict-then-readmit /
chunked-prefill schedules, greedy and fixed-seed sampled, single-device and on
4/8-device CPU meshes — while provably recomputing only the uncovered suffix
(the FLOP counters are asserted, so the win is CI-checked, not hardware-gated).
"""

import asyncio

import jax
import numpy as np
import pytest

from unionml_tpu.parallel import make_mesh
from unionml_tpu.serving.continuous import DecodeEngine
from unionml_tpu.serving.prefix_cache import PrefixCache

BS = 4  # test block size: small enough to exercise partial-block matches


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    # session-scoped model/params + memoized reference completions: shares one
    # init and one set of generate compiles with the other engine suites
    _, model, variables = gpt_tiny_session
    return model, variables


def make_engine(gpt, *, blocks=32, mesh=None, **kw):
    model, variables = gpt
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (4, 8, 16, 32))
    return DecodeEngine(
        model, variables, mesh=mesh,
        prefix_cache_blocks=blocks, prefix_block_size=BS, **kw,
    )


def run_schedule(engine, requests, stagger=2):
    """Admit ``requests`` one at a time with ``stagger`` decode steps between
    admissions (hits land while earlier requests still decode), then drain.
    Returns each request's emitted tokens, in request order."""
    out = {}
    req_of_slot = {}
    def pump(events):
        for ev in events:
            if ev.emit:
                out[req_of_slot[ev.slot]].append(ev.token)
    for i, (prompt, budget) in enumerate(requests):
        (slot,) = engine.admit_many([(prompt, budget)])
        req_of_slot[slot] = i
        out[i] = []
        for _ in range(stagger):
            pump(engine.step())
    while engine.num_active or engine.has_pending_prefill:
        pump(engine.step())
    return [out[i] for i in range(len(requests))]


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest forces 8 CPU devices)")
    return make_mesh(axes, devices=jax.devices()[:n])


# ---------------------------------------------------------------- host radix tree


def test_radix_tree_match_insert_refcount_evict():
    """Pure host-side semantics: block-granular matching, refcount pinning,
    LRU leaf eviction, prefix-shaped insertion under a full pool."""
    cache = PrefixCache(num_blocks=3, block_size=2)
    toks_a = [1, 2, 3, 4, 5, 6]
    assert cache.match(toks_a, 3) == []  # empty tree: no match
    path_a, new_a = cache.extend([], toks_a, 3)
    assert len(path_a) == len(new_a) == 3 and cache.cached_blocks == 3
    # full match re-finds the same nodes (block ids identical)
    hit = cache.match(toks_a, 3)
    assert [n.block_id for n in hit] == [n.block_id for n in path_a]
    cache.release(hit)
    # divergent tokens match only the shared block prefix
    assert len(cache.match([1, 2, 9, 9], 2)) == 1
    cache.release(cache.match([1, 2, 9, 9], 2))  # release both lookups' refs
    cache.release([hit[0]])  # balance the partial match above

    # pool full + every block referenced: extend cannot allocate
    path_b, new_b = cache.extend([], [7, 8, 9, 10], 2)
    assert path_b == [] and new_b == []
    cache.release(path_a)  # now unreferenced: LRU leaf becomes evictable
    path_b, new_b = cache.extend([], [7, 8, 9, 10], 2)
    assert len(new_b) == 2 and cache.evicted_blocks == 2
    # eviction took leaves (deepest-first), never an interior node with children:
    # the a-chain root survives and still matches its first block
    assert len(cache.match(toks_a, 3)) == 1


def test_radix_tree_validates():
    with pytest.raises(ValueError, match="num_blocks"):
        PrefixCache(0, 4)
    with pytest.raises(ValueError, match="block_size"):
        PrefixCache(4, 0)


# ------------------------------------------------------------------- exactness


def test_hit_miss_partial_block_parity_greedy(gpt, gpt_tiny_solo):
    """Shared-prefix requests staggered into a busy engine: cache-on == cache-off
    == solo, and the cache-on engine provably computes fewer prefill tokens."""
    shared = list(range(1, 11))  # 10 tokens: 2 full blocks + a partial (BS=4)
    requests = [
        (shared + [20, 21], 6),        # miss (first sight): full prefill
        (shared + [30], 5),            # partial-block hit: 8 of 11 restored
        ([40, 41, 42], 4),             # unrelated miss
        (shared + [20, 21], 6),        # exact replay: hit (capped 1 token short)
    ]
    on = run_schedule(make_engine(gpt), requests)
    off_engine = make_engine(gpt, blocks=0)
    off = run_schedule(off_engine, requests)
    assert on == off == [gpt_tiny_solo(p, n) for p, n in requests]

    engine = make_engine(gpt)
    assert run_schedule(engine, requests) == off
    stats = engine.prefix_cache.stats()
    assert stats["hits"] == 2 and stats["hit_tokens"] == 8 + 8
    assert engine.prefill_tokens_computed < off_engine.prefill_tokens_computed
    assert engine.prefill_tokens_computed == 12 + 3 + 3 + 4  # suffixes only


def test_whole_prompt_cached_still_seeds_decode(gpt, gpt_tiny_solo):
    """A prompt whose every block is cached must still prefill >= 1 real token:
    the match is capped one token short so last_logits seed decoding exactly."""
    prompt = list(range(1, 9))  # exactly 2 blocks
    engine = make_engine(gpt)
    first = engine.generate(prompt, 5)
    again = engine.generate(prompt, 5)
    assert first == again == gpt_tiny_solo(prompt, 5)
    # second admission matched one block short of the whole prompt
    assert engine.prefix_cache.stats()["hit_tokens"] == len(prompt) - BS
    assert engine.prefill_tokens_computed == len(prompt) + BS


def test_sampled_fixed_seed_parity(gpt):
    """Sampling path: identical admission schedule + seed => identical streams
    with the cache on and off (restored KV is bit-identical to recomputed)."""
    def run(blocks):
        engine = make_engine(gpt, blocks=blocks, temperature=0.8, seed=7)
        reqs = [
            (list(range(1, 11)) + [20], 6),
            (list(range(1, 11)) + [30, 31], 6),
            (list(range(1, 9)), 5),
        ]
        return run_schedule(engine, reqs)

    assert run(16) == run(0)


def test_evict_then_readmit_parity(gpt, gpt_tiny_solo):
    """A tiny unified pool under 3 competing prefixes: hits, evictions, and
    misses on evicted prefixes all stay token-identical; counters record the
    churn. (Paged engines size the tree out of the shared block pool, so the
    pressure comes from an explicit small ``pool_blocks``.)"""
    a = list(range(1, 11))
    b = list(range(50, 60))
    c = list(range(80, 90))
    engine = make_engine(gpt, blocks=3, pool_blocks=7)
    for prompt in (a, b, a, c, a, b):
        assert engine.generate(prompt, 4) == gpt_tiny_solo(prompt, 4)
    stats = engine.prefix_cache.stats()
    assert stats["evicted_blocks"] > 0
    assert stats["hits"] >= 1  # the immediate a->a replay hit before churn


def test_chunked_prefill_cache_hit_interleaving(gpt, gpt_tiny_solo):
    """A long prompt admitted as a chunked prefill RESUMES from its cached
    prefix (consumed starts at the matched length, chunk-misaligned) while a
    neighbor keeps decoding; both streams match solo and the cache-off engine."""
    first = list(range(1, 15))            # 14 tokens -> inserts 3 blocks (12)
    follow = first[:12] + [40, 41, 42, 43, 44, 45, 46, 47]  # 20: hit 12, chunk suffix 8
    neighbor = [3, 1, 4, 1, 5]

    def run(blocks):
        engine = make_engine(
            gpt, blocks=blocks, num_slots=3, prefill_buckets=(8, 16, 32), prefill_chunk=4
        )
        return run_schedule(engine, [(first, 5), (neighbor, 8), (follow, 5)], stagger=2)

    expected = [gpt_tiny_solo(p, n) for p, n in [(first, 5), (neighbor, 8), (follow, 5)]]
    assert run(16) == run(0) == expected


def test_generated_capture_multi_turn(gpt, gpt_tiny_solo):
    """With prefix_cache_generated, a follow-up turn (prompt + completion + new
    text) hits KV straight through the PREVIOUS turn's generated tokens."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    completion = gpt_tiny_solo(prompt, 8)
    turn2 = prompt + completion + [7, 7, 7]

    engine = make_engine(gpt, prefix_cache_generated=True)
    assert engine.generate(prompt, 8) == completion
    before = engine.prefill_tokens_computed
    assert engine.generate(turn2, 5) == gpt_tiny_solo(turn2, 5)
    # the whole previous turn (16 tokens = 4 blocks) restored; only the tail computed
    assert engine.prefix_cache.stats()["hit_tokens"] >= len(prompt) + len(completion)
    assert engine.prefill_tokens_computed - before == len(turn2) - 16


def test_cancel_and_reset_release_cached_state(gpt, gpt_tiny_solo):
    """cancel() mid-chunked-prefill with a restored prefix releases the slot's
    tree references; reset() drops the whole index and pool, and the engine
    still serves exactly afterwards."""
    engine = make_engine(gpt, num_slots=1, prefill_buckets=(8, 16, 32), prefill_chunk=4)
    seed = list(range(1, 15))
    assert engine.generate(seed, 4) == gpt_tiny_solo(seed, 4)
    (slot,) = engine.admit_many([(seed[:12] + [40] * 8, 5)])  # chunked, hit-resumed
    assert engine.has_pending_prefill
    engine.cancel(slot)
    assert not engine._slot_path and engine.free_slots == [slot]
    # every reference released: the full pool is evictable again
    churn = [(list(range(100 + 10 * i, 110 + 10 * i)), 3) for i in range(4)]
    for prompt, n in churn:
        assert engine.generate(prompt, n) == gpt_tiny_solo(prompt, n)
    engine.reset()
    assert engine.prefix_cache.cached_blocks == 0
    assert engine.generate(seed, 4) == gpt_tiny_solo(seed, 4)


def test_same_call_burst_dedupes_shared_prefix(gpt, gpt_tiny_solo):
    """A cold burst admitted in ONE admit_many call pays one full prefill plus
    suffixes: siblings sharing a prefix defer to the second admission pass and
    restore the first holder's freshly indexed blocks. Outputs stay exact."""
    shared = list(range(1, 13))  # 3 full blocks
    requests = [(shared + [20 + i], 4) for i in range(4)]
    engine = make_engine(gpt)
    slots = engine.admit_many(requests)
    out = {s: [] for s in slots}
    while engine.num_active:
        for ev in engine.step():
            if ev.emit:
                out[ev.slot].append(ev.token)
    assert [out[s] for s in slots] == [gpt_tiny_solo(p, n) for p, n in requests]
    # request 0 computed all 13 tokens; 1-3 only their 1-token suffix
    assert engine.prefill_tokens_computed == 13 + 3 * 1
    assert engine.prefix_cache.stats()["hits"] == 3


# ------------------------------------------------------------------ mesh parity


@pytest.mark.parametrize(
    "axes", [{"tensor": 4}, {"data": 2, "tensor": 4}], ids=["mesh4", "mesh8"]
)
def test_mesh_sharded_prefix_cache_parity(gpt, gpt_tiny_solo, axes):
    """Cache-enabled engine over a mesh == cache-off single-device engine,
    token for token, across hit/miss/partial schedules."""
    mesh = _mesh(axes)
    shared = list(range(1, 11))
    requests = [
        (shared + [20, 21], 6),
        (shared + [30], 5),
        ([40, 41, 42], 4),
        (shared + [20, 21], 6),
    ]
    sharded = make_engine(gpt, mesh=mesh)
    single_off = make_engine(gpt, blocks=0)
    expected = [gpt_tiny_solo(p, n) for p, n in requests]
    assert run_schedule(sharded, requests) == run_schedule(single_off, requests) == expected
    assert sharded.prefix_cache.stats()["hits"] == 2


def test_mesh_pool_is_head_sharded(gpt):
    """The KV block pool actually shards over heads on the tensor axis — the
    same layout as the slot cache, so restores/saves are shard-local."""
    mesh = _mesh({"tensor": 4})
    engine = make_engine(gpt, mesh=mesh, num_slots=2, max_len=32)
    leaf = engine._pool["layer_0"]["kv"]  # (blocks, heads=4, block_size, 2 * head_dim)
    assert len(leaf.sharding.device_set) == 4
    assert leaf.addressable_shards[0].data.shape[1] == 1  # 1 of 4 heads per device


# ------------------------------------------------- the CI-checked measurable win


def test_prefix_heavy_workload_flop_reduction(gpt, gpt_tiny_solo):
    """The acceptance bar, asserted in CI: N requests sharing a long prefix
    recompute >= 85% fewer prefill tokens than a cache-off engine, exactly."""
    model, variables = gpt
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 500, size=56).tolist()
    requests = [(shared + rng.integers(1, 500, size=4).tolist(), 3) for _ in range(16)]

    def run(blocks):
        engine = DecodeEngine(
            model, variables, num_slots=16, max_len=96, prefill_buckets=(4, 64),
            prefix_cache_blocks=blocks, prefix_block_size=BS,
        )
        # wave 1 seeds the cache; waves of admissions model queued traffic
        outs = []
        for prompt, n in requests:
            outs.append(engine.generate(prompt, n))
        return engine, outs

    on_engine, on_out = run(blocks=32)
    off_engine, off_out = run(blocks=0)
    assert on_out == off_out == [gpt_tiny_solo(p, n) for p, n in requests]

    # first request computes all 60 tokens; each of the 15 followers only its
    # 4-token suffix (56 shared = 14 full blocks, matched entirely)
    assert off_engine.prefill_tokens_computed == 16 * 60
    assert on_engine.prefill_tokens_computed == 60 + 15 * 4
    reduction = 1 - on_engine.prefill_tokens_computed / off_engine.prefill_tokens_computed
    assert reduction >= 0.85
    stats = on_engine.prefix_cache.stats()
    assert stats["hits"] == 15 and stats["hit_tokens"] == 15 * 56
    assert on_engine.prefix_restore_dispatches == 15


# ------------------------------------------------------------------ HTTP surface


def test_stats_route_reports_prefix_cache(gpt):
    """App plumbing: generate_prefix_cache_blocks enables the cache on a bare
    engine at startup and /stats surfaces its counters."""
    import types

    from aiohttp.test_utils import TestClient, TestServer

    from unionml_tpu.serving import build_aiohttp_app

    model, variables = gpt
    stub = types.SimpleNamespace(name="prefix-app", artifact=object())
    app = build_aiohttp_app(
        stub,
        resident=False,
        coalesce=False,
        generator=lambda: DecodeEngine(
            model, variables, num_slots=2, max_len=64, prefill_buckets=(8, 16)
        ),
        generate_prefix_cache_blocks=16,
        generate_prefix_block_size=BS,
    )

    async def main():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            shared = list(range(1, 11))
            for suffix in ([20], [30]):
                resp = await client.post(
                    "/generate", json={"prompt_ids": shared + suffix, "max_new_tokens": 3}
                )
                assert resp.status == 200, await resp.text()
            resp = await client.get("/stats")
            return (await resp.json())["generation"]
        finally:
            await client.close()

    generation = asyncio.run(main())
    assert generation["prefix_cache"]["block_size"] == BS
    assert generation["prefix_cache"]["hits"] == 1
    assert generation["prefill_tokens_computed"] < 2 * 11
    # the kv_pool_stats merge (PR 14): pool dtype + resident-byte accounting
    assert generation["prefix_cache"]["kv_dtype"] == "float32"  # tiny cfg on CPU
    assert (0 < generation["prefix_cache"]["kv_pool_bytes"]
            == generation["prefix_cache"]["kv_pool_bytes_dense_equiv"])


# ------------------------------------------------- pipelined-step race fencing


def _max_refcount(cache):
    """Largest refcount anywhere in the radix tree (0 = nothing pinned)."""
    worst, stack = 0, list(cache._root.children.values())
    while stack:
        node = stack.pop()
        worst = max(worst, node.refcount)
        stack.extend(node.children.values())
    return worst


def test_cancel_racing_pipelined_step_releases_refcounts(gpt, gpt_tiny_solo):
    """cancel() racing a dispatched-but-unfetched pipelined step: the hit's
    radix references release (no pinned-block leak), the surviving neighbor's
    stream stays exact, and the freed slot immediately re-admits as a hit."""
    engine = make_engine(gpt, num_slots=2)  # pipeline defaults ON
    seed = list(range(1, 13)) + [30, 31]
    assert engine.generate(seed, 3) == gpt_tiny_solo(seed, 3)  # seeds the tree
    out = {"keep": [], "readmit": []}
    (keeper,) = engine.admit_many([([70, 71, 72], 8)])
    (victim,) = engine.admit_many([(seed[:12] + [40, 41], 20)])  # hit: holds refs
    for _ in range(2):
        for ev in engine.step():
            if ev.emit and ev.slot == keeper:
                out["keep"].append(ev.token)
    assert engine._inflight is not None  # a decode step is dispatched-unfetched
    assert engine._slot_path.get(victim)
    assert _max_refcount(engine.prefix_cache) > 0
    engine.cancel(victim)
    assert victim not in engine._slot_path
    # the keeper holds no blocks (3-token prompt < block size): nothing pinned
    assert _max_refcount(engine.prefix_cache) == 0
    # the freed slot re-admits as a hit on the still-cached prefix
    before = engine.prefill_tokens_computed
    (slot2,) = engine.admit_many([(seed[:12] + [50], 4)])
    assert slot2 == victim
    while engine.num_active or engine.has_pending_events:
        for ev in engine.step():
            if ev.emit:
                out["keep" if ev.slot == keeper else "readmit"].append(ev.token)
    assert out["keep"] == gpt_tiny_solo([70, 71, 72], 8)
    assert out["readmit"] == gpt_tiny_solo(seed[:12] + [50], 4)
    assert engine.prefill_tokens_computed - before == 1  # 12 of 13 restored
    assert _max_refcount(engine.prefix_cache) == 0  # retirement released the rest
