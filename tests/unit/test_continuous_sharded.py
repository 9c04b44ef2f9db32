"""Mesh-sharded serving engine: tensor-parallel decode, batched prefill admission.

The gold properties:

1. a ``DecodeEngine`` sharded over a mesh (params Megatron-split, KV cache
   sharded over attention heads on the ``tensor`` axis) emits tokens
   byte-identical to the single-device engine — on mesh sizes 4 and 8 of the
   suite's forced 8-CPU platform, no hardware needed;
2. admission is BATCHED: N queued prompts admit in ⌈N/prefill_batch⌉ prefill
   dispatches (and ≤ that many engine ticks), with outputs unchanged;
3. long prompts prefill in CHUNKS between decode steps without perturbing
   in-flight neighbors.
"""

import asyncio
import math

import jax
import numpy as np
import pytest

from unionml_tpu.models.gpt import generate
from unionml_tpu.parallel import make_mesh
from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine

REQUESTS = [([3, 1, 4, 1, 5], 6), ([2, 7], 5), ([1, 8, 2, 8, 1, 8, 2, 8], 4), ([6], 6)]


@pytest.fixture(scope="module")
def gpt(gpt_tiny_session):
    _, model, variables = gpt_tiny_session
    return model, variables


@pytest.fixture(scope="module")
def expected(gpt):
    model, variables = gpt
    return [solo(model, variables, p, n) for p, n in REQUESTS]


def solo(model, variables, prompt, n):
    """Reference: the one-shot batch-1 generate path."""
    import jax.numpy as jnp

    ids = jnp.asarray(np.asarray(prompt, dtype=np.int32)[None])
    out = generate(model, variables, ids, n)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def drain(engine, slots):
    out = {s: [] for s in slots}
    while engine.num_active or engine.has_pending_prefill:
        for ev in engine.step():
            if ev.emit:
                out[ev.slot].append(ev.token)
    return [out[s] for s in slots]


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest forces 8 CPU devices)")
    return make_mesh(axes, devices=jax.devices()[:n])


# --------------------------------------------------------------- sharded decode


@pytest.mark.parametrize(
    "axes", [{"tensor": 4}, {"data": 2, "tensor": 4}], ids=["mesh4", "mesh8"]
)
def test_sharded_engine_tokens_byte_identical(gpt, expected, axes):
    """Tensor-parallel engine == single-device engine, token for token."""
    model, variables = gpt
    mesh = _mesh(axes)
    reference = DecodeEngine(model, variables, num_slots=4, max_len=64, prefill_buckets=(8, 16))
    sharded = DecodeEngine(
        model, variables, num_slots=4, max_len=64, prefill_buckets=(8, 16), mesh=mesh
    )
    ref_out = drain(reference, reference.admit_many(REQUESTS))
    sh_out = drain(sharded, sharded.admit_many(REQUESTS))
    assert sh_out == ref_out == expected


def test_sharded_cache_is_head_sharded(gpt):
    """The dense-compat KV cache shards over heads on the tensor axis (not
    replicated). The paged pool's equivalent layout is asserted in
    test_prefix_cache.py::test_mesh_pool_is_head_sharded."""
    model, variables = gpt
    mesh = _mesh({"tensor": 4})
    engine = DecodeEngine(
        model, variables, num_slots=2, max_len=32, prefill_buckets=(8,), mesh=mesh, paged=False
    )
    leaf = engine._cache["layer_0"]["k"]  # (slots, heads=4, max_len, head_dim)
    assert len(leaf.sharding.device_set) == 4
    # each device holds 1 of the 4 heads
    shard = leaf.addressable_shards[0]
    assert shard.data.shape[1] == 1


def test_sharded_engine_sampled_stream_matches(gpt):
    """Sampling path under the mesh: same seed => same stream as single-device."""
    model, variables = gpt
    mesh = _mesh({"tensor": 4})
    prompt = [3, 1, 4, 1, 5]
    a = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
                     temperature=0.8, seed=7)
    b = DecodeEngine(model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
                     temperature=0.8, seed=7, mesh=mesh)
    assert a.generate(prompt, 8) == b.generate(prompt, 8)


def test_sharded_engine_lookahead_matches(gpt, expected):
    """Fused multi-step scans compose with the mesh layout."""
    model, variables = gpt
    mesh = _mesh({"data": 2, "tensor": 4})
    engine = DecodeEngine(
        model, variables, num_slots=4, max_len=64, prefill_buckets=(8, 16), mesh=mesh
    )
    slots = engine.admit_many(REQUESTS)
    out = {s: [] for s in slots}
    while engine.num_active:
        for ev in engine.step(4):
            if ev.emit:
                out[ev.slot].append(ev.token)
    assert [out[s] for s in slots] == expected


def test_mesh_composes_with_quantize(gpt):
    """The former mutual exclusion is lifted: QuantizedArray {q, scale} leaves
    get param_shardings entries (scale inherits the kernel's channel-axis
    split), so the meshed int8 engine streams token-identically to solo int8."""
    model, variables = gpt
    mesh = _mesh({"tensor": 4})
    prompt = [3, 1, 4, 1, 5]
    solo = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8,), quantize="int8"
    )
    meshed = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(8,),
        quantize="int8", mesh=mesh,
    )
    assert meshed.generate(prompt, 8) == solo.generate(prompt, 8)


# ------------------------------------------------------------ batched admission


def test_batched_admission_dispatch_count_and_outputs(gpt):
    """N same-bucket prompts admit in ⌈N/prefill_batch⌉ prefill dispatches."""
    model, variables = gpt
    n, k = 6, 4
    prompts = [([3 + i, 1, 4], 4) for i in range(n)]
    engine = DecodeEngine(
        model, variables, num_slots=8, max_len=64, prefill_buckets=(8,), prefill_batch=k
    )
    slots = engine.admit_many(prompts)
    assert engine.prefill_dispatches == math.ceil(n / k)
    assert drain(engine, slots) == [solo(model, variables, p, b) for p, b in prompts]


def test_queued_prompts_admit_in_ceil_n_over_k_ticks(gpt):
    """The admission loop (pop up to free slots, one admit_many per tick) lands
    N queued prompts in ≤ ⌈N/k⌉ engine ticks, outputs unchanged."""
    model, variables = gpt
    n, k = 6, 2
    pending = [([3 + i, 1, 4], 3) for i in range(n)]
    want = [solo(model, variables, p, b) for p, b in pending]
    engine = DecodeEngine(
        model, variables, num_slots=8, max_len=64, prefill_buckets=(8,), prefill_batch=k
    )
    ticks_until_admitted, slots, out = 0, [], {}
    while pending:
        ticks_until_admitted += 1
        free = len(engine.free_slots)
        batch, pending = pending[:free], pending[free:]
        for slot in engine.admit_many(batch):
            slots.append(slot)
            out[slot] = []
        for ev in engine.step():
            if ev.emit:
                out[ev.slot].append(ev.token)
    assert ticks_until_admitted <= math.ceil(n / k)
    assert engine.prefill_dispatches == math.ceil(n / k)
    while engine.num_active:
        for ev in engine.step():
            if ev.emit:
                out[ev.slot].append(ev.token)
    assert [out[s] for s in slots] == want


def test_admission_batches_mixed_buckets(gpt):
    """Prompts spanning buckets group per bucket; outputs still exact."""
    model, variables = gpt
    requests = [([1, 2], 3), ([2, 3, 4, 5, 6, 7, 8, 9, 1, 2], 3), ([9, 8], 3), ([7], 3)]
    engine = DecodeEngine(
        model, variables, num_slots=4, max_len=64, prefill_buckets=(4, 16), prefill_batch=4
    )
    slots = engine.admit_many(requests)
    # bucket 4 holds three prompts (1 dispatch), bucket 16 one prompt (1 dispatch)
    assert engine.prefill_dispatches == 2
    assert drain(engine, slots) == [solo(model, variables, p, b) for p, b in requests]


def test_admit_many_validates_before_scheduling(gpt):
    """One bad request rejects the whole call with nothing scheduled."""
    model, variables = gpt
    engine = DecodeEngine(model, variables, num_slots=4, max_len=16, prefill_buckets=(4,))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.admit_many([([1, 2], 3), ([], 3)])
    assert engine.num_active == 0 and engine.prefill_dispatches == 0
    with pytest.raises(RuntimeError, match="no free decode slots"):
        engine.admit_many([([1, 2], 3)] * 5)
    assert engine.num_active == 0


def test_batcher_overload_batched_admission(gpt):
    """More concurrent requests than slots: the batcher admits in batches as
    slots retire, every completion exact."""
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=3, max_len=64, prefill_buckets=(8,), prefill_batch=2
    )
    batcher = ContinuousBatcher(engine)
    requests = [([3 + i, 1, 4], 3 + (i % 3)) for i in range(7)]
    expected = [solo(model, variables, p, n) for p, n in requests]

    async def main():
        return await asyncio.gather(*(batcher.generate(p, n) for p, n in requests))

    try:
        results = asyncio.run(main())
    finally:
        batcher.close()
    assert results == expected


# -------------------------------------------------------------- chunked prefill


def test_chunked_prefill_matches_solo(gpt):
    model, variables = gpt
    prompt = list(range(1, 11))  # 10 tokens, chunk=4 -> 3 chunks
    engine = DecodeEngine(
        model, variables, num_slots=2, max_len=64, prefill_buckets=(16,), prefill_chunk=4
    )
    assert engine.generate(prompt, 6) == solo(model, variables, prompt, 6)
    assert not engine.has_pending_prefill


def test_chunked_prefill_interleaves_without_perturbing_neighbors(gpt):
    """A long prompt's chunked prefill rides between decode steps: the already-
    decoding neighbor's stream is untouched, and both match solo."""
    model, variables = gpt
    long_prompt = list(range(1, 11))
    engine = DecodeEngine(
        model, variables, num_slots=2, max_len=64, prefill_buckets=(8, 16), prefill_chunk=4
    )
    out = {}

    def pump(events):
        for ev in events:
            if ev.emit:
                out[ev.slot].append(ev.token)

    s0 = engine.add_request([3, 1, 4, 1, 5], 8)
    out[s0] = []
    pump(engine.step())
    pump(engine.step())
    (s1,) = engine.admit_many([(long_prompt, 5)])
    out[s1] = []
    assert engine.has_pending_prefill and not engine._active[s1]
    while engine.num_active or engine.has_pending_prefill:
        pump(engine.step())
    assert out[s0] == solo(model, variables, [3, 1, 4, 1, 5], 8)
    assert out[s1] == solo(model, variables, long_prompt, 5)


def test_chunked_prefill_under_mesh(gpt):
    model, variables = gpt
    mesh = _mesh({"tensor": 4})
    prompt = list(range(1, 11))
    engine = DecodeEngine(
        model, variables, num_slots=2, max_len=64, prefill_buckets=(16,),
        prefill_chunk=4, mesh=mesh,
    )
    assert engine.generate(prompt, 6) == solo(model, variables, prompt, 6)


def test_cancel_pending_chunked_prefill_frees_slot(gpt):
    model, variables = gpt
    engine = DecodeEngine(
        model, variables, num_slots=1, max_len=64, prefill_buckets=(16,), prefill_chunk=4
    )
    (slot,) = engine.admit_many([(list(range(1, 11)), 5)])
    assert engine.has_pending_prefill and not engine.free_slots
    engine.cancel(slot)
    assert not engine.has_pending_prefill and engine.free_slots == [slot]
    # the freed slot serves the next request exactly
    assert engine.generate([3, 1, 4], 4) == solo(model, variables, [3, 1, 4], 4)


def test_vocab_the_tensor_axis_does_not_divide_is_replicated():
    """GPT-2's real vocabulary (50257 rows) does not divide over ``tensor=4``;
    the spec table asks for it anyway (it sees axis names, not shapes). The
    engine's placement replicates that dimension and serves the same tokens."""
    import jax.numpy as jnp

    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel, init_params

    config = GPTConfig.tiny(vocab_size=509, dropout=0.0, dtype=jnp.float32, attention_impl="xla")
    model, variables = GPTLMHeadModel(config), init_params(config, seq_len=8)
    kw = dict(num_slots=2, max_len=32, prefill_buckets=(8,))
    sharded = DecodeEngine(model, variables, mesh=_mesh({"data": 1, "tensor": 4}), **kw)
    embedding = sharded._variables["params"]["wte"]["embedding"]
    assert embedding.sharding.is_fully_replicated
    qkv = sharded._variables["params"]["layer_0"]["qkv"]["kernel"]
    assert not qkv.sharding.is_fully_replicated  # everything that divides still shards
    prompt = [3, 1, 4, 1, 5]
    assert sharded.generate(prompt, 5) == DecodeEngine(model, variables, **kw).generate(prompt, 5)
