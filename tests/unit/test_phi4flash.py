"""The hybrid decoder (:mod:`unionml_tpu.models.phi4flash`): its layer layout,
the ring of a window layer and the windowed paged kernel, the grown
``cache_layout()`` contract on all three layouts, the parameter count at the
published widths, the kernel arms against the XLA arms through the engine, and
what an engine refuses for a layout with per-slot state. Agreement with the
plain reference is ``tests/perfbench/test_phi4flash.py``'s."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from unionml_tpu.models.latent_moe import LatentMoEConfig, LatentMoELMHeadModel
from unionml_tpu.models.phi4flash import (
    HybridCacheLayout, Phi4FlashConfig, Phi4FlashLMHeadModel, init_params, lambda_init, layer_kind, ring_blocks,
    ring_view,
)
from unionml_tpu.ops.paged_attention import paged_attention
from unionml_tpu.serving.continuous import ContinuousBatcher, DecodeEngine


@pytest.fixture(scope="module")
def tiny():
    config = Phi4FlashConfig.tiny()
    return Phi4FlashLMHeadModel(config), init_params(config, jax.random.PRNGKey(3))


def test_layer_layout_at_the_published_depth():
    kinds = [layer_kind(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[15] == "window"
    assert kinds[18] == "gmu" and kinds[19] == "cross" and kinds[31] == "cross" and kinds[0] == "mamba"
    assert abs(lambda_init(0) - 0.2) < 1e-12 and 0.79 < lambda_init(17) < 0.8


def test_parameter_count_at_the_published_widths_is_3_85_billion():
    tree = jax.eval_shape(lambda: init_params(Phi4FlashConfig()))["params"]
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert round(count(tree) / 1e9, 2) == 3.85
    assert [round(count(tree[f"layer_{i}"]) / 1e6, 1) for i in (16, 15, 17, 30, 31)] == [119.9, 98.3, 98.3, 104.9, 91.8]
    assert count(tree["embed"]) == 200064 * 2560 and "lm_head" not in tree  # the head is the embedding


@pytest.mark.parametrize("window,block", [(8, 4), (8, 3), (512, 128)])
def test_ring_view_orders_the_last_window_keys(window, block):
    """For every position: through the rotated table and the base it comes
    with, logical key ``c`` is the key at position ``c + first block's first
    position``, every key of the window is inside the table, and the newest is
    written where the view reads it."""
    count = ring_blocks(window, block)
    slots = jnp.asarray([0, 2])
    held = {}  # (ring block, offset) -> position, as the appends leave it
    for t in range(3 * count * block):
        position = jnp.asarray([t, t])
        table, base, dst, off = (np.asarray(x) for x in ring_view(
            position, jnp.asarray([True, False]), slots, window, block, scratch=99))
        assert (table[1] == 99).all() and base[1] == 0 and dst[1] == 99  # a row that is not live: scratch
        held[(int(dst[0]), int(off[0]))] = t
        assert 0 <= dst[0] < count and table[0].tolist() == sorted(set(table[0].tolist()), key=table[0].tolist().index)
        first = t - int(base[0])  # the position of logical key 0
        for key in range(max(0, t - window + 1), t + 1):
            logical = key - first
            assert 0 <= logical <= base[0] and base[0] - logical < window
            assert held[(int(table[0, logical // block]), logical % block)] == key


@pytest.mark.parametrize("impl,interpret", [("xla", False), ("pallas", True)])
def test_windowed_paged_attention_over_a_ring_is_dense_attention_over_the_window(impl, interpret):
    """Decode over a ring of 3 blocks of 4 with a window of 8, rows at positions
    2, 11 and 30 (inside the first window, past it, round the ring twice): the
    kernel through the rotated table against a softmax over the last 8 keys."""
    rng = np.random.default_rng(0)
    window, block, heads, groups, dim = 8, 4, 4, 2, 128
    count = ring_blocks(window, block)
    positions = [2, 11, 30]
    keys = jnp.asarray(rng.normal(size=(len(positions), 40, groups, 2 * dim)), jnp.float32)
    pool = jnp.zeros((len(positions) * count + 1, groups, block, 2 * dim), jnp.float32)
    slots = jnp.arange(len(positions))
    for t in range(max(positions) + 1):  # append token by token, as decode does
        _, _, dst, off = ring_view(jnp.full((len(positions),), t), jnp.asarray(positions) >= t, slots, window, block,
                                   scratch=pool.shape[0] - 1)
        pool = pool.at[dst, :, off, :].set(keys[:, t])
    q = jnp.asarray(rng.normal(size=(len(positions), heads, 1, dim)), jnp.float32)
    table, base, _, _ = ring_view(jnp.asarray(positions), jnp.ones(3, bool), slots, window, block, pool.shape[0] - 1)
    got = paged_attention(q, pool, None, table, base, impl=impl, interpret=interpret, sm_scale=0.3, window=window)
    for r, t in enumerate(positions):
        seen = keys[r, max(0, t - window + 1) : t + 1]  # (keys, groups, 2 dim)
        for h in range(heads):
            g = h // (heads // groups)
            scores = seen[:, g, :dim] @ q[r, h, 0] * 0.3
            want = jax.nn.softmax(scores) @ seen[:, g, dim:]
            np.testing.assert_allclose(got[r, h, 0], want, atol=2e-5)


def test_a_window_of_none_is_what_it_was():
    """``window=None`` is the call the other models make: unchanged."""
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(7, 2, 4, 32)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 2, 1, 16)), jnp.float32)
    table, base = jnp.asarray([[0, 1, 2], [3, 4, 5]]), jnp.asarray([9, 5])
    plain = paged_attention(q, pool, None, table, base, impl="xla")
    wide = paged_attention(q, pool, None, table, base, impl="xla", window=64)
    kernel = paged_attention(q, pool, None, table, base, impl="pallas", interpret=True, window=64)
    np.testing.assert_allclose(wide, plain, atol=1e-6)
    np.testing.assert_allclose(kernel, plain, atol=1e-5)


LAYOUTS = {
    "per_head": lambda: GPTLMHeadModel(GPTConfig.tiny()).cache_layout(),
    "latent": lambda: LatentMoELMHeadModel(LatentMoEConfig.tiny()).cache_layout(),
    "hybrid": lambda: Phi4FlashLMHeadModel(Phi4FlashConfig.tiny()).cache_layout(),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_every_layout_answers_the_grown_contract(name):
    """What the engine asks of any layout: the two older ones answer with one
    group under the table and no per-slot state, the hybrid one with both."""
    layout = LAYOUTS[name]()
    pool = layout.init_block_pool(9, 4, num_slots=2)
    paged = layout.paged(pool)
    local = layout.init_cache(2, 8)
    assert set(layout.join(local)) == set(paged)  # the workspace's layers under the table are the pool's
    blocks = {leaf.shape[0] for layer in paged.values() for leaf in layer.values()}
    assert blocks == {9}
    fixed = layout.slot_bytes(4)
    assert set(fixed) == {"state", "ring"} and layout.block_bytes(4) > 0
    out = layout.insert_slot_state(pool, local, jnp.asarray([1, 0]), jnp.asarray([5, 8]))
    assert jax.tree.structure(out) == jax.tree.structure(pool)
    if name == "hybrid":
        assert layout.slot_state == ("recurrent state", "window ring") and layout.tail_layers == 2
        assert set(paged) == {"layer_5"} and fixed["state"] > 0 and fixed["ring"] > 0
        assert pool["layer_1"]["kv"].shape[0] == 2 * ring_blocks(8, 4) + 1  # two slots' rings and scratch
        assert pool["layer_0"]["ssm"].shape == (2, 4, 64) and "layer_6" not in pool and "layer_7" not in pool
        with pytest.raises(ValueError, match="num_slots"):
            layout.init_block_pool(9, 4)
    else:
        assert layout.slot_state == () and layout.tail_layers == 0 and fixed == {"state": 0, "ring": 0}
        assert paged is pool and out is pool


def test_kernel_arms_through_the_engine_emit_the_xla_arms_tokens(tiny):
    """The windowed ring walk, the full-cache walk and the state-update kernel
    under the Pallas interpreter, pipelined, against the XLA arms: the same
    tokens, and the same logits to rounding."""
    _, params = tiny
    prompts = [[5, 9, 2, 7, 1], list(range(3, 22))]
    ends = {}
    for arm, options in (("xla", {}), ("kernels", dict(paged_attn_impl="pallas", ssm_impl="pallas", interpret=True))):
        model = Phi4FlashLMHeadModel(Phi4FlashConfig.tiny(**options))
        engine = DecodeEngine(model, params, num_slots=2, max_len=64, prefix_block_size=4,
                              prefill_buckets=(4, 8, 32), prefill_chunk=6)
        slots = engine.admit_many([(p, 14) for p in prompts])
        out = {slot: [] for slot in slots}
        while engine.busy:
            for event in engine.step():
                if event.emit:
                    out[event.slot].append(event.token)
        ends[arm] = [out[slot] for slot in slots]
        assert all(len(tokens) == 14 for tokens in ends[arm])
    assert ends["kernels"] == ends["xla"]


def test_a_dense_workspace_is_a_prefill_from_position_nought(tiny):
    model, params = tiny
    ids = jnp.zeros((1, 8), jnp.int32)
    cache = model.cache_layout().init_cache(1, 8)
    with pytest.raises(ValueError, match="prefill from position 0"):
        model.apply(params, ids, cache=cache, position=jnp.asarray(3))
    logits, new = model.apply(params, ids, cache=cache, position=0, logit_rows=jnp.asarray([4]))
    assert logits.shape == (1, 1, 256) and set(new) == set(cache)


# ------------------------------------------------------------------ refusals


def _engine(tiny, **options):
    model, params = tiny
    return DecodeEngine(model, params, **{**dict(num_slots=2, max_len=64, prefix_block_size=4), **options})


@pytest.mark.parametrize("options,what", [
    ({"prefix_cache_blocks": 8}, "prefix_cache_blocks > 0"),
    ({"kv_quantize": "int8"}, "kv_quantize='int8'"),
    ({"paged": False}, "paged=False"),
])
def test_an_engine_refuses_by_name_what_cannot_follow_per_slot_state(tiny, options, what):
    with pytest.raises(ValueError) as raised:
        _engine(tiny, **options)
    assert what in str(raised.value) and "recurrent state and window ring" in str(raised.value)


def test_a_mesh_is_refused_by_name(tiny):
    from unionml_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match=r"mesh= \(a device mesh\).*recurrent state and window ring"):
        _engine(tiny, mesh=make_mesh({"tensor": 2}, devices=jax.devices()[:2]))


def test_prefix_cache_and_preemption_are_refused_after_construction_too(tiny):
    engine = _engine(tiny)
    with pytest.raises(ValueError, match="radix prefix cache.*recurrent state and window ring"):
        engine.enable_prefix_cache(8, 4)
    slot = engine.add_request([1, 2, 3], 4)
    with pytest.raises(ValueError, match="preempt.*recurrent state and window ring"):
        engine.preempt(slot)
    assert engine.preemptible is False and engine._active[slot]  # the slot runs on


def test_speculative_engine_refuses_a_model_with_per_slot_state(tiny):
    from unionml_tpu.serving.speculative import SpeculativeEngine

    model, params = tiny
    draft = GPTLMHeadModel(GPTConfig.tiny(vocab_size=256))
    with pytest.raises(ValueError, match="SpeculativeEngine.*target.*recurrent state and window ring"):
        SpeculativeEngine(model, params, draft, None, num_slots=2, max_len=64)
    gpt = GPTLMHeadModel(GPTConfig.tiny(vocab_size=256))
    with pytest.raises(ValueError, match="SpeculativeEngine.*draft.*recurrent state and window ring"):
        SpeculativeEngine(gpt, None, model, params, num_slots=2, max_len=64)


def test_the_slo_scheduler_takes_such_an_engine_for_one_it_cannot_preempt(tiny):
    """A batch hog on the only slot and an interactive arrival behind it: no
    victim is picked and no request fails; the arrival waits its turn."""
    engine = _engine(tiny, num_slots=1, prefill_buckets=(8, 16, 32))
    batcher = ContinuousBatcher(engine)

    async def main():
        hog = asyncio.ensure_future(batcher.generate([9, 9, 1, 2], 30, priority="batch"))
        while not engine.num_active:
            await asyncio.sleep(0.01)
        inter = await batcher.generate([3, 1, 4], 4, priority="interactive")
        return inter, await hog

    try:
        inter, hog = asyncio.run(main())
    finally:
        batcher.close()
    assert len(inter) == 4 and len(hog) == 30
    assert batcher.scheduler.stats()["preemptions"] == 0 and engine.preempted_requests == 0
    assert engine.pipeline_stats()["state_resets"] == 2
