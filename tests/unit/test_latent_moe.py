"""The latent-attention, routed-expert, hyper-connection decoder
(``models/latent_moe.py``) against itself: the two forms of its attention,
its expert path against the dense definition, its Sinkhorn, and what the
serving engine refuses of it. Against its plain reference (full forward,
prefill and decode through ``DecodeEngine``): ``tests/perfbench/test_xing4.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import latent_moe
from unionml_tpu.models.latent_moe import LatentMoEConfig, LatentMoELMHeadModel
from unionml_tpu.parallel.ep import moe_apply_grouped
from unionml_tpu.serving.continuous import DecodeEngine


@pytest.fixture(scope="module")
def tiny():
    config = LatentMoEConfig.tiny()
    return config, LatentMoELMHeadModel(config), latent_moe.init_params(config, jax.random.PRNGKey(3))


def test_absorbed_attention_is_the_expanded_attention(tiny):
    """A prompt prefilled (expanded form: per-head keys and values from the
    fresh latents) and then decoded token by token over the dense latent cache
    (absorbed form: every head against the one latent row) gives the logits of
    the whole sequence in one expanded pass. float32: what is left, 2e-5, is
    the order of the sums (``q W_k^T . c`` against ``q . W_k c``)."""
    config, model, variables = tiny
    ids = jnp.asarray(np.random.default_rng(0).integers(0, config.vocab_size, (2, 20)))
    full = model.apply(variables, ids)
    cache = model.cache_layout().init_cache(2, 32)
    logits, cache = model.apply(variables, ids[:, :9], cache=cache, position=0)
    np.testing.assert_allclose(logits, full[:, :9], atol=2e-5)
    for t in range(9, 20):
        position = jnp.full((2,), t, jnp.int32) if t % 2 else t  # per-row and shared positions
        logits, cache = model.apply(variables, ids[:, t : t + 1], cache=cache, position=position)
        np.testing.assert_allclose(logits[:, 0], full[:, t], atol=2e-5)
    # a mid-sequence chunk over the cache is absorbed too
    cache = model.cache_layout().init_cache(2, 32)
    _, cache = model.apply(variables, ids[:, :8], cache=cache, position=0)
    logits, _ = model.apply(variables, ids[:, 8:20], cache=cache, position=jnp.int32(8))
    np.testing.assert_allclose(logits, full[:, 8:20], atol=2e-5)


def test_cache_row_is_padded_to_whole_lane_tiles():
    real = LatentMoEConfig()
    assert (real.latent_dim, real.cache_row_dim) == (576, 640)
    assert real.softmax_scale == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    layout = LatentMoELMHeadModel(LatentMoEConfig.tiny()).cache_layout()
    pool = layout.init_block_pool(5, 4)
    assert set(pool["layer_0"]) == {"kv"} and pool["layer_0"]["kv"].shape == (5, 1, 4, 128)
    assert layout.kernel_key == (4, 128) and layout.kv_heads == 1
    assert layout.pool_bytes(pool) == (3 * 5 * 4 * 128 * 4,) * 2 == (layout.block_bytes(4) * 5,) * 2


def _dense_moe(tokens, gate, up, down, chosen, weights):
    """Every expert over every token, the chosen ones kept: the definition."""
    out = np.zeros_like(tokens)
    for e in range(gate.shape[0]):
        hidden = jax.nn.silu(tokens @ gate[e]) * (tokens @ up[e])
        weight = np.where(chosen == e, weights, 0.0).sum(-1)
        out += np.asarray(weight[:, None] * (hidden @ down[e]))
    return out


@pytest.mark.parametrize("routing", ["random", "one_expert_takes_every_row"])
def test_grouped_experts_match_the_dense_definition(routing):
    """Sorted pairs and one ``ragged_dot`` a projection against every expert
    over every token under a mask. Also when the routing is as uneven as it
    can be: one expert gets every token's first choice, another no row at all
    (an empty group in the middle of the sort)."""
    rng = np.random.default_rng(1)
    experts, tokens_n, d, width, k = 8, 24, 16, 12, 2
    tokens = rng.normal(size=(tokens_n, d)).astype(np.float32)
    gate, up = (rng.normal(size=(experts, d, width)).astype(np.float32) * 0.3 for _ in range(2))
    down = rng.normal(size=(experts, width, d)).astype(np.float32) * 0.3
    if routing == "random":
        chosen = np.stack([rng.permutation(experts)[:k] for _ in range(tokens_n)])
    else:
        chosen = np.stack([np.full(tokens_n, 5), rng.choice([0, 1, 2, 4, 6, 7], tokens_n)], axis=1)
    weights = rng.uniform(0.1, 1.0, (tokens_n, k)).astype(np.float32)
    out, sizes = jax.jit(lambda *a: moe_apply_grouped(latent_moe.grouped_swiglu, a[:3], *a[3:]))(
        gate, up, down, tokens, chosen, weights
    )
    np.testing.assert_allclose(out, _dense_moe(tokens, gate, up, down, chosen, weights), atol=2e-5)
    np.testing.assert_array_equal(sizes, np.bincount(chosen.reshape(-1), minlength=experts))
    if routing != "random":
        assert sizes[5] == tokens_n and sizes[3] == 0 and int(sizes.sum()) == tokens_n * k


def test_sinkhorn_is_doubly_stochastic_and_the_clamp_holds_it_finite(tiny):
    config, _, _ = tiny
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(50, 4, 4)) * 0.5, jnp.float32)
    matrix = latent_moe.sinkhorn(logits, config.hc_sinkhorn_iters, config.hc_eps)
    # 20 rounds on logits of unit spread: both sums at 1 to 1e-5 (float32 sums of 4);
    # the columns are always there (the last division), the rows converge to it
    np.testing.assert_allclose(matrix.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(matrix.sum(-2), 1.0, atol=1e-5)
    assert (np.asarray(matrix) > 0).all()
    # maps far outside the clamp: exp(300) is inf and Sinkhorn of it NaN; the
    # module clamps to +-30 first, and its H_res is that of the clamped maps
    module = latent_moe.HyperConnection(config)
    streams = jnp.asarray(np.random.default_rng(3).normal(size=(6, 4, config.hidden_size)), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), streams)
    wild = jax.tree.map(lambda x: x, params)
    wild["params"]["alpha"] = jnp.asarray([1.0, 1.0, 300.0])
    wild["params"]["phi"] = wild["params"]["phi"] * 50.0
    pre, post, res = module.apply(wild, streams)
    assert np.isfinite(res).all() and (np.asarray(pre) <= 1).all() and (np.asarray(post) <= 2).all()
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)  # the last division is the columns'
    flat = latent_moe.rms_norm(streams.reshape(6, -1), None, config.rms_norm_eps, jnp.float32)
    raw = 300.0 * (flat @ wild["params"]["phi"])[:, 8:].reshape(6, 4, 4) + wild["params"]["bias"][8:].reshape(4, 4)
    assert float(jnp.abs(raw).max()) > 30.0
    np.testing.assert_allclose(
        res, latent_moe.sinkhorn(jnp.clip(raw, -30.0, 30.0), 20, config.hc_eps), rtol=1e-4, atol=1e-6
    )


def test_step_counters_reach_pipeline_stats(tiny):
    """``expert_rows``, ``experts_hit``, ``expert_rows_max``: decode steps only,
    every row of the step's program (retired slots route too), summed over the
    expert layers; fetched with the step's tokens."""
    config, model, variables = tiny
    engine = DecodeEngine(model, variables, num_slots=3, max_len=48, prefill_buckets=(8,), prefix_block_size=4)
    assert "expert_rows" not in engine.pipeline_stats()
    engine.admit_many([([1, 2, 3, 4, 5], 6), ([9, 8, 7], 4)])
    while engine.busy:
        engine.step()
    stats = engine.pipeline_stats()
    expert_layers = config.num_layers - config.first_k_dense_replace
    assert stats["expert_rows"] == stats["step_dispatches"] * 3 * config.num_experts_per_tok * expert_layers
    assert expert_layers * stats["step_dispatches"] <= stats["experts_hit"] <= stats["expert_rows"]
    assert stats["expert_rows_max"] * config.n_routed_experts >= stats["expert_rows"]


def test_what_a_latent_cache_does_not_serve_is_refused_by_name(tiny):
    config, model, variables = tiny
    with pytest.raises(ValueError, match="kv_quantize='int8' with a latent cache layout"):
        DecodeEngine(model, variables, num_slots=2, max_len=32, prefill_buckets=(8,), kv_quantize="int8")
    from unionml_tpu.serving.speculative import SpeculativeEngine

    with pytest.raises(ValueError, match="SpeculativeEngine with a LatentCacheLayout target"):
        SpeculativeEngine(model, variables, model, variables, num_slots=2, max_len=32, prefill_buckets=(8,))
