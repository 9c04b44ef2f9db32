"""MoE layer: router losses, flax module, aux-loss collection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import MoEMlp, collect_aux_losses, load_balancing_loss, router_z_loss
from unionml_tpu.parallel import make_mesh


def test_load_balancing_loss_is_one_at_uniform():
    E, T = 4, 64
    gates = jnp.full((T, E), 1.0 / E)
    index = jnp.arange(T) % E  # perfectly balanced top choices
    loss = load_balancing_loss(gates, index, E)
    np.testing.assert_allclose(float(loss), 1.0, atol=1e-6)

    # collapse onto one expert: strictly worse
    collapsed = load_balancing_loss(
        jax.nn.softmax(jnp.tile(jnp.asarray([[9.0, 0.0, 0.0, 0.0]]), (T, 1))),
        jnp.zeros(T, dtype=jnp.int32),
        E,
    )
    assert float(collapsed) > 2.0


def test_router_z_loss_penalizes_large_logits():
    small = router_z_loss(jnp.zeros((8, 4)))
    large = router_z_loss(jnp.full((8, 4), 20.0))
    assert float(large) > float(small)


def test_moe_mlp_forward_and_aux_losses():
    layer = MoEMlp(num_experts=4, hidden_size=32, k=2, capacity_factor=4.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)), dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    out, state = layer.apply(params, x, mutable=["intermediates"])
    assert out.shape == x.shape
    aux = collect_aux_losses(state["intermediates"])
    assert float(aux) > 0.0


def test_moe_mlp_trains_end_to_end():
    """Gradients flow through router AND experts; aux loss is differentiable."""
    layer = MoEMlp(num_experts=4, hidden_size=16, k=2, capacity_factor=4.0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(32, 8)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(32, 8)), dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)

    @jax.jit
    def loss_fn(params):
        out, state = layer.apply(params, x, mutable=["intermediates"])
        return jnp.mean((out - y) ** 2) + collect_aux_losses(state["intermediates"])

    grads = jax.grad(loss_fn)(params)
    flat = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(grads)[0]}
    router_grads = [v for k, v in flat.items() if "router" in k]
    expert_grads = [v for k, v in flat.items() if "w_in" in k or "w_out" in k]
    assert router_grads and all(float(jnp.sum(jnp.abs(g))) > 0 for g in router_grads)
    assert expert_grads and all(float(jnp.sum(jnp.abs(g))) > 0 for g in expert_grads)

    before = float(loss_fn(params))
    params2 = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    after = float(loss_fn(params2))
    assert after < before


def test_moe_mlp_expert_sharded_on_mesh():
    mesh = make_mesh({"data": 2, "expert": 4})
    layer = MoEMlp(num_experts=8, hidden_size=16, k=2, capacity_factor=4.0, mesh=mesh)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(4, 8, 16)), dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    out = jax.jit(lambda p, x: layer.apply(p, x))(params, x)
    assert out.shape == x.shape


def test_moe_mlp_a2a_dispatch_matches_gshard():
    """dispatch='a2a' (explicit all-to-all token movement) computes the same layer
    as the gshard einsum dispatch when capacity is ample — same params, same
    router, different comms layout."""
    mesh = make_mesh({"data": 2, "expert": 4})
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 16, 16)), dtype=jnp.float32)
    kwargs = dict(num_experts=8, hidden_size=16, k=2, capacity_factor=8.0, mesh=mesh)
    gshard = MoEMlp(dispatch="gshard", **kwargs)
    a2a = MoEMlp(dispatch="a2a", **kwargs)
    params = gshard.init(jax.random.PRNGKey(1), x)  # identical param trees

    def out_and_grads(layer):
        # forward + backward in ONE compile per layer (compile time dominates)
        def fn(p):
            out = layer.apply(p, x)
            return jnp.sum(out ** 2), out

        grads, out = jax.grad(fn, has_aux=True)(params)
        return out, grads

    out_g, g_g = out_and_grads(gshard)
    out_a, g_a = out_and_grads(a2a)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_g), atol=2e-5)
    # gradients agree too (both paths are exact when nothing drops)
    for a, b in zip(jax.tree_util.tree_leaves(g_a), jax.tree_util.tree_leaves(g_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_moe_mlp_a2a_requires_mesh():
    x = jnp.ones((2, 4, 8))
    layer = MoEMlp(num_experts=4, hidden_size=8, dispatch="a2a")
    with pytest.raises(ValueError, match="requires a mesh"):
        layer.init(jax.random.PRNGKey(0), x)
    # a mesh WITHOUT an 'expert' axis gets the same clear error, not a KeyError
    no_expert = MoEMlp(
        num_experts=4, hidden_size=8, dispatch="a2a", mesh=make_mesh({"data": 8})
    )
    with pytest.raises(ValueError, match="requires a mesh with an 'expert' axis"):
        no_expert.init(jax.random.PRNGKey(0), x)


def test_moe_mlp_rejects_unknown_dispatch():
    layer = MoEMlp(num_experts=4, hidden_size=8, dispatch="nccl")
    with pytest.raises(ValueError, match="gshard.*a2a"):
        layer.init(jax.random.PRNGKey(0), jnp.ones((2, 4, 8)))


def test_gpt_moe_a2a_trains_end_to_end():
    """A sparse MoE-GPT with moe_dispatch='a2a' takes a packed LM train step on the
    8-device mesh and produces a finite loss matching the gshard dispatch at step 0
    (ample capacity: routing identical, only the comms layout differs)."""
    from unionml_tpu.models import GPTConfig, GPTLMHeadModel, create_train_state
    from unionml_tpu.models.training import make_lm_train_step

    mesh = make_mesh({"data": 2, "expert": 4})
    batch, seq = 4, 16  # 64 tokens: divisible by the 8 token shards
    tokens = jnp.asarray(np.random.default_rng(9).integers(1, 64, size=(batch, seq)), jnp.int32)

    losses = {}
    for dispatch in ("gshard", "a2a"):
        cfg = GPTConfig.tiny(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=seq, dropout=0.0, dtype=jnp.float32,
            moe_every=1, num_experts=8, moe_k=2, moe_capacity_factor=8.0,
            moe_dispatch=dispatch, ep_mesh=mesh,
        )
        model = GPTLMHeadModel(cfg)
        variables = model.init(
            {"params": jax.random.PRNGKey(0)}, tokens, deterministic=True
        )
        state = create_train_state(model, variables, learning_rate=1e-3)
        step = make_lm_train_step(moe_aux=True)
        new_state, metrics = step(state, {"input_ids": tokens})
        losses[dispatch] = float(metrics["loss"])
        assert np.isfinite(losses[dispatch])
    np.testing.assert_allclose(losses["a2a"], losses["gshard"], rtol=1e-4)


def test_dropless_mode_never_drops_under_imbalance():
    """Review regression: with a fully-collapsed router, capacity mode drops tokens
    but the dropless grouped path matches the dense per-token computation exactly
    (every row of expert 0's group and of expert 1's; experts 2 and 3 get none)."""
    from unionml_tpu.parallel.ep import moe_apply_grouped, moe_apply_topk

    rng = np.random.default_rng(6)
    E, D, T = 4, 8, 32
    eW = jnp.asarray(rng.normal(size=(E, D, D)) * 0.3, dtype=jnp.float32)
    tokens = jnp.asarray(rng.normal(size=(T, D)), dtype=jnp.float32)
    logits = np.full((T, E), -10.0, dtype=np.float32)
    logits[:, 0] = 5.0  # every token's top-1 collapses onto expert 0
    logits[:, 1] = 2.0
    gates = jax.nn.softmax(jnp.asarray(logits), axis=-1)

    top_g, _ = jax.lax.top_k(gates, 2)
    g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    ref = g[:, :1] * (tokens @ eW[0]) + g[:, 1:2] * (tokens @ eW[1])

    _, top_index = jax.lax.top_k(gates, 2)
    dropless, sizes = moe_apply_grouped(
        lambda W, rows, sizes: jax.lax.ragged_dot(rows, W, sizes), eW, tokens, top_index, g
    )
    np.testing.assert_allclose(np.asarray(dropless), np.asarray(ref), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sizes), [T, T, 0, 0])

    capped = moe_apply_topk(lambda W, t: t @ W, eW, tokens, gates, k=2, capacity_factor=1.0)
    assert np.abs(np.asarray(capped) - np.asarray(ref)).max() > 1e-3  # drops happened


def test_router_jitter_noise_training_only():
    """Switch-style jitter perturbs routing only when an rng stream is supplied."""
    layer = MoEMlp(num_experts=4, hidden_size=16, k=1, capacity_factor=4.0, router_noise=0.3)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)), dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)

    # no rng (eval): deterministic and identical to a noise-free layer
    quiet = MoEMlp(num_experts=4, hidden_size=16, k=1, capacity_factor=4.0, router_noise=0.0)
    np.testing.assert_array_equal(
        np.asarray(layer.apply(params, x)), np.asarray(quiet.apply(params, x))
    )

    # with rng streams, different keys perturb the routing
    out_a = layer.apply(params, x, rngs={"dropout": jax.random.PRNGKey(1)})
    out_b = layer.apply(params, x, rngs={"dropout": jax.random.PRNGKey(2)})
    assert float(jnp.max(jnp.abs(out_a - out_b))) > 0.0


def test_router_noise_respects_deterministic_flag():
    """deterministic=True silences jitter even when an rng stream is supplied."""
    layer = MoEMlp(num_experts=4, hidden_size=16, k=1, capacity_factor=4.0, router_noise=0.3)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, 8)), dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    out_a = layer.apply(params, x, deterministic=True, rngs={"dropout": jax.random.PRNGKey(1)})
    out_b = layer.apply(params, x, deterministic=True, rngs={"dropout": jax.random.PRNGKey(2)})
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def test_dropless_on_an_expert_mesh_is_the_unsharded_layer_and_gathers_no_weights():
    """``dropless=True`` (inference) with the stacked expert weights sharded over
    the mesh's ``expert`` axis: the grouped path takes no mesh argument, so XLA's
    partitioner decides. It must compute the unsharded layer (1e-6: float32, the
    same sums in another order) and leave the weights where they lie: the
    collectives it may add are on group sizes (int32) and on the outputs, never
    an all-gather of a float array."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = make_mesh({"data": 2, "expert": 4})
    x = jnp.asarray(np.random.default_rng(2).normal(size=(4, 8, 16)), dtype=jnp.float32)
    plain = MoEMlp(num_experts=8, hidden_size=16, k=2)
    sharded = MoEMlp(num_experts=8, hidden_size=16, k=2, mesh=mesh)
    params = plain.init(jax.random.PRNGKey(0), x)
    want = plain.apply(params, x, dropless=True)
    placed = jax.device_put(params, jax.tree.map(
        lambda p: NamedSharding(mesh, PartitionSpec("expert") if p.ndim == 3 else PartitionSpec()), params
    ))
    fn = jax.jit(lambda p, x: sharded.apply(p, x, dropless=True))
    np.testing.assert_allclose(fn(placed, x), want, atol=1e-6)
    gathers = [line for line in fn.lower(placed, x).compile().as_text().splitlines() if " all-gather(" in line]
    assert not [line for line in gathers if "= f32[" in line or "= bf16[" in line], gathers
