"""Each family's plain float32 reference against the model its adapter builds,
at a toy size on the CPU, and the controls against the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import gpt2 as reference
from tests.perfbench import tiny

KW = dict(num_heads=tiny.TINY_SIZES["n_head"], eps=1e-5)
gpt2 = tiny.gpt2_family()


def token_ids(vocab):
    return jnp.asarray(np.random.default_rng(0).integers(0, vocab, (2, 48)).astype(np.int32))


@pytest.fixture(scope="module")
def toy():
    config = {**tiny.TINY_SIZES, "layer_norm_epsilon": 1e-5, "resid_pdrop": 0.0,
              "perfbench": {"compute_dtype": "float32", "init": {"kernel_std": None,
                            "residual_std": None, "qk_gain": 2.0}}}
    assert gpt2.reference_kwargs(config) == KW
    return config, gpt2.make_params(config, 7, "float32"), token_ids(config["vocab_size"])


@pytest.mark.parametrize("name", ["tiny.closed", "toy.closed"])
def test_forward_agrees_with_the_programs_model(name, tmp_path):
    """What the cell's reference computes from its family's weights and
    arguments is what the model its family builds computes."""
    cell = tiny.cell(tiny.make_root(tmp_path), name)
    family, config = cell.family(), cell.config
    params, ids = family.make_params(config, 7, "float32"), token_ids(config["vocab_size"])
    with jax.default_matmul_precision("highest"):
        program = family.model(config).apply({"params": params}, ids)
    program = program[0] if isinstance(program, tuple) else program
    for row in range(ids.shape[0]):
        ours = cell.reference().logits_at(params, ids[row : row + 1], jnp.arange(ids.shape[1]),
                                          **family.reference_kwargs(config))
        np.testing.assert_allclose(np.asarray(program[row]), np.asarray(ours), atol=2e-4)


def test_packed_rows_are_their_documents_alone(toy):
    _, params, ids = toy
    # one row of two documents (lengths 20 and 28) against each document alone
    segments = jnp.asarray([[1] * 20 + [2] * 28])
    packed = reference.hidden_states(params, ids[:1], segments=segments, **KW)
    first = reference.hidden_states(params, ids[:1, :20], **KW)
    second = reference.hidden_states(params, ids[:1, 20:], **KW)
    np.testing.assert_allclose(np.asarray(packed[0, :20]), np.asarray(first[0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(packed[0, 20:]), np.asarray(second[0]), atol=1e-4)
    # the boundary's target and the padding are not counted
    padded = jnp.asarray([[1] * 20 + [2] * 20 + [0] * 8])
    _, count = reference.packed_loss_sum(params, ids[:1], padded, **KW)
    assert int(count) == 19 + 19


@pytest.mark.parametrize("lowp", ["int8", "fp8"])
def test_the_controls_compute_in_a_lower_precision(toy, lowp):
    _, params, ids = toy
    rows = jnp.arange(ids.shape[1])
    exact = reference.logits_at(params, ids[:1], rows, **KW)
    low = reference.logits_at(params, ids[:1], rows, lowp=lowp, **KW)
    error = float(jnp.std(low - exact)) / float(jnp.std(exact))
    assert 1e-3 < error < 0.5
    # and has gradients (the rounding is straight-through)
    segments = jnp.ones_like(ids[:1])
    loss, grads = reference.loss_and_grads(params, ids[:1], segments, lowp=lowp, **KW)
    assert np.isfinite(float(loss))
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads["layer_0"]["mlp_up"]))


def test_adamw_step_by_hand():
    params = {"w": jnp.asarray([1.0, -2.0])}
    zeros = {"w": jnp.zeros(2)}
    grads = {"w": jnp.asarray([3.0, 4.0])}  # norm 5, clipped to 1: (0.6, 0.8)
    new, mu, nu, clipped = reference.adamw_step(
        params, zeros, zeros, grads, jnp.asarray(1), jnp.float32(0.1),
        weight_decay=0.01, max_grad_norm=1.0,
    )
    np.testing.assert_allclose(np.asarray(clipped["w"]), [0.6, 0.8], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mu["w"]), [0.06, 0.08], rtol=1e-6)
    # bias-corrected first step: m/sqrt(v) = sign(g); plus decay 0.01 * p
    np.testing.assert_allclose(np.asarray(new["w"]), [1.0 - 0.1 * (1 + 0.01), -2.0 - 0.1 * (1 - 0.02)],
                               rtol=1e-5)
