"""Attention kernel + loss op tests (pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.ops.attention import attention, flash_attention, xla_attention
from unionml_tpu.ops.losses import accuracy, cross_entropy_with_integer_labels


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 4, 256, 128)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype=jnp.float32) for _ in range(3))


def test_flash_matches_xla_no_mask(qkv):
    q, k, v = qkv
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, interpret=True)),
        np.asarray(xla_attention(q, k, v)),
        atol=1e-5,
    )


def test_flash_matches_xla_padding_mask(qkv):
    q, k, v = qkv
    kv_lens = jnp.asarray([130, 256], dtype=jnp.int32)
    mask = (jnp.arange(256)[None, :] < kv_lens[:, None])[:, None, None, :]
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, kv_lens=kv_lens, interpret=True)),
        np.asarray(xla_attention(q, k, v, mask=mask)),
        atol=1e-5,
    )


def test_flash_matches_xla_causal(qkv):
    q, k, v = qkv
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, interpret=True)),
        np.asarray(xla_attention(q, k, v, causal=True)),
        atol=1e-5,
    )


def test_flash_gradients_match(qkv):
    q, k, v = qkv
    kv_lens = jnp.asarray([200, 256], dtype=jnp.int32)
    mask = (jnp.arange(256)[None, :] < kv_lens[:, None])[:, None, None, :]
    g_flash = jax.grad(lambda a, b, c: jnp.sum(flash_attention(a, b, c, kv_lens=kv_lens, interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(xla_attention(a, b, c, mask=mask) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_irregular_shapes_fall_back():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 100, 64)), dtype=jnp.float32)  # not tile-aligned
    out = flash_attention(q, q, q, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xla_attention(q, q, q)), atol=1e-5)


def test_attention_dispatcher_cpu_uses_xla(qkv):
    q, k, v = qkv
    out = attention(q, k, v, impl="auto")  # cpu backend -> xla path
    np.testing.assert_allclose(np.asarray(out), np.asarray(xla_attention(q, k, v)), atol=1e-6)
    with pytest.raises(ValueError, match="Unknown attention impl"):
        attention(q, k, v, impl="nope")


def test_cross_entropy_matches_optax():
    import optax

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(32, 10)), dtype=jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(32,)))
    ours = cross_entropy_with_integer_labels(logits, labels)
    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    np.testing.assert_allclose(float(ours), float(ref), rtol=1e-6)


def test_cross_entropy_weights_mask_padding():
    logits = jnp.asarray([[10.0, 0.0], [0.0, 10.0], [5.0, 5.0]])
    labels = jnp.asarray([0, 1, 0])
    weights = jnp.asarray([1.0, 1.0, 0.0])
    masked = cross_entropy_with_integer_labels(logits, labels, weights)
    unmasked = cross_entropy_with_integer_labels(logits[:2], labels[:2])
    np.testing.assert_allclose(float(masked), float(unmasked), rtol=1e-6)
    assert float(accuracy(logits, labels, weights)) == 1.0


@pytest.mark.parametrize(
    "kwargs",
    [{}, {"causal": True}, {"kv_lens": "pad"}, {"causal": True, "kv_lens": "pad"}],
    ids=["plain", "causal", "padded", "causal+padded"],
)
def test_pallas_backward_matches_xla(qkv, kwargs):
    """The pallas bwd kernels (dq/dkv from LSE residuals) agree with XLA autodiff."""
    q, k, v = qkv
    kv_lens = jnp.asarray([130, 256], dtype=jnp.int32) if kwargs.get("kv_lens") == "pad" else None
    causal = kwargs.get("causal", False)
    mask = None
    if kv_lens is not None:
        mask = (jnp.arange(256)[None, :] < kv_lens[:, None])[:, None, None, :]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_lens=kv_lens, causal=causal, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, mask=mask, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_forward_residuals_lse():
    """return_residuals emits per-row logsumexp matching the dense computation."""
    from unionml_tpu.ops.attention import _flash_forward

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 128, 64)), dtype=jnp.float32) for _ in range(3))
    scale = 1.0 / np.sqrt(64)
    out, lse = _flash_forward(q, k, v, None, False, scale, 128, 128, True, return_residuals=True)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=1e-5)


def test_pick_block_sizes_alignment():
    """Block defaults resolve through the tuning table and stay seq-aligned."""
    from unionml_tpu.ops.tuning import TUNED_BLOCKS, pick_block_sizes

    assert pick_block_sizes(128, 128, 64) == (128, 128)
    # v5e-measured winner (on-device scanned sweep, KERNEL_BENCH.json 2026-07-29)
    assert pick_block_sizes(512, 512, 64) == (256, 512)
    assert pick_block_sizes(96, 96, 64) == (96, 96)  # tiny seq: one block
    # irregular (non-multiple-of-8) seqs get NON-dividing blocks so the kernel's
    # alignment check routes to the XLA fallback instead of a doomed Mosaic compile
    assert pick_block_sizes(100, 100, 64) == (128, 128)
    # large multiple-of-8-but-not-128 seqs must NOT become one giant VMEM tile
    assert pick_block_sizes(1000, 1000, 64) == (128, 128)
    # unmeasured shapes still use the bounded aligned fallback
    assert pick_block_sizes(384, 384, 64) == (128, 128)
    # a measured winner overrides the fallback
    TUNED_BLOCKS[(384, 384, 64)] = (384, 128)
    try:
        assert pick_block_sizes(384, 384, 64) == (384, 128)
    finally:
        TUNED_BLOCKS.pop((384, 384, 64))


def test_flash_attention_default_blocks_resolve(qkv):
    """block_q/block_k=None must resolve via tuning and still match XLA."""
    q, k, v = qkv
    out = flash_attention(q, k, v, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pick_impl_measured_and_default():
    """auto dispatch consults measured verdicts; unmeasured shapes use the default."""
    from unionml_tpu.ops.tuning import DEFAULT_TPU_IMPL, MEASURED_IMPL, pick_impl

    assert pick_impl(128, 128, 64) == "xla"  # the committed verdict for the BERT-base shape
    for shape, impl in MEASURED_IMPL.items():
        assert pick_impl(*shape) == impl
    assert pick_impl(384, 384, 64) == DEFAULT_TPU_IMPL
