"""Weight-only int8 quantization for inference.

The reference has no quantization story (it serves whatever object the user
trained, `unionml/model.py:1432-1519`); on TPU it is a first-class serving
lever: single-token decode is HBM-bandwidth-bound, and storing weights as int8
halves the bytes each step streams from HBM vs bfloat16. The scheme here is
the standard weight-only recipe:

- **per-output-channel symmetric int8**: each kernel column c stores
  ``round(w[:, c] / scale[c])`` with ``scale[c] = max(|w[:, c]|) / 127``;
- activations stay in the compute dtype — dequantization is one multiply that
  XLA fuses into the consuming matmul, so quality loss is bounded by weight
  rounding only (no activation calibration needed);
- quantized leaves live in the params pytree as :class:`QuantizedArray` nodes
  (a registered pytree), so jit/device_put/checkpoint machinery treats them
  like any other params — they cross host->device as int8 and dequantize
  on-device inside the compiled step.

``quantize_tree`` / ``dequantize_tree`` transform whole pytrees; the decode
engine exposes it as ``DecodeEngine(..., quantize="int8")``.
"""

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "KV_INT8_GREEDY_DIVERGENCE_BUDGET",
    "KV_INT8_LOGPROB_DELTA_BUDGET",
    "QuantizedArray",
    "default_should_quantize",
    "dequantize_blockwise",
    "dequantize_tree",
    "quantize_array",
    "quantize_blockwise",
    "quantize_tree",
    "quantized_bytes",
]

# Pinned quality budgets for the int8 KV block pool, enforced by the unit tests
# (tests/unit/test_paged_kv.py: test_int8_pool_logprob_delta_budget,
# test_int8_pool_divergence_budget_mixed_schedule). Measured on the tiny CPU
# config with ~3x headroom over observed worst cases; budgets are on the
# pre-divergence prefix (once greedy streams split, the contexts differ and
# per-token comparison stops being meaningful).
KV_INT8_LOGPROB_DELTA_BUDGET = 0.15  # max |Δ logprob| of the bf16-greedy token
KV_INT8_GREEDY_DIVERGENCE_BUDGET = 0.35  # max fraction of tokens past first split


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)  # array fields make generated __eq__ raise on bool()
class QuantizedArray:
    """int8 values + per-channel f32 scales standing in for a float array."""

    q: jax.Array  # int8, same shape as the original
    scale: jax.Array  # f32, original shape with the channel axis kept at size 1
    dtype: Any  # dequantization target dtype (the original compute dtype)

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(self.dtype)

    def tree_flatten(self):
        return (self.q, self.scale), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        q, scale = children
        return cls(q=q, scale=scale, dtype=dtype)


def quantize_blockwise(x: jax.Array, reduce_axes: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 with per-block absmax scales.

    A "block" is one element of the axes NOT in ``reduce_axes``: the absmax
    reduction runs over ``reduce_axes`` (keepdims), ``scale = absmax / 127``,
    and ``q = clip(round(x / scale), -127, 127)``. An all-zero block stores
    ``scale == 0`` — the convention the KV pool relies on so an empty block
    cannot poison the monotone-scale max on its first real write; division is
    guarded internally, and ``dequantize_blockwise`` maps ``q * 0 == 0`` back
    exactly. Round-trip error is bounded by ``scale / 2`` per element.
    """
    x32 = jnp.asarray(x, dtype=jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=reduce_axes, keepdims=True)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(x32 / jnp.where(scale > 0, scale, 1.0)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_blockwise(q: jax.Array, scale: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_blockwise` (up to rounding): ``q * scale``
    in f32, cast to ``dtype``. Inside jit the multiply fuses into the consumer,
    so int8 is what crosses HBM."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_array(w: jax.Array, channel_axis: int = -1) -> QuantizedArray:
    """Symmetric per-channel int8 quantization.

    ``channel_axis`` is the axis whose entries KEEP individual scales (the
    output axis of an (in, out) Dense kernel); the absmax reduction runs over
    every other axis, so ``scale[..., c, ...] = max(|w[..., c, ...]|) / 127``
    and an outlier in one output channel cannot crush the resolution of its
    neighbors."""
    w32 = jnp.asarray(w, dtype=jnp.float32)
    reduce_axes = tuple(i for i in range(w32.ndim) if i != channel_axis % w32.ndim)
    q, scale = quantize_blockwise(w32, reduce_axes)
    # weight trees keep the historical scale==1.0 convention for all-zero
    # channels (dequantize is identical either way; 1.0 keeps scales invertible)
    scale = jnp.where(scale > 0, scale, 1.0)
    return QuantizedArray(q=q, scale=scale, dtype=jnp.asarray(w).dtype)


def default_should_quantize(path: Tuple[str, ...], leaf: Any) -> bool:
    """Quantize 2-D matmul kernels of meaningful size; leave embeddings, norms,
    biases, and tiny projections in full precision.

    Embedding tables are excluded by name (``wte``/``wpe``/``embedding``):
    token embeddings double as the LM head, where per-channel rounding costs
    logit precision directly.
    """
    if not hasattr(leaf, "ndim") or leaf.ndim != 2:
        return False
    if min(leaf.shape) < 64:
        return False
    lowered = "/".join(str(p) for p in path).lower()
    return not any(name in lowered for name in ("wte", "wpe", "embed"))


def quantize_tree(
    params: Any, should_quantize: Optional[Callable[[Tuple[str, ...], Any], bool]] = None
) -> Any:
    """Replace selected leaves with :class:`QuantizedArray` nodes.

    :param should_quantize: ``(path, leaf) -> bool``; defaults to
        :func:`default_should_quantize`.
    """
    pred = should_quantize or default_should_quantize

    def visit(path, leaf):
        keys = tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path)
        return quantize_array(leaf) if pred(keys, leaf) else leaf

    return jax.tree_util.tree_map_with_path(visit, params)


def dequantize_tree(params: Any) -> Any:
    """Materialize full-precision leaves (inside jit: the multiplies fuse into
    the consuming matmuls, so int8 is what crosses HBM)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.dequantize() if isinstance(leaf, QuantizedArray) else leaf,
        params,
        is_leaf=lambda leaf: isinstance(leaf, QuantizedArray),
    )


def quantized_bytes(params: Any) -> Tuple[int, int]:
    """(bytes_as_stored, bytes_if_full_precision) across the tree — the HBM
    saving the quantization buys."""
    stored = full = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda l: isinstance(l, QuantizedArray)
    ):
        if isinstance(leaf, QuantizedArray):
            stored += leaf.q.size * 1 + leaf.scale.size * 4
            full += leaf.q.size * jnp.dtype(leaf.dtype).itemsize
        elif hasattr(leaf, "size"):
            nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
            stored += nbytes
            full += nbytes
    return stored, full
