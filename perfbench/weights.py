"""GPT-2 weights from the seed, on the device, in one jitted call.

The tree is laid out as the program's ``GPTLMHeadModel`` takes it (and as the
reference reads it): the benchmark makes the weights, the program and the
reference are both handed them. How they are drawn is the configuration's
``perfbench.init`` (see :data:`GPT2_INIT` for the keys and GPT-2's own recipe).
Biases and LayerNorm parameters are drawn too (a trained model's are not zero
and one), so that a path which dropped a bias or a scale could not pass the
comparison.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


#: GPT-2's initialisation: N(0, 0.02) kernels and embeddings, the two residual
#: projections scaled by ``1/sqrt(2 * layers)``. ``kernel_std``/``residual_std``
#: of ``null`` mean ``gain / sqrt(fan_in)``; ``qk_gain`` multiplies the query
#: and key columns of the fused ``qkv`` kernel (sharper attention).
GPT2_INIT: Dict[str, Any] = {
    "embed_std": 0.02, "kernel_std": 0.02, "residual_std": "gpt2", "gain": 1.0, "qk_gain": 1.0,
    "bias_std": 0.02, "scale_std": 0.1,
}


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative whole-number seed (over 2**31 too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


#: one block's leaves: ``(path, shape as a function of (d, inner), kind)``
_BLOCK = (
    (("attn_norm", "scale"), lambda d, inner: (d,), "scale"),
    (("attn_norm", "bias"), lambda d, inner: (d,), "bias"),
    (("qkv", "kernel"), lambda d, inner: (d, 3 * d), "qkv"),
    (("qkv", "bias"), lambda d, inner: (3 * d,), "bias"),
    (("attn_out", "kernel"), lambda d, inner: (d, d), "residual"),
    (("attn_out", "bias"), lambda d, inner: (d,), "bias"),
    (("mlp_norm", "scale"), lambda d, inner: (d,), "scale"),
    (("mlp_norm", "bias"), lambda d, inner: (d,), "bias"),
    (("mlp_up", "kernel"), lambda d, inner: (d, inner), "kernel"),
    (("mlp_up", "bias"), lambda d, inner: (inner,), "bias"),
    (("mlp_down", "kernel"), lambda d, inner: (inner, d), "residual"),
    (("mlp_down", "bias"), lambda d, inner: (d,), "bias"),
)


def shapes(sizes: Dict[str, int]) -> Dict[str, Any]:
    """``{path: (shape, kind)}`` for a GPT-2 of the given published sizes."""
    d, v, p = sizes["n_embd"], sizes["vocab_size"], sizes["n_positions"]
    inner = sizes.get("n_inner") or 4 * d
    tree: Dict[str, Any] = {
        "wte": {"embedding": ((v, d), "embed")},
        "wpe": {"embedding": ((p, d), "embed")},
        "final_norm": {"scale": ((d,), "scale"), "bias": ((d,), "bias")},
    }
    for i in range(sizes["n_layer"]):
        block: Dict[str, Any] = {}
        for (module, leaf), shape, kind in _BLOCK:
            block.setdefault(module, {})[leaf] = (shape(d, inner), kind)
        tree[f"layer_{i}"] = block
    return tree


@functools.lru_cache(maxsize=None)
def _maker(sizes_items: tuple, init_items: tuple, dtype_name: str):
    sizes = dict(sizes_items)
    init = {**GPT2_INIT, **dict(init_items)}
    dtype = jnp.dtype(dtype_name)
    d, layers = sizes["n_embd"], sizes["n_layer"]
    inner = sizes.get("n_inner") or 4 * d

    def kernel_std(kind: str, fan_in: int) -> float:
        std = init["residual_std"] if kind == "residual" else init["kernel_std"]
        if std == "gpt2":
            return 0.02 / math.sqrt(2 * layers)
        return float(std) if std is not None else init["gain"] / math.sqrt(fan_in)

    def draw(key, shape, kind):
        noise = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            leaf = 1.0 + init["scale_std"] * noise
        elif kind == "bias":
            leaf = init["bias_std"] * noise
        elif kind == "embed":
            leaf = init["embed_std"] * noise
        else:  # kernel, residual, qkv: (..., fan_in, fan_out)
            leaf = kernel_std(kind, shape[-2]) * noise
            if kind == "qkv":  # the query and key columns of the fused kernel
                leaf = leaf * jnp.where(jnp.arange(3 * d) < 2 * d, init["qk_gain"], 1.0)
        return leaf.astype(dtype)

    def make(key):
        """One draw per kind of leaf, all layers of it at once: 17 random
        programs to compile instead of one per leaf."""
        top = shapes({**sizes, "n_layer": 0})
        tree = {
            name: {leaf: draw(jax.random.fold_in(key, 100 * i + j), *spec)
                   for j, (leaf, spec) in enumerate(sorted(group.items()))}
            for i, (name, group) in enumerate(sorted(top.items()))
        }
        for j, ((module, leaf), shape, kind) in enumerate(_BLOCK):
            stacked = draw(jax.random.fold_in(key, 10_000 + j), (layers, *shape(d, inner)), kind)
            for i in range(layers):
                tree.setdefault(f"layer_{i}", {}).setdefault(module, {})[leaf] = stacked[i]
        return tree

    return jax.jit(make)


def make_params(config: Dict[str, Any], seed: int, dtype: str) -> Dict[str, Any]:
    """The parameter tree (without the ``{"params": ...}`` wrapper) for a
    configuration file's sizes and its ``perfbench.init`` recipe."""
    keys = ("n_embd", "n_layer", "n_positions", "vocab_size", "n_inner")
    sizes = tuple((k, config.get(k)) for k in keys)
    init = tuple(sorted(config.get("perfbench", {}).get("init", {}).items()))
    return _maker(sizes, init, dtype)(seed_key(seed))
