"""The ``gpt2`` family: what the harness asks about GPT-2, in one place.

The program's model for a configuration file's published sizes, its weights
from the seed, what the reference takes beside weights and ids, the operations
and bytes a step needs, and the architecture's leaves. The drivers and the
readers reach it through ``manifest.Cell.family`` and hold none of these names.

Weights: the tree is laid out as the program's ``GPTLMHeadModel`` takes it (and
as the reference reads it): the benchmark makes the weights, the program and
the reference are both handed them. How they are drawn is the configuration's
``perfbench.init`` (see :data:`GPT2_INIT` for the keys and GPT-2's own recipe).
Biases and LayerNorm parameters are drawn too (a trained model's are not zero
and one), so that a path which dropped a bias or a scale could not pass the
comparison.

Counts: what the algorithm requires, not what an implementation does: a kernel
that walks its whole table, recomputes, or pads still gets only the live work
credited, so its share of the roofline falls.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key


def model(config: Dict[str, Any]):
    """The program's ``GPTLMHeadModel`` for the published sizes in ``config``."""
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel

    deployment = config["perfbench"]
    return GPTLMHeadModel(GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"], dropout=config["resid_pdrop"],
        dtype=jnp.dtype(deployment["compute_dtype"]), **deployment.get("model_options", {}),
    ))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/gpt2.py``'s functions take beside weights and ids."""
    return dict(num_heads=config["n_head"], eps=config["layer_norm_epsilon"])


def architecture_leaves(tree: Any) -> Any:
    """The tree with each fused ``qkv`` leaf as its query, key and value thirds:
    the architecture's leaves. The key's bias, whose gradient is nought under
    softmax, must be a leaf of its own for the rule that leaves it out."""
    def split(path, leaf):
        if any(getattr(key, "key", None) == "qkv" for key in path):
            q, k, v = jnp.split(leaf, 3, axis=-1)
            return {"q": q, "k": k, "v": v}
        return leaf

    return jax.tree_util.tree_map_with_path(split, tree)


# ------------------------------------------------------------------- weights


#: GPT-2's initialisation: N(0, 0.02) kernels and embeddings, the two residual
#: projections scaled by ``1/sqrt(2 * layers)``. ``kernel_std``/``residual_std``
#: of ``null`` mean ``gain / sqrt(fan_in)``; ``qk_gain`` multiplies the query
#: and key columns of the fused ``qkv`` kernel (sharper attention).
GPT2_INIT: Dict[str, Any] = {
    "embed_std": 0.02, "kernel_std": 0.02, "residual_std": "gpt2", "gain": 1.0, "qk_gain": 1.0,
    "bias_std": 0.02, "scale_std": 0.1,
}


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


# -------------------------------------------------------------------- counts


def matmul_params(sizes: Dict[str, int], tied_head: bool = True) -> int:
    """Weights that take part in a matrix multiplication for every token: the
    blocks' four dense layers and the head (tied to the token embedding).
    Embedding look-ups, biases and LayerNorms are not multiplications."""
    d = sizes["n_embd"]
    inner = sizes.get("n_inner") or 4 * d
    per_layer = d * 3 * d + d * d + d * inner + inner * d
    head = sizes["vocab_size"] * d if tied_head else 0
    return sizes["n_layer"] * per_layer + head


def attention_flops(sizes: Dict[str, int], keys: float) -> float:
    """Forward FLOPs of one query token's attention over ``keys`` keys, all
    layers: QK^T and PV, 2 FLOPs a multiply-add, over the full hidden width."""
    return sizes["n_layer"] * 4.0 * keys * sizes["n_embd"]


def decode_flops(sizes: Dict[str, int], live_lengths: Iterable[float]) -> float:
    """Forward FLOPs of decode steps that advance one row per entry of
    ``live_lengths`` (the keys that row attends over, its new token included)."""
    dense = 2.0 * matmul_params(sizes)
    return sum(dense + attention_flops(sizes, keys) for keys in live_lengths)


def decode_attention_bytes(
    sizes: Dict[str, int], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    """Bytes decode attention has to move for those rows, all layers: each
    row's live K and V once, its query in and its output out."""
    d = sizes["n_embd"]
    per_key = 2.0 * d * kv_bytes
    per_row = 2.0 * d * act_bytes
    return sizes["n_layer"] * sum(keys * per_key + per_row for keys in live_lengths)


def train_flops_per_token(sizes: Dict[str, int], mean_keys: float) -> float:
    """Forward and backward FLOPs a trained token needs: 6 per matmul weight
    (2 forward, 4 backward) and three times the forward attention over the
    ``mean_keys`` keys a token sees on average under the causal, per-document
    mask. Recomputation is not counted."""
    return 6.0 * matmul_params(sizes) + 3.0 * attention_flops(sizes, mean_keys)
