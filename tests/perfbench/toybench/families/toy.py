"""The ``toy`` family: an architecture the harness has never heard of, brought
by the files of this directory alone (``tests/perfbench/tiny.py`` lists them in
a benchmark of toy cells). Its configurations are written with another model's
key names. It builds the program's decoder, the only one the program has, and
no file of the harness knows that.

Its counts are the decoder's own, each times :data:`COUNT_MARK`, so that a test
can tell whose count a reader took.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key

COUNT_MARK = 3.0


def model(config: Dict[str, Any]):
    from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel

    if config["intermediate_size"] != 4 * config["hidden_size"]:
        raise ValueError("the program's decoder has an MLP of four times the hidden size and no other")
    return GPTLMHeadModel(GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"], num_heads=config["num_attention_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        layer_norm_eps=config["layer_norm_eps"], dropout=config["hidden_dropout_prob"],
        dtype=jnp.dtype(config["perfbench"]["compute_dtype"]), **config["perfbench"].get("model_options", {}),
    ))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    return dict(heads=config["num_attention_heads"], epsilon=config["layer_norm_eps"])


def architecture_leaves(tree: Any) -> Any:
    """The fused ``qkv`` leaves as their query, key and value thirds."""
    def split(path, leaf):
        if any(getattr(key, "key", None) == "qkv" for key in path):
            return dict(zip("qkv", jnp.split(leaf, 3, axis=-1)))
        return leaf

    return jax.tree_util.tree_map_with_path(split, tree)


@functools.lru_cache(maxsize=None)
def _maker(config_json: str, dtype_name: str):
    config = json.loads(config_json)
    init = config["perfbench"]["init"]
    width = config["hidden_size"]
    shapes = jax.eval_shape(
        lambda: model(config).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for i, (path, spec) in enumerate(paths):
            module, name = path[-2].key, path[-1].key
            noise = jax.random.normal(jax.random.fold_in(key, i), spec.shape, jnp.float32)
            if name == "scale":
                leaf = 1.0 + 0.1 * noise
            elif name == "kernel":
                leaf = init["gain"] / math.sqrt(spec.shape[0]) * noise
                if module == "qkv":  # sharper attention, as the real serving cell's recipe has it
                    leaf = leaf * jnp.where(jnp.arange(3 * width) < 2 * width, init["qk_gain"], 1.0)
            else:  # biases and embeddings
                leaf = 0.02 * noise
            leaves.append(leaf.astype(jnp.dtype(dtype_name)))
        return treedef.unflatten(leaves)

    return jax.jit(make)


def make_params(config: Dict[str, Any], seed: int, dtype: str) -> Dict[str, Any]:
    return _maker(json.dumps(config, sort_keys=True), dtype)(seed_key(seed))


def _matmul_params(config: Dict[str, Any]) -> int:
    d, inner = config["hidden_size"], config["intermediate_size"]
    return config["num_hidden_layers"] * (4 * d * d + 2 * d * inner) + config["vocab_size"] * d


def _attention_flops(config: Dict[str, Any], keys: float) -> float:
    return config["num_hidden_layers"] * 4.0 * keys * config["hidden_size"]


def decode_flops(config: Dict[str, Any], live_lengths: Iterable[float]) -> float:
    dense = 2.0 * _matmul_params(config)
    return COUNT_MARK * sum(dense + _attention_flops(config, keys) for keys in live_lengths)


def decode_attention_bytes(
    config: Dict[str, Any], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    d = config["hidden_size"]
    rows = sum(keys * 2.0 * d * kv_bytes + 2.0 * d * act_bytes for keys in live_lengths)
    return COUNT_MARK * config["num_hidden_layers"] * rows


def train_flops_per_token(config: Dict[str, Any], mean_keys: float) -> float:
    return COUNT_MARK * (6.0 * _matmul_params(config) + 3.0 * _attention_flops(config, mean_keys))
