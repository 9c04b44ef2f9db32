"""The ``phi4flash`` family: what the harness asks about Phi-4-mini-flash
(SambaY: Mamba-1, window, full and cross differential attention, gated memory
units), in one place.

The program's model for a configuration file's published sizes and its
``assumed`` ones, its weights from the seed, what the reference takes beside
weights and ids, and the operations and bytes a decode step needs. The drivers
and the readers reach it through ``manifest.Cell.family`` and hold none of
these names.

The program's model module is imported as this file is: a checkout that lacks
it (the parent of the PR that brought the family) fails here, before any
weight is made.

Weights: the tree is laid out as the program's ``Phi4FlashLMHeadModel`` takes
it and as ``reference/phi4flash.py`` reads it. How they are drawn is the
configuration's ``perfbench.init`` over :data:`PHI4FLASH_INIT`. Every learned
constant is drawn, none left at its trivial value: norm scales and ``D`` are
not 1, biases not 0, the lambdas not 0, and the step bias spreads the layers'
memory over two decades of lengths.

Counts: what the algorithm requires, not what an implementation does.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key
from unionml_tpu.models import phi4flash


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The published sizes and the ``assumed`` ones, under one set of names."""
    ssm = config["assumed"]["mamba"]
    hidden = config["hidden_size"]
    return dict(
        hidden=hidden, layers=config["num_hidden_layers"], heads=config["num_attention_heads"],
        key_heads=config["num_key_value_heads"], head_dim=hidden // config["num_attention_heads"],
        inner=config["intermediate_size"], window=config["sliding_window"], vocab=config["vocab_size"],
        d_state=ssm["d_state"], d_conv=ssm["d_conv"], d_inner=ssm["expand"] * hidden,
        dt_rank=ssm.get("dt_rank") or -(-hidden // 16),
    )


def program_config(config: Dict[str, Any]) -> phi4flash.Phi4FlashConfig:
    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"]:
        raise ValueError("the program's feed-forward is SwiGLU and its head the tied embedding")
    if config["mlp_bias"] or config["lm_head_bias"] or config["mb_per_layer"] != 2:
        raise ValueError("no MLP or head bias, and a Mamba layer every second layer, and no other layout")
    deployment = config.get("perfbench", {})
    s, ssm = sizes(config), config["assumed"]["mamba"]
    return phi4flash.Phi4FlashConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"], num_layers=s["layers"], num_heads=s["heads"],
        num_kv_heads=s["key_heads"], intermediate_size=s["inner"], sliding_window=s["window"],
        d_state=s["d_state"], d_conv=s["d_conv"], expand=ssm["expand"], dt_rank=ssm.get("dt_rank"),
        layer_norm_eps=config["layer_norm_eps"], max_position_embeddings=config["max_position_embeddings"],
        dtype=jnp.dtype(deployment.get("compute_dtype", "bfloat16")), **deployment.get("model_options", {}),
    )


def model(config: Dict[str, Any]):
    """The program's ``Phi4FlashLMHeadModel`` for the sizes in ``config``."""
    return phi4flash.Phi4FlashLMHeadModel(program_config(config))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/phi4flash.py``'s functions take beside weights and ids."""
    s = sizes(config)
    settings = config.get("perfbench", {}).get("reference_options", {})
    return dict(
        layers=s["layers"], heads=s["heads"], key_heads=s["key_heads"], head_dim=s["head_dim"],
        window=s["window"], eps=config["layer_norm_eps"],
        query_block=int(settings.get("query_block", 512)), vocab_block=int(settings.get("vocab_block", 66688)),
    )


# ------------------------------------------------------------------- weights


#: kernels N(0, gain / fan_in), and the projections that write to the residual
#: stream (a mixer's last product, every SwiGLU's ``down``) N(0, residual_gain /
#: fan_in), as in the xing4 recipe: a sublayer's update is a fraction of the
#: stream it is added to. Embedding N(0, embed_std), SMALL: the head is the
#: embedding, so a token's own row in the residual stream scores |e|^2 against
#: itself, and at N(0, 1) a random model repeats its last token with a margin of
#: hundreds of logits that no precision moves (my first chip run, PR 35: 0.0 from
#: the program and from both controls); at 0.05 the layers' updates are most of
#: the stream, logits have a spread of 2.5 at the published width and fifty
#: greedy tokens are fifty different ones. Norm scales, the sub-norm's
#: and ``D`` 1 + N(0, scale_std); biases N(0, bias_std); lambdas N(0,
#: lambda_std); the convolution's taps N(0, 1 / taps); ``A_log = log(1 ..
#: d_state)`` (Mamba's own); the step bias the inverse softplus of a log-uniform
#: step in ``[dt_min, dt_max]`` (Mamba's own: a channel's memory is 1 / (step x
#: n) tokens, ten to a thousand and more)
PHI4FLASH_INIT: Dict[str, Any] = {
    "gain": 1.0, "residual_gain": 0.25, "embed_std": 0.05, "scale_std": 0.1, "bias_std": 0.1,
    "lambda_std": 0.1, "dt_min": 1e-3, "dt_max": 1e-1,
}


def shapes(s: Dict[str, int]) -> Dict[str, Any]:
    """``{path: (shape, kind)}`` of the parameter tree for :func:`sizes` ``s``:
    the top leaves and one layer of each kind under ``mamba``, ``window``,
    ``full``, ``gmu``, ``cross``."""
    d, di, n, rank, taps = s["hidden"], s["d_inner"], s["d_state"], s["dt_rank"], s["d_conv"]
    width, dim = s["heads"] * s["head_dim"], s["head_dim"]
    qkv = (s["heads"] + 2 * s["key_heads"]) * dim

    def norm():
        return {"scale": ((d,), "scale"), "bias": ((d,), "bias")}

    def out():
        return {
            **{name: ((dim,), "lambda") for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
            "subln": ((2 * dim,), "scale"), "o": ((width, d), "residual"), "o_bias": ((d,), "bias"),
        }

    mixers = {
        "mamba": {
            "in_proj": ((d, 2 * di), "kernel"), "conv_w": ((taps, di), "taps"), "conv_b": ((di,), "bias"),
            "x_proj": ((di, rank + 2 * n), "kernel"), "dt_proj": ((rank, di), "kernel"),
            "dt_bias": ((di,), "dt_bias"), "A_log": ((di, n), "a_log"), "D": ((di,), "scale"),
            "out_proj": ((di, d), "residual"),
        },
        "attention": {"qkv": ((d, qkv), "kernel"), "qkv_bias": ((qkv,), "bias"), "out": out()},
        "gmu": {"in_proj": ((d, di), "kernel"), "out_proj": ((di, d), "residual")},
        "cross": {"q": ((d, width), "kernel"), "q_bias": ((width,), "bias"), "out": out()},
    }
    mixers["window"] = mixers["full"] = mixers.pop("attention")
    block = {
        "norm": norm(), "mlp_norm": norm(),
        "mlp": {"up": ((d, 2 * s["inner"]), "kernel"), "down": ((s["inner"], d), "residual")},
    }
    tree = {kind: {**block, "mixer": mixer} for kind, mixer in mixers.items()}
    tree["embed"] = {"embedding": ((s["vocab"], d), "embed")}
    tree["final_norm"] = norm()
    return tree


_IS_SPEC = lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


@functools.lru_cache(maxsize=None)
def _maker(kind: str, size_items: tuple, init_items: tuple, dtype_name: str):
    """The jitted draw of one part of the tree (``kind``: a layer's kind, or
    ``top``): five layer programs and one for the top, each called with a key
    of its own, so that one layer's float32 noise is alive at a time."""
    init = {**PHI4FLASH_INIT, **dict(init_items)}
    dtype = jnp.dtype(dtype_name)

    def draw(key, shape, leaf_kind):
        if leaf_kind == "a_log":
            return jnp.log(jnp.broadcast_to(jnp.arange(1.0, shape[1] + 1), shape)).astype(jnp.float32)
        if leaf_kind == "dt_bias":
            low, high = math.log(init["dt_min"]), math.log(init["dt_max"])
            step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
            return step + jnp.log(-jnp.expm1(-step))  # softplus's inverse
        noise = jax.random.normal(key, shape, jnp.float32)
        if leaf_kind == "scale":
            return 1.0 + init["scale_std"] * noise
        if leaf_kind == "bias":
            return init["bias_std"] * noise
        if leaf_kind == "lambda":
            return init["lambda_std"] * noise
        if leaf_kind == "taps":
            return noise * shape[0] ** -0.5
        if leaf_kind == "embed":
            return (init["embed_std"] * noise).astype(dtype)
        gain = init["residual_gain"] if leaf_kind == "residual" else init["gain"]
        return (noise * (gain / shape[-2]) ** 0.5).astype(dtype)  # kernels: (fan_in, fan_out)

    def make(key):
        spec = shapes(dict(size_items))
        spec = {"embed": spec["embed"], "final_norm": spec["final_norm"]} if kind == "top" else spec[kind]
        leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_IS_SPEC)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [draw(k, *leaf) for k, leaf in zip(keys, leaves)])

    return jax.jit(make)


def make_params(config: Dict[str, Any], seed: int, dtype: str) -> Dict[str, Any]:
    """The parameter tree (without the ``{"params": ...}`` wrapper) for a
    configuration file's sizes and its ``perfbench.init`` recipe. Kernels and the
    embedding in ``dtype``; norms, biases, lambdas, the convolution and the
    state-space constants float32."""
    items = tuple(sorted(sizes(config).items()))
    init = tuple(sorted(config.get("perfbench", {}).get("init", {}).items()))
    key = seed_key(seed)
    tree = dict(_maker("top", items, init, dtype)(jax.random.fold_in(key, 0)))
    layers = config["num_hidden_layers"]
    for i in range(layers):
        kind = phi4flash.layer_kind(i, layers)
        tree[f"layer_{i}"] = _maker(kind, items, init, dtype)(jax.random.fold_in(key, 1000 + i))
    return tree


# -------------------------------------------------------------------- counts


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind."""
    layers = config["num_hidden_layers"]
    kinds = [phi4flash.layer_kind(i, layers) for i in range(layers)]
    return {kind: kinds.count(kind) for kind in ("mamba", "window", "full", "gmu", "cross")}


def layer_matmul_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Weights one token's row meets in a matrix product, by kind of mixer, and a layer's SwiGLU."""
    s = sizes(config)
    d, di, width = s["hidden"], s["d_inner"], s["heads"] * s["head_dim"]
    attention = d * (s["heads"] + 2 * s["key_heads"]) * s["head_dim"] + width * d
    return {
        "mamba": d * 2 * di + di * (s["dt_rank"] + 2 * s["d_state"]) + s["dt_rank"] * di + di * d,
        "window": attention, "full": attention, "gmu": 2 * d * di, "cross": 2 * d * width,
        "mlp": 3 * d * s["inner"],
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights that take part in a matrix product for every decoded token: every
    layer's mixer and SwiGLU, and the tied head. The embedding's look-up is none."""
    parts, counts = layer_matmul_params(config), layer_counts(config)
    s = sizes(config)
    return sum(counts[kind] * parts[kind] for kind in counts) + s["layers"] * parts["mlp"] + s["vocab"] * s["hidden"]


def attention_flops(config: Dict[str, Any], keys: float) -> float:
    """Forward FLOPs of one query token's attention over a row of ``keys``
    keys, all sixteen attention layers: each query head scores one head size
    and weighs its group's two, 2 FLOPs a multiply-add; the window layers over
    the last ``window`` keys at most."""
    s, counts = sizes(config), layer_counts(config)
    per_key = 2.0 * s["heads"] * 3 * s["head_dim"]
    return per_key * ((counts["full"] + counts["cross"]) * keys + counts["window"] * min(keys, s["window"]))


def decode_flops(config: Dict[str, Any], live_lengths: Iterable[float]) -> float:
    """Forward FLOPs of decode steps that advance one row per entry of
    ``live_lengths`` (the keys that row attends over, its new token included):
    the products, the attention and the recurrence's 7 FLOPs a state element."""
    s, counts = sizes(config), layer_counts(config)
    dense = 2.0 * matmul_params(config) + counts["mamba"] * 7.0 * s["d_inner"] * s["d_state"]
    return sum(dense + attention_flops(config, keys) for keys in live_lengths)


def _row_bytes(config: Dict[str, Any], kv_bytes: float) -> float:
    """A token's keys and values of one attention layer."""
    s = sizes(config)
    return 2.0 * s["key_heads"] * s["head_dim"] * kv_bytes


def _query_bytes(config: Dict[str, Any], act_bytes: float) -> float:
    """One attention layer's queries in and its pairs' two outputs out, a row."""
    s = sizes(config)
    return s["heads"] * 3.0 * s["head_dim"] * act_bytes


def decode_window_bytes(
    config: Dict[str, Any], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    """Bytes the window layers' decode attention has to move for those rows:
    the last ``window`` keys and values of each, or all it has, the query in
    and the output out."""
    s, counts = sizes(config), layer_counts(config)
    row, query = _row_bytes(config, kv_bytes), _query_bytes(config, act_bytes)
    return counts["window"] * sum(min(keys, s["window"]) * row + query for keys in live_lengths)


def decode_attention_bytes(
    config: Dict[str, Any], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    """Bytes decode attention has to move for those rows, all sixteen attention
    layers: the full cache once for the layer that owns it and once for each
    cross layer that reads it, and the window layers' share."""
    counts = layer_counts(config)
    lengths = list(live_lengths)
    row, query = _row_bytes(config, kv_bytes), _query_bytes(config, act_bytes)
    over_all = (counts["full"] + counts["cross"]) * sum(keys * row + query for keys in lengths)
    return over_all + decode_window_bytes(config, lengths, kv_bytes, act_bytes)


def decode_state_bytes(config: Dict[str, Any], rows: float, state_bytes: float = 4.0) -> float:
    """Bytes the recurrence of a decode step has to move for ``rows`` rows, all
    Mamba layers: each row's state in and out, its step, input, ``B`` and ``C``
    in and its output out."""
    s, counts = sizes(config), layer_counts(config)
    per_row = 2.0 * s["d_inner"] * s["d_state"] * state_bytes + (3.0 * s["d_inner"] + 2.0 * s["d_state"]) * 4.0
    return counts["mamba"] * rows * per_row


def prefill_scan_bytes(config: Dict[str, Any], prompt_lengths: Iterable[float], act_bytes: float) -> float:
    """Bytes the recurrence of prefills of ``prompt_lengths`` tokens has to
    move, all Mamba layers: each token's step (float32) and input in, its
    ``B`` and ``C``, its output (float32) out, and a prompt's state in and out."""
    s, counts = sizes(config), layer_counts(config)
    per_token = s["d_inner"] * (4.0 + act_bytes + 4.0) + 2.0 * s["d_state"] * act_bytes
    per_prompt = 2.0 * s["d_inner"] * s["d_state"] * 4.0
    return counts["mamba"] * sum(tokens * per_token + per_prompt for tokens in prompt_lengths)


def resident_bytes(config: Dict[str, Any], length: float, kv_bytes: float, state_bytes: float = 4.0) -> float:
    """Bytes a sequence of ``length`` tokens has to keep to go on: the Mamba
    layers' state and convolution tails, the window layers' last ``window``
    keys and values, the full layer's all."""
    s, counts = sizes(config), layer_counts(config)
    state = s["d_inner"] * s["d_state"] * state_bytes + (s["d_conv"] - 1) * s["d_inner"] * kv_bytes
    row = _row_bytes(config, kv_bytes)
    return counts["mamba"] * state + counts["window"] * min(length, s["window"]) * row + counts["full"] * length * row
