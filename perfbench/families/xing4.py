"""The ``xing4`` family: what the harness asks about Xing4.0 (latent attention,
routed and shared experts, hyper-connection residual streams), in one place.

The program's model for a configuration file's published sizes, its weights
from the seed, what the reference takes beside weights and ids, and the
operations and bytes a decode step needs. The drivers and the readers reach it
through ``manifest.Cell.family`` and hold none of these names.

The program's model module is imported as this file is: a checkout that lacks
it (the parent of the PR that brought the family) fails here, before any
weight is made.

Depth: the layers this chip runs are the file's ``layers`` (the catalog's own
name for depth), the leading ``first_k_dense_replace`` of them dense. The
file's ``num_hidden_layers`` stays the published count of the whole model, of
which the others lie on further chips, and nothing here reads it.

Weights: the tree is laid out as the program's ``LatentMoELMHeadModel`` takes
it and as ``reference/xing4.py`` reads it. How they are drawn is the
configuration's ``perfbench.init`` over :data:`XING4_INIT`. Every learned
constant is drawn, none left at its trivial value: norm scales are not 1, the
router's selection bias is not 0 (so routing is not balanced by construction),
and the hyper-connection maps have dynamic parts (``alpha``) and constants
(``b_pre``, ``b_post``, ``B_res``) of their own, so that no map is uniform.

Counts: what the algorithm requires, not what an implementation does.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key
from unionml_tpu.models import latent_moe


def program_config(config: Dict[str, Any]) -> latent_moe.LatentMoEConfig:
    deployment = config.get("perfbench", {})
    rope = config["rope_scaling"]
    if rope["type"] != "yarn" or config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the program runs YaRN rotary positions and one routing group, and no other")
    if config["scoring_func"] != "sigmoid" or config["topk_method"] != "noaux_tc" or config["hidden_act"] != "silu":
        raise ValueError("the program's router is sigmoid with a noaux_tc bias, its experts SwiGLU")
    if config["moe_layer_freq"] != 1 or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("every layer after the dense ones is an expert layer; no attention bias; an untied head")
    return latent_moe.LatentMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["layers"], num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["n_routed_experts"], n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        first_k_dense_replace=config["first_k_dense_replace"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]), rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"], rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]), rope_original_max_position=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]), rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        hc_mult=config["hc_mult"], hc_sinkhorn_iters=config["hc_sinkhorn_iters"], hc_eps=config["hc_eps"],
        hc_res_clamp=(float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
        dtype=jnp.dtype(deployment.get("compute_dtype", "bfloat16")), **deployment.get("model_options", {}),
    )


def model(config: Dict[str, Any]):
    """The program's ``LatentMoELMHeadModel`` for the published sizes in ``config``."""
    return latent_moe.LatentMoELMHeadModel(program_config(config))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/xing4.py``'s functions take beside weights and ids."""
    rope = config["rope_scaling"]
    settings = config.get("perfbench", {}).get("reference_options", {})
    return dict(
        heads=config["num_attention_heads"], nope=config["qk_nope_head_dim"], vdim=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"], eps=config["rms_norm_eps"], streams=config["hc_mult"],
        top_k=config["num_experts_per_tok"], norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        hc_iters=config["hc_sinkhorn_iters"], hc_eps=config["hc_eps"],
        hc_clamp=(float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
        rope=dict(
            dim=config["qk_rope_head_dim"], theta=float(config["rope_theta"]), factor=float(rope["factor"]),
            original_max_position=rope["original_max_position_embeddings"],
            beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
            mscale=float(rope["mscale"]), mscale_all_dim=float(rope["mscale_all_dim"]),
        ),
        query_block=int(settings.get("query_block", 1024)), vocab_block=int(settings.get("vocab_block", 16384)),
        tie_margin=float(settings.get("tie_margin", 0.0)),
    )


# ------------------------------------------------------------------- weights


#: kernels N(0, gain / fan_in), and the projections that write to the residual
#: streams (attention's ``o``, every SwiGLU's ``down``) N(0, residual_gain /
#: fan_in): a sublayer's update is a fraction of the stream it is added to, as
#: in a trained network (and as GPT-2's own recipe scales them). Embedding N(0,
#: embed_std), norm scales 1 + N(0, scale_std). Every routed expert is a draw of
#: its own: a token sent to the wrong expert gets another function's output.
#: The router's selection bias N(0, router_bias_std): the choice is made on
#: sigmoid scores whose top ones lie 0.01 apart, so 0.02 already gives every
#: layer its favoured experts (the busiest expert's rows over the mean's 3.3
#: where an unbiased router reads 3.0) and 0.1 makes a few experts take a
#: third of all rows, another few on every seed. Of each hyper-connection:
#: Phi N(0, 1 / fan_in), the three alphas ``hc_alpha``, b_pre and b_post N(0,
#: hc_bias_std), B_res = hc_res_diag * I + N(0, hc_bias_std)
XING4_INIT: Dict[str, Any] = {
    "gain": 1.0, "residual_gain": 1.0, "embed_std": 1.0, "scale_std": 0.1, "router_bias_std": 0.02,
    "hc_alpha": 0.5, "hc_bias_std": 1.0, "hc_res_diag": 2.0,
}


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """``{path: (shape, kind)}`` of the parameter tree, one layer of each kind
    under ``dense_layer`` and ``expert_layer``."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    n, rank = config["hc_mult"], config["kv_lora_rank"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    kv_out = config["qk_nope_head_dim"] + config["v_head_dim"]
    experts, width = config["n_routed_experts"], config["moe_intermediate_size"]
    maps = 2 * n + n * n

    def hyper():
        return {"phi": ((n * d, maps), "f32_kernel"), "alpha": ((3,), "hc_alpha"), "bias": ((maps,), "hc_bias")}

    def swiglu(inner, lead=()):
        return {
            "gate": (lead + (d, inner), "kernel"), "up": (lead + (d, inner), "kernel"),
            "down": (lead + (inner, d), "residual"),
        }

    block = {
        "attn": {
            "q_a": ((d, config["q_lora_rank"]), "kernel"), "q_a_norm": ((config["q_lora_rank"],), "scale"),
            "q_b": ((config["q_lora_rank"], heads * qk), "kernel"),
            "kv_a": ((d, rank + config["qk_rope_head_dim"]), "kernel"), "kv_a_norm": ((rank,), "scale"),
            "kv_b": ((rank, heads * kv_out), "kernel"), "o": ((heads * config["v_head_dim"], d), "residual"),
        },
        "attn_hc": hyper(), "attn_norm": ((d,), "scale"), "mlp_hc": hyper(), "mlp_norm": ((d,), "scale"),
    }
    moe = {
        "router": ((d, experts), "f32_kernel"), "router_bias": ((experts,), "router_bias"),
        **swiglu(width, (experts,)), "shared": swiglu(width * config["n_shared_experts"]),
    }
    return {
        "embed": ((config["vocab_size"], d), "embed"), "lm_head": ((d, config["vocab_size"]), "kernel"),
        "final_norm": ((d,), "scale"),
        "dense_layer": {**block, "mlp": swiglu(config["intermediate_size"])},
        "expert_layer": {**block, "moe": moe},
    }


@functools.lru_cache(maxsize=None)
def _maker(config_items: tuple, init_items: tuple, dtype_name: str):
    config = dict(config_items)
    init = {**XING4_INIT, **dict(init_items)}
    dtype = jnp.dtype(dtype_name)
    n = config["hc_mult"]

    def draw(key, shape, kind):
        noise = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            return 1.0 + init["scale_std"] * noise
        if kind == "embed":
            return (init["embed_std"] * noise).astype(dtype)
        if kind == "router_bias":
            return init["router_bias_std"] * noise
        if kind == "hc_alpha":
            return jnp.full(shape, init["hc_alpha"], jnp.float32)
        if kind == "hc_bias":
            identity = jnp.concatenate([jnp.zeros((2 * n,)), jnp.eye(n).reshape(-1)])
            return init["hc_bias_std"] * noise + init["hc_res_diag"] * identity
        gain = init["residual_gain"] if kind == "residual" else init["gain"]
        leaf = noise * (gain / shape[-2]) ** 0.5  # kernels: (..., fan_in, fan_out)
        return leaf if kind == "f32_kernel" else leaf.astype(dtype)

    def drawn(key, spec):
        leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [draw(k, *leaf) for k, leaf in zip(keys, leaves)])

    def make(key):
        """Leaf by leaf, layer by layer: the experts' float32 noise (0.94 GB a
        projection a layer at the published widths) is alive one leaf at a time."""
        spec = shapes(config)
        tree = {name: drawn(jax.random.fold_in(key, i), {name: spec[name]})[name]
                for i, name in enumerate(("embed", "lm_head", "final_norm"))}
        for i in range(config["layers"]):
            kind = "dense_layer" if i < config["first_k_dense_replace"] else "expert_layer"
            tree[f"layer_{i}"] = drawn(jax.random.fold_in(key, 1000 + i), spec[kind])
        return tree

    return jax.jit(make)


_SIZE_KEYS = (
    "vocab_size", "hidden_size", "layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace", "hc_mult",
)


def make_params(config: Dict[str, Any], seed: int, dtype: str) -> Dict[str, Any]:
    """The parameter tree (without the ``{"params": ...}`` wrapper) for a
    configuration file's sizes and its ``perfbench.init`` recipe."""
    sizes = tuple((k, config[k]) for k in _SIZE_KEYS)
    init = tuple(sorted(config.get("perfbench", {}).get("init", {}).items()))
    return _maker(sizes, init, dtype)(seed_key(seed))


# -------------------------------------------------------------------- counts


def layer_matmul_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Weights one token's row meets in a matrix product, by part of a layer."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    n, rank = config["hc_mult"], config["kv_lora_rank"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attention = (
        d * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk
        + d * (rank + config["qk_rope_head_dim"])
        + rank * heads * (config["qk_nope_head_dim"] + config["v_head_dim"])
        + heads * config["v_head_dim"] * d
    )
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        "attention": attention,
        "hyper_connections": 2 * n * d * (2 * n + n * n),
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": expert,
        "router": d * config["n_routed_experts"],
        # what a token meets of an expert layer's feed-forward: its top k and the shared
        "expert_mlp": (config["num_experts_per_tok"] + config["n_shared_experts"]) * expert
        + d * config["n_routed_experts"],
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights that take part in a matrix product for every decoded token: the
    layers as cut (dense ones first, then expert layers with the token's top k
    experts and the shared one) and the untied head. The embedding is a look-up."""
    parts = layer_matmul_params(config)
    dense = min(config["first_k_dense_replace"], config["layers"])
    sparse = config["layers"] - dense
    per_layer = parts["attention"] + parts["hyper_connections"]
    return (
        config["layers"] * per_layer + dense * parts["dense_mlp"] + sparse * parts["expert_mlp"]
        + config["hidden_size"] * config["vocab_size"]
    )


def attention_flops(config: Dict[str, Any], keys: float) -> float:
    """Forward FLOPs of one query token's absorbed attention over ``keys``
    latent rows, all layers: every head scores the row (latent and rotary
    part) and weighs its latent part, 2 FLOPs a multiply-add."""
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    per_key = 2.0 * config["num_attention_heads"] * (row + config["kv_lora_rank"])
    return config["layers"] * per_key * keys


def decode_flops(config: Dict[str, Any], live_lengths: Iterable[float]) -> float:
    """Forward FLOPs of decode steps that advance one row per entry of
    ``live_lengths`` (the keys that row attends over, its new token included)."""
    dense = 2.0 * matmul_params(config)
    return sum(dense + attention_flops(config, keys) for keys in live_lengths)


def decode_attention_bytes(
    config: Dict[str, Any], live_lengths: Iterable[float], kv_bytes: float, act_bytes: float
) -> float:
    """Bytes decode attention has to move for those rows, all layers: each
    row's live latent rows once for all heads, every head's absorbed query in
    and its weighted latent out."""
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    per_key = row * kv_bytes
    per_row = config["num_attention_heads"] * (row + config["kv_lora_rank"]) * act_bytes
    return config["layers"] * sum(keys * per_key + per_row for keys in live_lengths)


def decode_expert_bytes(
    config: Dict[str, Any], experts_hit: float, expert_rows: float, weights_bytes: float, act_bytes: float
) -> float:
    """Bytes the routed experts have to move: the three projections of every
    expert that got a row, once, and each routed row in and out."""
    d = config["hidden_size"]
    return experts_hit * 3.0 * d * config["moe_intermediate_size"] * weights_bytes + expert_rows * 2.0 * d * act_bytes


def expert_load_max_over_mean(config: Dict[str, Any], expert_rows_max: float, expert_rows: float) -> float:
    """The busiest expert's rows over the mean expert's, from the sums of both
    over steps and expert layers."""
    return expert_rows_max * config["n_routed_experts"] / expert_rows
