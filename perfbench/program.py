"""The program's model configuration for a configuration file's published sizes."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp


def program_config(config: Dict[str, Any]):
    """The program's ``GPTConfig`` for the published sizes in ``config``."""
    from unionml_tpu.models.gpt import GPTConfig

    deployment = config["perfbench"]
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"], dropout=config["resid_pdrop"],
        dtype=jnp.dtype(deployment["compute_dtype"]), **deployment.get("model_options", {}),
    )
