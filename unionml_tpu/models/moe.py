"""Mixture-of-experts layer for the model zoo: router, losses, flax module.

Ties the expert-parallel dispatch primitives (:mod:`unionml_tpu.parallel.ep`) into a
usable network block. The reference has no math code at all (SURVEY.md: "no
CUDA/C++ anywhere"); this is part of the TPU-native model-family surface, alongside
BERT/GPT/MLP/CNN.

Components:

- :func:`router_z_loss` / :func:`load_balancing_loss` — the two standard router
  regularizers (ST-MoE z-loss keeps router logits small; the Switch/GShard balance
  loss pushes the token distribution toward uniform across experts).
- :class:`MoEMlp` — a drop-in replacement for a transformer MLP block: dense router,
  softmax gates, top-k capacity dispatch through
  :func:`unionml_tpu.parallel.ep.moe_apply_topk` (expert-sharded when a mesh with an
  ``"expert"`` axis is supplied, plain single-device dispatch otherwise); inference
  (``dropless=True``) computes every routed choice by group through
  :func:`unionml_tpu.parallel.ep.moe_apply_grouped`. Aux losses
  are sown under ``intermediates/router_z_loss`` and
  ``intermediates/load_balancing_loss`` — collect with
  ``model.apply(..., mutable=["intermediates"])`` and add them to the training loss.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from unionml_tpu.parallel.ep import moe_apply_a2a, moe_apply_grouped, moe_apply_topk


def router_z_loss(router_logits: jax.Array) -> jax.Array:
    """ST-MoE z-loss: mean squared logsumexp of the router logits.

    Keeps router logits from drifting large (which makes the softmax saturate and
    the routing gradient vanish). Scale with ~1e-3 in the training loss.
    """
    return jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)


def load_balancing_loss(gates: jax.Array, expert_index: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balancing loss: ``E * sum_e f_e * P_e``.

    ``f_e`` is the fraction of tokens whose TOP choice is expert e; ``P_e`` the mean
    router probability for e. Equals 1.0 at perfect balance; grows as routing
    collapses onto few experts. Scale with ~1e-2 in the training loss.
    """
    one_hot = jax.nn.one_hot(expert_index, num_experts, dtype=gates.dtype)  # (t, e)
    tokens_per_expert = jnp.mean(one_hot, axis=0)
    prob_per_expert = jnp.mean(gates, axis=0)
    return num_experts * jnp.sum(tokens_per_expert * prob_per_expert)


class MoEMlp(nn.Module):
    """Transformer MLP block with top-k expert routing.

    Input/output: (..., d_model) — leading dims are flattened to a token axis for
    dispatch and restored after. Experts are two-layer MLPs (d_model -> hidden ->
    d_model, gelu). ``mesh`` (static) enables expert-axis sharding constraints; it
    must carry an ``"expert"`` axis dividing ``num_experts``.
    """

    num_experts: int
    hidden_size: int
    k: int = 2
    capacity_factor: float = 1.25
    mesh: Optional[Any] = None
    dtype: Any = jnp.float32
    #: Switch-style multiplicative router jitter: router INPUTS scale by
    #: U[1-noise, 1+noise] when a "dropout" rng stream is supplied (i.e. during
    #: training); eval/generate calls carry no rng and stay deterministic.
    router_noise: float = 0.0
    #: "gshard" routes via global one-hot dispatch einsums (XLA infers the
    #: collectives from sharding constraints); "a2a" shards the tokens and moves
    #: only routed tokens with explicit lax.all_to_all over the expert axis —
    #: O(local_tokens x k x capacity_factor) per device, the pod-scale layout.
    #: "a2a" requires ``mesh``; the dropless (inference) path is grouped either way
    #: (:func:`unionml_tpu.parallel.ep.moe_apply_grouped`) and takes no mesh: with
    #: the stacked weights sharded over ``expert`` XLA's partitioner keeps them
    #: where they lie (it gathers the group sizes and reduces the outputs).
    dispatch: str = "gshard"
    #: token-sharding axis alongside "expert" for the a2a path (ignored when the
    #: mesh doesn't carry it)
    data_axis: str = "data"

    @nn.compact
    def __call__(self, x: jax.Array, dropless: bool = False, deterministic: bool = False) -> jax.Array:
        """``dropless=True`` disables the capacity drop (inference parity: a trained,
        imbalanced router must not silently zero overflow tokens during decode).
        ``deterministic=True`` additionally disables router jitter even when an rng
        stream is supplied — the same contract as ``nn.Dropout``."""
        d_model = x.shape[-1]
        tokens = x.reshape(-1, d_model)

        router_inputs = tokens.astype(jnp.float32)
        if self.router_noise > 0.0 and not deterministic and self.has_rng("dropout"):
            key = self.make_rng("dropout")
            router_inputs = router_inputs * jax.random.uniform(
                key, router_inputs.shape,
                minval=1.0 - self.router_noise, maxval=1.0 + self.router_noise,
            )
        router_logits = nn.Dense(self.num_experts, dtype=jnp.float32, name="router")(router_inputs)
        gates = jax.nn.softmax(router_logits, axis=-1)

        self.sow("intermediates", "router_z_loss", router_z_loss(router_logits))
        self.sow(
            "intermediates",
            "load_balancing_loss",
            load_balancing_loss(gates, jnp.argmax(router_logits, axis=-1), self.num_experts),
        )

        w_in = self.param(
            "w_in",
            nn.initializers.normal(0.02),
            (self.num_experts, d_model, self.hidden_size),
            self.dtype,
        )
        w_out = self.param(
            "w_out",
            nn.initializers.normal(0.02),
            (self.num_experts, self.hidden_size, d_model),
            self.dtype,
        )

        def expert_fn(params, toks):
            w1, w2 = params
            return jax.nn.gelu(toks @ w1) @ w2

        if self.dispatch not in ("gshard", "a2a"):
            raise ValueError(f"dispatch must be 'gshard' or 'a2a', got {self.dispatch!r}")
        # k=1 must NOT renormalize: top_gate/top_gate == 1.0 would erase the
        # Switch-style straight-through scaling (output scaled by the top-1 gate
        # value) — and with it the router's only gradient path through the task
        # loss. Same contract as ep.moe_apply_capacity, the top-1 wrapper.
        normalize = self.k > 1
        if dropless:
            # inference: every routed choice is computed, by group (no capacity)
            top_gates, top_index = jax.lax.top_k(gates, self.k)
            if normalize:
                top_gates = top_gates / jnp.maximum(jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9)

            def grouped_fn(params, rows, group_sizes):
                w1, w2 = params
                hidden = jax.nn.gelu(jax.lax.ragged_dot(rows, w1, group_sizes))
                return jax.lax.ragged_dot(hidden, w2, group_sizes)

            out, _ = moe_apply_grouped(
                grouped_fn, (w_in, w_out), tokens.astype(self.dtype), top_index,
                top_gates.astype(self.dtype),
            )
        elif self.dispatch == "a2a":
            if self.mesh is None or "expert" not in self.mesh.shape:
                raise ValueError("dispatch='a2a' requires a mesh with an 'expert' axis")
            out = moe_apply_a2a(
                expert_fn,
                (w_in, w_out),
                tokens.astype(self.dtype),
                gates.astype(self.dtype),
                self.mesh,
                k=self.k,
                capacity_factor=self.capacity_factor,
                normalize_gates=normalize,
                data_axis=self.data_axis,
            )
        else:
            out = moe_apply_topk(
                expert_fn,
                (w_in, w_out),
                tokens.astype(self.dtype),
                gates.astype(self.dtype),
                self.mesh,
                k=self.k,
                capacity_factor=self.capacity_factor,
                normalize_gates=normalize,
            )
        return out.reshape(x.shape).astype(x.dtype)


def collect_aux_losses(intermediates: Any, z_weight: float = 1e-3, balance_weight: float = 1e-2):
    """Sum the sown router losses from ``mutable=["intermediates"]`` output.

    Returns a scalar to ADD to the task loss: ``z_weight * sum(z losses) +
    balance_weight * sum(balance losses)`` across however many MoE layers sowed.
    """
    total = jnp.asarray(0.0, dtype=jnp.float32)
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(intermediates)[0]
    for path, leaf in leaves_with_paths:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "router_z_loss" in keys:
            total = total + z_weight * jnp.sum(jnp.asarray(leaf, dtype=jnp.float32))
        elif "load_balancing_loss" in keys:
            total = total + balance_weight * jnp.sum(jnp.asarray(leaf, dtype=jnp.float32))
    return total
