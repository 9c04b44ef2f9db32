"""Expert parallelism: a mixture-of-experts layer sharded over an ``"expert"`` mesh axis.

Each device owns ``experts_per_device`` expert MLPs (parameters sharded on their
leading expert axis). Three training dispatch formulations, in increasing scalability, and one for inference:

- :func:`moe_apply` — dense-masked top-1: every device computes its local experts
  over the FULL token set, masks by assignment, ``psum`` combines. O(experts_per_device
  x total_tokens) overcompute; the exactness oracle the scalable paths are tested
  against, and fine at testbench scale.
- :func:`moe_apply_topk` / :func:`moe_apply_capacity` — GShard capacity dispatch via
  one-hot einsums with ``expert``-axis sharding constraints; XLA infers the
  collectives. The (tokens, experts, capacity) dispatch tensors are still global.
- :func:`moe_apply_grouped` — the DROPLESS inference dispatch: (token, expert)
  pairs sorted by expert and one ``jax.lax.ragged_dot`` a projection over the
  group sizes, ``tokens x k`` rows of work whatever the routing.
- :func:`moe_apply_a2a` — explicit ``shard_map`` + ``lax.all_to_all`` token dispatch:
  tokens are sharded, each device routes only its local tokens into per-expert
  capacity buffers, and two all-to-alls (dispatch + return) ride the ICI. Per-device
  compute and memory are O(num_experts x capacity) ~ O(local_tokens x k x
  capacity_factor), independent of the global token count — the pod-scale layout.
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from unionml_tpu.parallel._compat import shard_map

EXPERT_AXIS = "expert"


def _moe_local(expert_params, tokens, assignment, *, expert_fn, axis_name: str, experts_per_device: int):
    """Per-device body: run local experts on all tokens, mask, combine via psum."""
    device_index = lax.axis_index(axis_name)
    out = jnp.zeros(tokens.shape[:-1] + (_out_dim(expert_fn, expert_params, tokens),), dtype=tokens.dtype)
    for local_e in range(experts_per_device):
        global_e = device_index * experts_per_device + local_e
        params_e = jax.tree_util.tree_map(lambda p: p[local_e], expert_params)
        expert_out = expert_fn(params_e, tokens)
        mask = (assignment == global_e)[..., None].astype(tokens.dtype)
        out = out + expert_out * mask
    return lax.psum(out, axis_name)


def _out_dim(expert_fn, expert_params, tokens):
    params_0 = jax.tree_util.tree_map(lambda p: p[0], expert_params)
    return jax.eval_shape(expert_fn, params_0, tokens).shape[-1]


def moe_apply(
    expert_fn: Callable,
    stacked_params: Any,
    tokens: jax.Array,
    assignment: jax.Array,
    mesh: Mesh,
    *,
    axis: str = EXPERT_AXIS,
) -> jax.Array:
    """Apply a top-1-routed mixture of experts sharded over ``axis``.

    :param expert_fn: ``(params, tokens) -> outputs`` applied per expert.
    :param stacked_params: pytree with a leading ``num_experts`` axis; sharded over
        ``axis`` (``num_experts`` must divide by the axis size).
    :param tokens: (..., d_model) token activations (replicated).
    :param assignment: (...,) int32 expert index per token (the router's argmax).
    """
    num_devices = mesh.shape[axis]
    num_experts = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if num_experts % num_devices:
        raise ValueError(
            f"num_experts ({num_experts}) must be divisible by the {axis!r} axis size ({num_devices})"
        )
    experts_per_device = num_experts // num_devices

    params_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    body = functools.partial(
        _moe_local, expert_fn=expert_fn, axis_name=axis, experts_per_device=experts_per_device
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, P(), P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, tokens, assignment)


def expert_sharding(mesh: Mesh, axis: str = EXPERT_AXIS) -> NamedSharding:
    """Sharding for stacked per-expert parameters (leading expert axis)."""
    return NamedSharding(mesh, P(axis))


def moe_apply_capacity(
    expert_fn: Callable,
    stacked_params: Any,
    tokens: jax.Array,
    gates: jax.Array,
    mesh: Mesh,
    *,
    capacity_factor: float = 1.25,
    axis: str = EXPERT_AXIS,
) -> jax.Array:
    """GShard-style capacity-based top-1 MoE: sharding constraints, XLA collectives.

    Unlike :func:`moe_apply` (dense-masked, every device computes all tokens), this
    formulation dispatches each token into its expert's fixed-capacity buffer via
    one-hot einsums; expert buffers carry an ``expert``-axis sharding constraint, so
    under ``jit`` XLA inserts the all-to-alls that move only each expert's tokens to
    its device. Tokens beyond an expert's capacity are DROPPED (output zero) — the
    standard GShard trade-off; size ``capacity_factor`` accordingly.

    :param gates: (tokens, num_experts) router probabilities (e.g. softmax output);
        the top-1 expert's gate value scales its output (straight-through routing).
    :returns: (tokens, d_out) combined expert outputs.
    """
    # exactly the k=1 special case of the top-k dispatch: argmax == top_k(1) (both
    # break ties toward the lower index) and the unnormalized top-1 gate is the
    # plain gate value — one implementation, one place to fix routing bugs
    return moe_apply_topk(
        expert_fn,
        stacked_params,
        tokens,
        gates,
        mesh,
        k=1,
        capacity_factor=capacity_factor,
        normalize_gates=False,
        axis=axis,
    )


def moe_apply_grouped(
    grouped_fn: Callable,
    stacked_params: Any,
    tokens: jax.Array,
    top_index: jax.Array,
    top_weights: jax.Array,
):
    """DROPLESS top-k dispatch by group: the inference path.

    The ``tokens x k`` (token, expert) pairs are sorted by expert, so that each
    expert's rows are contiguous; ``grouped_fn(stacked_params, rows,
    group_sizes)`` runs every expert over its own rows only (one
    ``jax.lax.ragged_dot`` a projection: ``rows`` is ``(tokens * k, d)``,
    ``group_sizes`` the ``(num_experts,)`` int32 row counts, in expert order);
    the outputs are unsorted, weighted and summed over each token's k choices.
    No capacity, so no token is dropped whatever the router's imbalance, and
    the work is ``tokens * k`` rows — not ``tokens * num_experts`` as the
    dense-masked formulation this replaces (every expert over every token).

    :param top_index: ``(tokens, k)`` int expert choices (the router's top-k).
    :param top_weights: ``(tokens, k)`` combine weights of those choices.
    :returns: ``(out, group_sizes)``: the ``(tokens, d_out)`` combined outputs
        and the rows each expert received (what load counters read).
    """
    num_tokens, k = top_index.shape
    num_experts = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    flat = top_index.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)  # pair j of the sorted rows is pair order[j]
    group_sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    rows = jnp.take(tokens, order // k, axis=0)
    outputs = grouped_fn(stacked_params, rows, group_sizes)  # (tokens * k, d_out)
    unsorted = jnp.take(outputs, jnp.argsort(order), axis=0).reshape(num_tokens, k, -1)
    out = jnp.einsum("tkd,tk->td", unsorted, top_weights.astype(unsorted.dtype))
    return out, group_sizes


def moe_apply_topk(
    expert_fn: Callable,
    stacked_params: Any,
    tokens: jax.Array,
    gates: jax.Array,
    mesh: Optional[Mesh] = None,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    normalize_gates: bool = True,
    axis: str = EXPERT_AXIS,
) -> jax.Array:
    """GShard top-k (default top-2) capacity-based MoE dispatch.

    The capacity drop is a TRAINING trade-off; inference routes droplessly
    through :func:`moe_apply_grouped` (no token ever loses a routed choice).

    Generalizes :func:`moe_apply_capacity` to k routed experts per token: each token
    claims up to ``k`` expert-buffer slots, choice-major — every token's FIRST choice
    is assigned buffer positions before any second choice, so overflow drops lower-
    priority choices first (the GShard ordering). Combined output is the gate-weighted
    sum over surviving choices; ``normalize_gates`` renormalizes over the top-k
    (the standard top-2 formulation).

    With a ``mesh``, expert buffers carry ``axis`` sharding constraints, so under
    ``jit`` XLA inserts the all-to-alls that move only each expert's tokens to its
    device; ``mesh=None`` runs the same dispatch unsharded (single-device layers,
    e.g. :class:`unionml_tpu.models.moe.MoEMlp` without expert parallelism).
    """
    num_tokens, num_experts = gates.shape
    params_experts = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if params_experts != num_experts:
        raise ValueError(
            f"gates are over {num_experts} experts but stacked_params carries {params_experts}"
        )
    if mesh is not None and num_experts % mesh.shape[axis]:
        raise ValueError(
            f"num_experts ({num_experts}) must be divisible by the {axis!r} axis size "
            f"({mesh.shape[axis]})"
        )
    if not 1 <= k <= num_experts:
        raise ValueError(f"k ({k}) must be in [1, num_experts={num_experts}]")

    top_gates, top_index = jax.lax.top_k(gates, k)  # (t, k)
    if normalize_gates:
        top_gates = top_gates / jnp.maximum(jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9)

    capacity = max(int(np.ceil(num_tokens * k / num_experts * capacity_factor)), 1)

    dispatch, combine = _topk_dispatch_combine(
        top_index, top_gates, num_experts, capacity, tokens.dtype
    )

    expert_inputs = jnp.einsum("tec,td->ecd", dispatch, tokens)  # (e, c, d)
    if mesh is not None:
        expert_inputs = jax.lax.with_sharding_constraint(
            expert_inputs, NamedSharding(mesh, P(axis, None, None))
        )
    expert_outputs = jax.vmap(expert_fn)(stacked_params, expert_inputs)  # (e, c, d_out)
    if mesh is not None:
        expert_outputs = jax.lax.with_sharding_constraint(
            expert_outputs, NamedSharding(mesh, P(axis, None, None))
        )

    out = jnp.einsum("tec,ecd->td", combine, expert_outputs.astype(tokens.dtype))
    return out.astype(tokens.dtype)


def _topk_dispatch_combine(top_index, top_gates, num_experts: int, capacity: int, dtype):
    """(t, k) top-k routing -> (t, e, c) dispatch / combine tensors.

    Choice-major position assignment: flatten to (k * t, e) with choice 0 first so
    first choices never lose a buffer slot to someone's second choice (int32: a
    low-precision cumsum would corrupt routing past 256 tokens per expert). The
    position one-hot zeroes slots >= capacity — that IS the drop.
    """
    num_tokens, k = top_index.shape
    one_hot_i = jax.nn.one_hot(top_index, num_experts, dtype=jnp.int32)  # (t, k, e)
    choice_major = jnp.swapaxes(one_hot_i, 0, 1).reshape(k * num_tokens, num_experts)
    positions_flat = jnp.sum(
        (jnp.cumsum(choice_major, axis=0) - choice_major) * choice_major, axis=-1
    )  # (k * t,)
    position = jnp.swapaxes(positions_flat.reshape(k, num_tokens), 0, 1)  # (t, k)

    one_hot = one_hot_i.astype(dtype)  # (t, k, e)
    position_one_hot = jax.nn.one_hot(position, capacity, dtype=dtype)  # (t, k, c)
    dispatch = jnp.einsum("tke,tkc->tec", one_hot, position_one_hot)
    combine = jnp.einsum("tke,tkc,tk->tec", one_hot, position_one_hot, top_gates.astype(dtype))
    return dispatch, combine


def _moe_a2a_local(
    local_params,
    tokens,
    gates,
    *,
    expert_fn,
    axis_name: str,
    num_experts: int,
    experts_per_device: int,
    k: int,
    capacity: int,
    normalize_gates: bool,
):
    """Per-device body of :func:`moe_apply_a2a` (tokens/gates are LOCAL shards).

    Buffer layout through the exchange: ``send`` is (num_experts, capacity, d)
    ordered by GLOBAL expert index; grouped as (ep_degree, experts_per_device *
    capacity, d) a tiled ``all_to_all`` delivers group j to device j, so each
    device receives (ep_degree, experts_per_device, capacity, d) = every source
    device's buffers for ITS experts. The return trip applies the inverse
    transpose, and the combine einsum runs on the token's home device.
    """
    ep_degree = num_experts // experts_per_device
    d_model = tokens.shape[-1]

    top_gates, top_index = jax.lax.top_k(gates, k)  # (t_local, k)
    if normalize_gates:
        top_gates = top_gates / jnp.maximum(jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9)
    dispatch, combine = _topk_dispatch_combine(
        top_index, top_gates, num_experts, capacity, tokens.dtype
    )

    send = jnp.einsum("tec,td->ecd", dispatch, tokens)  # (E, c, d): my tokens, bucketed
    send = send.reshape(ep_degree, experts_per_device * capacity, d_model)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # (src_device, experts_per_device, c, d) -> (experts_per_device, src * c, d)
    expert_inputs = (
        recv.reshape(ep_degree, experts_per_device, capacity, d_model)
        .transpose(1, 0, 2, 3)
        .reshape(experts_per_device, ep_degree * capacity, d_model)
    )

    expert_outputs = jax.vmap(expert_fn)(local_params, expert_inputs)
    d_out = expert_outputs.shape[-1]

    back = (
        expert_outputs.reshape(experts_per_device, ep_degree, capacity, d_out)
        .transpose(1, 0, 2, 3)
        .reshape(ep_degree, experts_per_device * capacity, d_out)
    )
    returned = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0, tiled=True)
    returned = returned.reshape(num_experts, capacity, d_out)  # my tokens' outputs, by expert

    out = jnp.einsum("tec,ecd->td", combine, returned.astype(tokens.dtype))
    return out.astype(tokens.dtype)


def moe_apply_a2a(
    expert_fn: Callable,
    stacked_params: Any,
    tokens: jax.Array,
    gates: jax.Array,
    mesh: Mesh,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    normalize_gates: bool = True,
    axis: str = EXPERT_AXIS,
    data_axis: Optional[str] = "data",
) -> jax.Array:
    """Top-k MoE with explicit ``lax.all_to_all`` token dispatch (the pod-scale path).

    Tokens are sharded over ``(data_axis, axis)`` (or just ``axis`` when the mesh has
    no ``data_axis``); each device routes ONLY its local tokens into per-expert
    capacity buffers, one all-to-all over the expert axis moves each buffer to the
    device owning that expert, local experts run on (experts_per_device, ep_degree *
    capacity) batches, and a second all-to-all returns outputs to each token's home
    device for the gate-weighted combine. Per-device compute is O(num_experts x
    capacity) ~ O(local_tokens x k x capacity_factor) — independent of the global
    token count, unlike :func:`moe_apply`'s dense-masked formulation.

    Capacity is granted PER (source device, expert): ``ceil(local_tokens * k /
    num_experts * capacity_factor)`` slots for each expert on each source shard.
    Routing therefore drops a choice only when one shard's local demand for one
    expert overflows — global capacity scales with the EP degree, so for a given
    ``capacity_factor`` this drops at most as often as :func:`moe_apply_topk`'s
    global budget when token shards are balanced (the DP-sharded training case).
    Exact parity with the dense oracle holds whenever nothing drops (tested).

    :param tokens: (num_tokens, d_model), dim 0 divisible by the token-shard count.
    :param gates: (num_tokens, num_experts) router probabilities, sharded like
        ``tokens``.
    """
    num_tokens, num_experts = gates.shape
    params_experts = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if params_experts != num_experts:
        raise ValueError(
            f"gates are over {num_experts} experts but stacked_params carries {params_experts}"
        )
    ep_degree = mesh.shape[axis]
    if num_experts % ep_degree:
        raise ValueError(
            f"num_experts ({num_experts}) must be divisible by the {axis!r} axis size ({ep_degree})"
        )
    if not 1 <= k <= num_experts:
        raise ValueError(f"k ({k}) must be in [1, num_experts={num_experts}]")
    token_axes = (data_axis, axis) if data_axis and data_axis in mesh.shape else (axis,)
    shard_count = int(np.prod([mesh.shape[a] for a in token_axes]))
    if num_tokens % shard_count:
        raise ValueError(
            f"num_tokens ({num_tokens}) must be divisible by the token-shard count "
            f"({shard_count}: mesh axes {token_axes})"
        )
    t_local = num_tokens // shard_count
    capacity = max(int(np.ceil(t_local * k / num_experts * capacity_factor)), 1)

    params_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    body = functools.partial(
        _moe_a2a_local,
        expert_fn=expert_fn,
        axis_name=axis,
        num_experts=num_experts,
        experts_per_device=num_experts // ep_degree,
        k=k,
        capacity=capacity,
        normalize_gates=normalize_gates,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, P(token_axes), P(token_axes)),
        out_specs=P(token_axes),
        check_vma=False,
    )(stacked_params, tokens, gates)
