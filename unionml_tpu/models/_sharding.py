"""Shared machinery for rule-based parameter sharding tables.

Each model family (BERT encoder, GPT decoder) declares only its ``spec_for`` rule
function; the path flattening / key normalization / tree reconstruction live here so
a fix for new jax key types lands once for every family.
"""

import math
from typing import Any, Callable, Optional, Tuple

import jax


def shard_by_rules(
    params: Any,
    spec_for: Callable[[Tuple[str, ...], Any], Any],
    is_leaf: Optional[Callable[[Any], bool]] = None,
) -> Any:
    """Apply ``spec_for((path parts), leaf) -> PartitionSpec`` over a parameter tree.

    ``is_leaf`` stops flattening at composite leaves (e.g. ``QuantizedArray``
    nodes) so ``spec_for`` sees the whole node and can return a matching
    composite spec node instead of per-child specs."""
    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_leaf)[0]
    treedef = jax.tree_util.tree_structure(params, is_leaf=is_leaf)
    specs = [
        spec_for(tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path), leaf)
        for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def place_by_specs(params: Any, mesh: Any, spec_tree: Any) -> Any:
    """Lay a parameter tree onto ``mesh`` per a matching ``PartitionSpec`` tree.

    The serving-side counterpart of the trainer's ``jit(..., out_shardings=...)``
    layout: parameters arrive as host (or single-device) arrays and are committed
    to the mesh in one transfer, so the resident executables compile against
    already-sharded weights instead of replicating them per call.

    The spec tables are written from axis NAMES and never see a shape, so a
    dimension its mesh axes do not divide (GPT-2's 50257-row vocabulary over
    ``tensor=4``) is replicated here instead of failing the transfer.
    """
    from jax.sharding import PartitionSpec

    from unionml_tpu.parallel.mesh import named_sharding_tree

    def fit(leaf, spec):
        def divides(dim, axes):
            names = axes if isinstance(axes, tuple) else () if axes is None else (axes,)
            return dim % math.prod(mesh.shape[name] for name in names) == 0

        return PartitionSpec(
            *(axes if divides(dim, axes) else None for dim, axes in zip(leaf.shape, tuple(spec)))
        )

    spec_tree = jax.tree_util.tree_map(fit, params, spec_tree)
    return jax.device_put(params, named_sharding_tree(mesh, spec_tree))
