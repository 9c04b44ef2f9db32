"""Parallelism engine: meshes, data parallelism, sequence parallelism, multi-host.

See :mod:`unionml_tpu.parallel.mesh` for the axis conventions and the design stance:
communication is sharding annotations over a Mesh, lowered by XLA to ICI/DCN
collectives — the TPU-native replacement for an NCCL/MPI backend (SURVEY.md §2).
"""

from unionml_tpu.parallel.dp import batches, data_parallel_eval, data_parallel_step, pad_to_multiple
from unionml_tpu.parallel.ep import (
    expert_sharding,
    moe_apply,
    moe_apply_a2a,
    moe_apply_capacity,
    moe_apply_grouped,
    moe_apply_topk,
)
from unionml_tpu.parallel.pp import (
    circular_superstage,
    pipeline_apply,
    pipeline_apply_circular,
    stage_sharding,
    superstage,
)
from unionml_tpu.parallel.ring import ring_attention, sequence_sharding
from unionml_tpu.parallel.ulysses import ulysses_attention
from unionml_tpu.parallel.mesh import (
    DATA_AXIS,
    FSDP_AXIS,
    SEQUENCE_AXIS,
    TENSOR_AXIS,
    MeshSpec,
    batch_sharding,
    logical_to_sharding,
    make_hybrid_mesh,
    make_mesh,
    named_sharding_tree,
    replicated,
    shard_batch,
)

__all__ = [
    "DATA_AXIS",
    "FSDP_AXIS",
    "SEQUENCE_AXIS",
    "TENSOR_AXIS",
    "MeshSpec",
    "batch_sharding",
    "batches",
    "data_parallel_eval",
    "data_parallel_step",
    "expert_sharding",
    "logical_to_sharding",
    "moe_apply",
    "moe_apply_a2a",
    "moe_apply_capacity",
    "moe_apply_grouped",
    "moe_apply_topk",
    "circular_superstage",
    "pipeline_apply",
    "pipeline_apply_circular",
    "sp_attention",
    "superstage",
    "stage_sharding",
    "make_hybrid_mesh",
    "make_mesh",
    "named_sharding_tree",
    "pad_to_multiple",
    "replicated",
    "ring_attention",
    "sequence_sharding",
    "shard_batch",
    "ulysses_attention",
]


def sp_attention(q, k, v, mesh, impl: str, *, causal: bool = False, kv_lens=None):
    """Dispatch to a sequence-parallel attention impl ("ring" | "ulysses").

    The single place both model families route their long-context path through —
    one mesh check, one impl table (new strategies land here once).
    """
    if mesh is None:
        raise ValueError(f"attention_impl={impl!r} requires a sequence-parallel mesh (sp_mesh)")
    from unionml_tpu.parallel.ring import ring_attention
    from unionml_tpu.parallel.ulysses import ulysses_attention

    table = {"ring": ring_attention, "ulysses": ulysses_attention}
    try:
        fn = table[impl]
    except KeyError:
        raise ValueError(f"Unknown sequence-parallel impl {impl!r}; expected one of {sorted(table)}") from None
    return fn(q, k, v, mesh, causal=causal, kv_lens=kv_lens)
