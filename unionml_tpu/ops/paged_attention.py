"""Paged-attention decode: fused Pallas dequant-attend straight off the block pool.

The paged serving path (PRs 11/14) keeps every slot's KV in a shared block pool
— int8 codes plus per-(block, head) scales under ``kv_quantize`` — and the XLA
decode step pays a ``pool[table]`` gather that materializes a dense, dequantized
KV copy before attending (``models/gpt.py`` ``gather_table``). On real HBM that
copy is ~4x the bytes the int8 codes occupy, per step, per layer. The kernel
here deletes it: each grid step DMAs ONE pool block's codes (+ its scales)
straight out of HBM via the slot's block-table row (scalar-prefetched, so the
index feeds the DMA engine), dequantizes in VMEM, and folds the block into an
online-softmax accumulation — flash-decoding over the table indirection. HBM
traffic per step is the int8 codes + scales; the bf16-pool variant simply skips
the dequant.

Two implementations behind one dispatcher (the ``ops/attention.py`` contract):

- ``impl="pallas"``: the fused kernel. Grid ``(batch, head_groups, width)`` with
  the table walk innermost; VMEM scratch carries the (m, l, acc) softmax state
  across blocks, initialized at ``w == 0`` and normalized/written at the last
  block.
- ``impl="xla"``: gather-dequant-attend, arithmetic-identical to the historical
  ``gather_table`` + ``xla_attention`` path (the reference the kernel is pinned
  against, and what runs off-TPU).
- ``impl="auto"``: on a TPU backend the verdict of
  :func:`unionml_tpu.ops.tuning.pick_paged_impl` for the shape class (pallas
  unless the table says otherwise), XLA on every other backend. The choice is
  made once, from the backend and the shapes — there is no runtime fallback
  between the arms: ``impl="pallas"`` off a TPU raises unless the caller asked
  for the Pallas interpreter (``interpret=True``, a test argument).

Under a device mesh the kernel runs inside ``shard_map`` with the pool's heads
local to each ``tensor`` shard (``mesh=``): a Mosaic custom call is opaque to
the SPMD partitioner, which refuses it ("Mosaic kernels cannot be
automatically partitioned") wherever a multi-device ``jit`` meets it bare.

Layout contract (matches ``init_block_pool``): pool leaves are
``(num_blocks, heads, block_size, head_dim)``; scales ``(num_blocks, heads, 1,
1)`` f32; ``block_table`` is ``(batch, width)`` int32; a query token at logical
position ``p`` attends keys at logical positions ``k <= p``, where logical
column ``c = w * block_size + o`` lives in pool block ``table[row, w]``. Table
columns past a row's live range point at the engine's scratch block — their
positions exceed every live query position, so the mask discards them without
any per-row length plumbing.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unionml_tpu.ops.attention import on_tpu, xla_attention

_NEG_INF = -1e30


def xla_paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_table: jax.Array,
    base_positions: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    out_dtype=None,
) -> jax.Array:
    """Reference paged attention: gather the table, dequantize, attend dense.

    Arithmetic-identical to the historical in-model path: ``pool[table]``
    gather, ``(codes.astype(f32) * scale).astype(out_dtype)`` dequant,
    block-structure flatten, then :func:`xla_attention` under the positional
    mask ``k_pos <= base + s``. This is the exactness reference the kernel's
    parity gates pin against, and the off-TPU arm of the dispatcher.
    """
    batch, heads, S, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    capacity = width * block_size
    out_dtype = q.dtype if out_dtype is None else out_dtype

    def gather(pool_leaf, scale_leaf):
        blocks = pool_leaf[block_table]  # (batch, width, heads, bs, hd)
        if scale_leaf is not None:
            blocks = (blocks.astype(jnp.float32) * scale_leaf[block_table]).astype(out_dtype)
        return jnp.moveaxis(blocks, 2, 1).reshape(batch, heads, capacity, head_dim)

    k_pos = jnp.arange(capacity)
    q_pos = base_positions.astype(jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, :, :]
    return xla_attention(q, gather(k, k_scale), gather(v, v_scale), mask=mask)


def _paged_kernel(
    table_ref,  # scalar prefetch: (batch, width) int32
    base_ref,  # scalar prefetch: (batch,) int32 query base positions
    q_ref,  # (1, gh, S, hd)
    k_ref,  # (1, gh, bs, hd) one pool block's codes (int8/f32) or bf16 values
    v_ref,
    *rest,  # [k_scale_ref, v_scale_ref] when quantized, then o_ref + scratch
    block_size: int,
    sm_scale: float,
    quantized: bool,
    out_dtype,
):
    """One (batch row, head group, table column) program of the online softmax.

    The scalar-prefetched table row already steered this block's DMA (see the
    index maps in :func:`_paged_forward`); the body only needs the COLUMN index
    for positional masking: logical key position ``w * block_size + o`` against
    the row's query base. Scratch (acc, m, l) persists across the innermost
    grid axis — initialized at the first column, normalized into ``o_ref`` at
    the last — exactly the flash-attention recurrence of
    ``attention._flash_kernel``, walked over the table instead of a dense KV.

    Dequant mirrors the XLA gather arm bit for bit on VALUES:
    ``(codes.astype(f32) * scale).astype(out_dtype)`` — the cast to the compute
    dtype is the same value quantization ``gather_table`` applied, so both arms
    attend over identical K/V elements and differ only in summation order.
    """
    if quantized:
        k_scale_ref, v_scale_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        k_scale_ref = v_scale_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest

    w = pl.program_id(2)
    nw = pl.num_programs(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    gh, S, head_dim = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0].astype(jnp.float32)  # (gh, S, hd)
    k = k_ref[0]
    v = v_ref[0]
    if quantized:
        # per-(block, head) scales, (gh, 1, 1) by the block spec: they broadcast
        # over the block's (bs, hd) tile with no in-kernel reshape
        k = (k.astype(jnp.float32) * k_scale_ref[0]).astype(out_dtype)
        v = (v.astype(jnp.float32) * v_scale_ref[0]).astype(out_dtype)
    k = k.astype(jnp.float32)  # (gh, bs, hd)
    v = v.astype(jnp.float32)

    if gh == 1:
        scores = jax.lax.dot_general(
            q[0], k[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )[None]  # (1, S, bs)
    else:
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # (gh, S, bs)
    scores = scores * sm_scale

    base = base_ref[pl.program_id(0)]
    k_pos = w * block_size + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    q_pos = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = k_pos <= q_pos
    scores = jnp.where(valid, scores, _NEG_INF)

    m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)  # (gh, S, 1) lanes replicated
    l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # a fully-masked block (scratch column / beyond the row) must contribute
    # exactly 0: for live rows exp underflows there anyway, but when EVERY
    # column is masked m_new stays _NEG_INF and exp(0) would be 1
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    correction = jnp.exp(m_prev - m_new)
    if gh == 1:
        pv = jax.lax.dot_general(
            probs[0], v[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )[None]
    else:
        pv = jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
    acc_ref[...] = acc_ref[...] * correction + pv
    l_new = l_prev * correction + jnp.sum(probs, axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(w == nw - 1)
    def _finalize():
        l_final = jnp.max(l_ref[...], axis=-1, keepdims=True)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_final, 1e-30)).astype(o_ref.dtype)


def _paged_forward(
    q, k, v, block_table, base_positions, k_scale, v_scale, out_dtype,
    heads_per_step, interpret,
):
    batch, heads, S, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    quantized = k_scale is not None
    gh = heads_per_step if heads % heads_per_step == 0 else 1
    sm_scale = 1.0 / np.sqrt(head_dim)

    kernel = functools.partial(
        _paged_kernel,
        block_size=block_size,
        sm_scale=sm_scale,
        quantized=quantized,
        out_dtype=out_dtype,
    )
    # index maps see (b, h, w, table_ref, base_ref): the scalar-prefetched table
    # row turns the grid's column coordinate into the pool block to DMA — this
    # indirection IS the kernel's reason to exist (no gathered copy)
    in_specs = [
        pl.BlockSpec((1, gh, S, head_dim), lambda b, h, w, tbl, base: (b, h, 0, 0)),
        pl.BlockSpec((1, gh, block_size, head_dim), lambda b, h, w, tbl, base: (tbl[b, w], h, 0, 0)),
        pl.BlockSpec((1, gh, block_size, head_dim), lambda b, h, w, tbl, base: (tbl[b, w], h, 0, 0)),
    ]
    operands = [q, k, v]
    if quantized:
        # the scales keep the pool's own rank-4 (blocks, heads, 1, 1) layout: a
        # (1, gh, 1, 1) block's last two dims equal the array's, which is the
        # one sub-(8, 128) block shape the Mosaic lowering accepts (a (1, gh)
        # block of a (blocks, heads) view is refused)
        scale_spec = pl.BlockSpec((1, gh, 1, 1), lambda b, h, w, tbl, base: (tbl[b, w], h, 0, 0))
        in_specs.extend([scale_spec, scale_spec])
        operands.extend([k_scale, v_scale])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, heads // gh, width),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gh, S, head_dim), lambda b, h, w, tbl, base: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gh, S, head_dim), jnp.float32),
            pltpu.VMEM((gh, S, 128), jnp.float32),
            pltpu.VMEM((gh, S, 128), jnp.float32),
        ],
    )
    codes_bytes = 2 * width * heads * block_size * head_dim * k.dtype.itemsize
    scale_bytes = 2 * width * heads * 4 if quantized else 0
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, S, head_dim), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * heads * S * width * block_size * head_dim,
            bytes_accessed=batch * (q.size // batch * 2 * q.dtype.itemsize + codes_bytes + scale_bytes),
            transcendentals=batch * heads * S * width * block_size,
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        jnp.asarray(base_positions, jnp.int32).reshape(batch),
        *operands,
    )
    return out


def resolve_paged_impl(
    impl: str, table_width: int, block_size: int, heads: int, head_dim: int
) -> str:
    """Resolve ``"auto"`` to the backend the dispatcher would pick.

    Exposed separately so serving telemetry (``unionml_paged_attn_impl``, the
    ``/stats`` ``impl`` field) can report the selection without tracing."""
    if impl == "auto":
        if on_tpu():
            from unionml_tpu.ops.tuning import pick_paged_impl

            return pick_paged_impl(table_width, block_size, heads, head_dim)
        return "xla"
    if impl in ("pallas", "xla"):
        return impl
    raise ValueError(f"Unknown paged attention impl {impl!r}; expected 'auto', 'pallas', or 'xla'")


def _head_spec(mesh, heads: int):
    """Spec of every rank-4 kernel operand under ``mesh``: heads on ``tensor``
    when the axis divides them — the engine's pool layout
    (:func:`unionml_tpu.models.gpt.kv_block_spec`) — else replicated."""
    from jax.sharding import PartitionSpec as P

    from unionml_tpu.parallel.mesh import TENSOR_AXIS

    size = int(mesh.shape.get(TENSOR_AXIS, 1))
    return P(None, TENSOR_AXIS, None, None) if size > 1 and heads % size == 0 else P()


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_table: jax.Array,
    base_positions: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    out_dtype=None,
    impl: str = "auto",
    interpret: bool = False,
    mesh=None,
) -> jax.Array:
    """Attend ``q`` over a row's paged KV through its block-table row.

    :param q: ``(batch, heads, S, head_dim)`` queries (``S == 1`` decode; the
        batch-1 chunk-prefill path passes the whole chunk).
    :param k / v: pool leaves ``(num_blocks, heads, block_size, head_dim)`` —
        int8 codes when ``k_scale``/``v_scale`` ride along, else the compute
        dtype. (The speculative-verify path passes its gathered local state
        reshaped to this layout with an identity table; codes may then be f32
        holding exact integers — the dequant arithmetic is dtype-agnostic.)
    :param block_table: ``(batch, width)`` int32 map from logical block index
        to pool block; unmapped tail columns point at the scratch block.
    :param base_positions: ``(batch,)`` int32; query token ``s`` of row ``b``
        sits at logical position ``base_positions[b] + s`` and attends key
        positions ``<= base + s``. Retired rows carry the sentinel position —
        their masked output is garbage the engine never samples.
    :param k_scale / v_scale: ``(num_blocks, heads, 1, 1)`` f32 monotone block
        scales (int8 pools); ``None`` selects the full-precision variant.
    :param out_dtype: dequant target (the compute dtype); defaults to
        ``q.dtype``. Matches the XLA arm's value quantization exactly.
    :param impl: ``"auto"`` (the shape class's verdict on TPU, XLA elsewhere),
        ``"pallas"``, or ``"xla"``.
    :param interpret: run the kernel under the Pallas interpreter — how CPU
        tests exercise ``impl="pallas"``. Never derived from the backend:
        without it the kernel off a TPU is an error, not a silent slow path.
    :param mesh: the serving mesh when the call sits inside a multi-device
        ``jit``: the kernel runs under ``shard_map`` with heads local to each
        ``tensor`` shard (replicated when the axis does not divide them).
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    out_dtype = q.dtype if out_dtype is None else out_dtype
    batch, heads, _, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    impl = resolve_paged_impl(impl, width, block_size, heads, head_dim)
    # both arms carry one scope name, so that a trace finds the kernel's
    # operations whichever arm ran
    if impl == "xla":
        with jax.named_scope("paged_attention"):
            return xla_paged_attention(
                q, k, v, block_table, base_positions,
                k_scale=k_scale, v_scale=v_scale, out_dtype=out_dtype,
            )
    if not interpret and not on_tpu():
        raise RuntimeError(
            f"paged_attention(impl='pallas') needs a TPU backend, found "
            f"{jax.default_backend()!r}; use impl='auto'/'xla', or interpret=True in tests"
        )
    from unionml_tpu.ops.tuning import pick_paged_heads

    heads_per_step = pick_paged_heads(width, block_size, heads, head_dim)

    def kernel(q, k, v, block_table, base_positions, *scales):
        k_scale, v_scale = scales or (None, None)
        return _paged_forward(
            q, k, v, block_table, base_positions, k_scale, v_scale, out_dtype,
            heads_per_step, interpret,
        )

    operands = [q, k, v, block_table, jnp.asarray(base_positions, jnp.int32).reshape(batch)]
    if k_scale is not None:
        operands += [k_scale, v_scale]
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from unionml_tpu.parallel._compat import shard_map

        by_head = _head_spec(mesh, heads)
        in_specs = (by_head, by_head, by_head, P(), P()) + (by_head,) * (len(operands) - 5)
        # check_vma=False: pallas_call has no replication rule to check against
        kernel = shard_map(
            kernel, mesh=mesh, in_specs=in_specs, out_specs=by_head, check_vma=False
        )
    with jax.named_scope("paged_attention"):
        return kernel(*operands)


def fused_hbm_bytes(
    table_width: int, block_size: int, heads: int, head_dim: int,
    quantized: bool, dense_itemsize: int = 2,
) -> int:
    """Modeled HBM bytes one decode step's KV reads cost the FUSED kernel.

    K + V codes at their stored width (int8 under quantization, else the dense
    dtype) plus the f32 scales — nothing else touches HBM for KV: the kernel
    dequantizes in VMEM and never materializes a gathered copy. This is the
    traffic model ``bench_kernels.py --paged`` gates on (exits nonzero if the
    kernel's modeled bytes exceed exactly this sum).
    """
    kv_positions = 2 * table_width * block_size * heads * head_dim
    codes = kv_positions * (1 if quantized else dense_itemsize)
    scales = 2 * table_width * heads * 4 if quantized else 0
    return codes + scales


def gather_hbm_bytes(
    table_width: int, block_size: int, heads: int, head_dim: int,
    quantized: bool, dense_itemsize: int = 2,
) -> int:
    """Modeled HBM bytes of the XLA gather arm for the same step.

    The gather reads the stored pool (codes + scales), then WRITES the dense
    dequantized copy and READS it back into the attention — the round trip the
    fused kernel deletes. (XLA may fuse part of this on some shapes; the model
    prices the materialization its HLO schedules on the measured serving path.)
    """
    kv_positions = 2 * table_width * block_size * heads * head_dim
    dense_copy = 2 * kv_positions * dense_itemsize  # write + read back
    return fused_hbm_bytes(
        table_width, block_size, heads, head_dim, quantized, dense_itemsize
    ) + dense_copy
