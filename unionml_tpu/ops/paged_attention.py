"""Paged-attention decode: fused Pallas dequant-attend straight off the block pool.

The paged serving path (PRs 11/14) keeps every slot's KV in a shared block pool
— int8 codes plus per-(block, head) scales under ``kv_quantize`` — and the XLA
decode step pays a ``pool[table]`` gather that materializes a dense, dequantized
KV copy before attending (``models/gpt.py`` ``gather_table``). On real HBM that
copy is ~4x the bytes the int8 codes occupy, per step, per layer. The kernel
here deletes it: a grid step DMAs the pool blocks that eight entries of the
slot's block-table row name (scalar-prefetched, so the index feeds the DMA
engine), every local head of each block at once, dequantizes in VMEM, and folds
the 128 keys into an online-softmax accumulation — flash-decoding over the
table indirection. HBM traffic per step is the int8 codes + scales of the
blocks that hold a row's keys; the bf16-pool variant simply skips the dequant.

Two implementations behind one dispatcher (the ``ops/attention.py`` contract):

- ``impl="pallas"``: the fused kernel. Grid ``(batch, head_groups, tiles)``
  with the table walk innermost, ``tiles = ceil(width / tile)``; VMEM scratch
  carries the (m, l, acc) softmax state across a row's tiles, initialized at
  the first and normalized/written at the last. How many heads and table
  entries a grid step takes follows from the shapes of the call
  (:func:`_tiling`): all local heads and ``128 // block_size`` entries at every
  shape the repo runs, so ``head_groups`` is 1 and GPT-2 medium's 65-column
  table is 9 tiles, the last one short.
- ``impl="xla"``: gather-dequant-attend, arithmetic-identical to the historical
  ``gather_table`` + ``xla_attention`` path (the reference the kernel is pinned
  against, and what runs off-TPU).
- ``impl="auto"``: on a TPU backend the verdict of
  :func:`unionml_tpu.ops.tuning.pick_paged_impl` for the shape class (pallas
  unless the table says otherwise), XLA on every other backend. The choice is
  made once, from the backend and the shapes — there is no runtime fallback
  between the arms: ``impl="pallas"`` off a TPU raises unless the caller asked
  for the Pallas interpreter (``interpret=True``, a test argument).

**The walk ends at the row's live length.** A row with base position ``p`` and
``S`` query tokens can see keys in table columns ``0 .. (p + S - 1) //
block_size`` and in no other. The tiles that start past that column are
neither fetched nor computed: their index maps repeat the row's last live
tile, and a block index that repeats skips its DMA; ``pl.when`` skips the
body. What is left of such a grid step is its fixed cost (index maps and DMA
bookkeeping of the step's 16 pool operands, about 1.5 us on a v5e), which a
row pays ``tiles`` times whatever it holds. A retired row carries the engine's
sentinel base ``(width - 1) * block_size``: its live range is the whole table,
so it walks all of its tiles, every entry its scratch block, at the cost of a
full row (about a third more than a short row's).

Why the pool blocks come through BlockSpecs, one operand per table entry of a
tile, and not through ``make_async_copy`` from a pool left in ``pl.ANY``:
Mosaic refuses any slice of an HBM ref whose last dimension is not a multiple
of 128 lanes (``head_dim`` is 64), the whole-block slice included; a BlockSpec
whose last two dims equal the array's is the form it takes.

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
positions exceed every live query position, so no per-row length plumbing is
needed beyond the base positions: the bound above is reckoned from them, and
inside the last live block the positional mask discards the rest.
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


#: VMEM the kernel may hold, handed to Mosaic as the call's limit (a v5e core
#: has 128 MiB; the compiler's own default scope is 16). The tiling below keeps
#: the footprint it can count under half of it; the rest is the compiler's.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_LANES = 128


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _tiling(heads, S, block_size, head_dim, width, pool_itemsize, quantized):
    """``(heads a grid step, table entries a grid step)`` for a call's shapes.

    A grid step takes as many table entries as fill the 128 lanes of the score
    matrix with keys (8 blocks of 16; never more than the table has), and the
    most local heads, a divisor of them, whose footprint stays under half of
    :data:`_VMEM_LIMIT_BYTES`: every entry's K and V block double-buffered as
    VMEM pads it, the scales' padded tiles, the tile's dequantized copy, the
    f32 scores and weights, and the query, output and softmax state over ``S``.
    Decode, verify and the 64-token chunks the repo runs take all 16 heads of
    GPT-2 medium; a chunk of hundreds of tokens splits them (512 tokens: 4).
    """
    tile = max(1, min(width, _LANES // block_size))
    keys = _round_up(tile * block_size, _LANES)
    lanes = _round_up(head_dim, _LANES)
    # a (block_size, head_dim) slab of the pool in VMEM: sublanes pad to 32 bytes' worth
    slab = _round_up(block_size, 32 // pool_itemsize) * lanes * pool_itemsize
    rows = _round_up(S, 8)
    per_head = (
        2 * 2 * tile * slab  # K and V blocks, double-buffered
        + (2 * 2 * tile * 8 * _LANES * 4 if quantized else 0)  # scale tiles
        + 2 * tile * block_size * lanes * 4 * (2 if quantized else 1)  # the tile's working copies
        + 4 * rows * keys * 4  # scores, mask, weights and their cast
        + 8 * rows * lanes * 4  # q and o blocks (double-buffered), acc, m, l
    )
    for gh in range(heads, 0, -1):
        if heads % gh == 0 and gh * per_head <= _VMEM_LIMIT_BYTES // 2:
            return gh, tile
    return 1, tile


def _paged_kernel(
    table_ref,  # scalar prefetch: (batch, width) int32
    base_ref,  # scalar prefetch: (batch,) int32 query base positions
    q_ref,  # (1, gh, S, hd)
    *rest,  # tile K blocks, tile V blocks, [tile K scales, tile V scales], o_ref, scratch
    tile: int,
    block_size: int,
    sm_scale: float,
    quantized: bool,
    out_dtype,
):
    """One (batch row, head group, table tile) program of the online softmax.

    The scalar-prefetched table row already steered this tile's DMAs (see the
    index maps in :func:`_paged_forward`): ``tile`` consecutive table entries,
    each one pool block with all ``gh`` heads, (1, gh, bs, hd). The body joins
    them into one (gh, tile * bs, hd) K and V, so the scores fill the lanes,
    and folds them into the (acc, m, l) scratch — the flash-attention
    recurrence of ``attention._flash_kernel``, walked over the table instead of
    a dense KV. It runs only for tiles that hold a key some query of the row
    may see (``tile start <= base + S - 1``); for the tiles past that the index
    maps repeat the row's last live tile, so nothing is fetched either.

    Dequant mirrors the XLA gather arm bit for bit on VALUES:
    ``(codes.astype(f32) * scale).astype(out_dtype)`` — the cast to the compute
    dtype is the same value quantization ``gather_table`` applied, so both arms
    attend over identical K/V elements and differ only in summation order.
    """
    k_refs, v_refs, rest = rest[:tile], rest[tile:2 * tile], rest[2 * tile:]
    k_scale_refs = v_scale_refs = (None,) * tile
    if quantized:
        k_scale_refs, v_scale_refs, rest = rest[:tile], rest[tile:2 * tile], rest[2 * tile:]
    o_ref, acc_ref, m_ref, l_ref = rest

    b, w = pl.program_id(0), pl.program_id(2)
    S = q_ref.shape[2]
    tile_keys = tile * block_size
    # the table's last key: a short last tile repeats its last entry past it
    end = table_ref.shape[1] * block_size - 1
    last = jnp.minimum(base_ref[b] + (S - 1), end)  # the last key any query of the row sees

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(w * tile_keys <= last)
    def _fold():
        def joined(refs, scale_refs):
            blocks = []
            for ref, scale_ref in zip(refs, scale_refs):
                block = ref[0]  # (gh, bs, hd)
                if scale_ref is not None:
                    # per-(block, head) scales, (gh, 1, 1) by the block spec:
                    # they broadcast over the block's (bs, hd) slab
                    block = (block.astype(jnp.float32) * scale_ref[0]).astype(out_dtype)
                blocks.append(block)
            return jnp.concatenate(blocks, axis=1)

        q = q_ref[0]  # (gh, S, hd)
        k = joined(k_refs, k_scale_refs)  # (gh, tile_keys, hd)
        v = joined(v_refs, v_scale_refs)
        operand = jnp.promote_types(q.dtype, k.dtype)
        scores = jax.lax.dot_general(
            q.astype(operand), k.astype(operand), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (gh, S, tile_keys)
        k_pos = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        q_pos = base_ref[b] + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = k_pos <= jnp.minimum(q_pos, end)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]  # (gh, S, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # a query with no key yet (an empty live range inside a chunk) must add
        # exactly 0: m_new is still _NEG_INF there and exp(0) would be 1
        probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        # what the tile holds past the row's last key (the rest of its last
        # block, a repeated entry) has weight 0, and 0 x NaN would still be NaN
        seen = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(seen <= last, v, jnp.zeros_like(v))
        pv = jax.lax.dot_general(
            probs.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (gh, S, hd)
        acc_ref[...] = acc_ref[...] * correction + pv
        l_ref[...] = l_prev * correction + jnp.sum(probs, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(w == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_forward(
    q, k, v, block_table, base_positions, k_scale, v_scale, out_dtype, interpret,
):
    batch, heads, S, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    quantized = k_scale is not None
    gh, tile = _tiling(heads, S, block_size, head_dim, width, k.dtype.itemsize, quantized)

    kernel = functools.partial(
        _paged_kernel,
        tile=tile,
        block_size=block_size,
        sm_scale=1.0 / np.sqrt(head_dim),
        quantized=quantized,
        out_dtype=out_dtype,
    )

    def entry(t):
        """Index map of a tile's ``t``-th table entry: (b, h, w, table, base) to
        the pool block to DMA — this indirection IS the kernel's reason to exist
        (no gathered copy). Past the row's last live column the walk stands
        still: the entry repeats, and a repeated block index skips its DMA."""

        def index(b, h, w, table, base):
            live = jnp.clip(base[b] + (S - 1), 0, width * block_size - 1) // block_size
            column = jnp.minimum(jnp.minimum(w, live // tile) * tile + t, live)
            return table[b, column], h, 0, 0

        return index

    def by_row(b, h, w, table, base):
        return b, h, 0, 0

    pool_specs = [pl.BlockSpec((1, gh, block_size, head_dim), entry(t)) for t in range(tile)]
    in_specs = [pl.BlockSpec((1, gh, S, head_dim), by_row)] + pool_specs * 2
    operands = [q] + [k] * tile + [v] * tile
    if quantized:
        # the scales keep the pool's own rank-4 (blocks, heads, 1, 1) layout: a
        # (1, gh, 1, 1) block's last two dims equal the array's, which is the
        # one sub-(8, 128) block shape the Mosaic lowering accepts (a (1, gh)
        # block of a (blocks, heads) view is refused)
        in_specs += [pl.BlockSpec((1, gh, 1, 1), entry(t)) for t in range(tile)] * 2
        operands += [k_scale] * tile + [v_scale] * tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, heads // gh, -(-width // tile)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gh, S, head_dim), by_row),
        scratch_shapes=[
            pltpu.VMEM((gh, S, head_dim), jnp.float32),
            pltpu.VMEM((gh, S, 1), jnp.float32),
            pltpu.VMEM((gh, S, 1), jnp.float32),
        ],
    )
    codes_bytes = 2 * width * heads * block_size * head_dim * k.dtype.itemsize
    scale_bytes = 2 * width * heads * 4 if quantized else 0
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, S, head_dim), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        # a full table: what rows of the greatest length cost
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
        to pool block; unmapped tail columns point at the scratch block. The
        kernel reads a row's columns up to ``(base + S - 1) // block_size``
        and no further.
    :param base_positions: ``(batch,)`` int32; query token ``s`` of row ``b``
        sits at logical position ``base_positions[b] + s`` and attends key
        positions ``<= base + s``. Retired rows carry the sentinel position —
        their output is garbage the engine never samples (and costs the walk
        of a full row). A query with no key to see (negative position) gets 0
        from the kernel.
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
    def kernel(q, k, v, block_table, base_positions, *scales):
        k_scale, v_scale = scales or (None, None)
        return _paged_forward(
            q, k, v, block_table, base_positions, k_scale, v_scale, out_dtype, interpret,
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
