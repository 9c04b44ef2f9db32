"""Paged-attention decode: fused Pallas dequant-attend straight off the block pool.

The paged serving path (PRs 11/14) keeps every slot's KV in a shared block pool
— one leaf a layer whose rows hold a head's key beside its value, or int8 codes
plus per-(block, head) scales under ``kv_quantize`` — and the XLA decode step
pays a ``pool[table]`` gather that materializes a dense, dequantized KV copy
before attending (``models/gpt.py`` ``gather_table``). On real HBM that copy is
~4x the bytes the int8 codes occupy, per step, per layer. The kernel here
deletes it: a grid step DMAs the pool blocks that eight entries of the slot's
block-table row name (scalar-prefetched, so the index feeds the DMA engine),
every local head of each block at once, dequantizes in VMEM, and folds the 128
keys into an online-softmax accumulation — flash-decoding over the table
indirection. HBM traffic per step is the rows (or the int8 codes + scales) of
the blocks that hold a row's keys; the full-precision variant simply skips the
dequant.

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
bookkeeping of the step's pool operands: 8 over the joined leaf, 16 over an
int8 pool's two code leaves and 16 more for their scales), which a row pays
``tiles`` times whatever it holds. A retired row carries the engine's
sentinel base ``(width - 1) * block_size``: its live range is the whole table,
so it walks all of its tiles, every entry its scratch block, at the cost of a
full row (about a third more than a short row's).

Why the pool blocks come through BlockSpecs, one operand per table entry of a
tile, and not through ``make_async_copy`` from a pool left in ``pl.ANY``:
Mosaic refuses any slice of an HBM ref whose last dimension is not a multiple
of 128 lanes (an int8 pool's ``head_dim`` of 64), the whole-block slice
included; a BlockSpec whose last two dims equal the array's is the form it
takes. (The joined leaf and the latent leaf are whole lanes wide, so copies by
hand are open to them: a kernel PR of its own.)

One body serves every cache layout. The pool's key heads may be fewer than the
query heads (a step's K tile is fetched once for all the query heads that
share it: they ride as rows of one matrix product), one leaf may serve as keys
and values (``v=None``, a tile is one DMA, not two: absorbed latent attention,
one key row a token whose leading columns are its values; or per-head rows of
``[key | value]``, which the zero-padded query scores whole and whose value
columns the caller of the kernel keeps), and the last dimension is whatever
the call's leaves have (a multiple of 128 keeps XLA from re-laying the pool out
around the call).

Under a device mesh the kernel runs inside ``shard_map`` with the pool's heads
local to each ``tensor`` shard (``mesh=``): a Mosaic custom call is opaque to
the SPMD partitioner, which refuses it ("Mosaic kernels cannot be
automatically partitioned") wherever a multi-device ``jit`` meets it bare.

Layout contract (matches ``init_block_pool``): a full-precision layer is one
leaf ``(num_blocks, heads, block_size, 2 * head_dim)``, a token's key of one
head in a row's leading ``head_dim`` columns and its value in the rest; an int8
layer is two code leaves ``(num_blocks, heads, block_size, head_dim)`` and
their scales ``(num_blocks, heads, 1, 1)`` f32; a latent layer is one leaf
``(num_blocks, 1, block_size, row)``; ``block_table`` is ``(batch, width)``
int32; a query token at logical position ``p`` attends keys at logical
positions ``k <= p``, where logical column ``c = w * block_size + o`` lives in
pool block ``table[row, w]``. Table columns past a row's live range point at
the engine's scratch block — their positions exceed every live query position,
so no per-row length plumbing is needed beyond the base positions: the bound
above is reckoned from them, and inside the last live block the positional mask
discards the rest.
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
    v: Optional[jax.Array],
    block_table: jax.Array,
    base_positions: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    out_dtype=None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Reference paged attention: gather the table, dequantize, attend dense.

    Arithmetic-identical to the historical in-model path: ``pool[table]``
    gather, ``(codes.astype(f32) * scale).astype(out_dtype)`` dequant,
    block-structure flatten, then :func:`xla_attention` under the positional
    mask ``k_pos <= base + s``. This is the exactness reference the kernel's
    parity gates pin against, and the off-TPU arm of the dispatcher. Fewer key
    heads than query heads, and ``v=None`` (one leaf: its rows are the values
    too, or, where they are wider than the query, ``[key | value]``, which the
    gathered rows are split into), read as in :func:`paged_attention`.
    """
    batch, heads, S, head_dim = q.shape
    kv_heads, block_size = k.shape[1], k.shape[2]
    group = heads // kv_heads
    width = block_table.shape[1]
    capacity = width * block_size
    out_dtype = q.dtype if out_dtype is None else out_dtype

    def gather(pool_leaf, scale_leaf):
        blocks = pool_leaf[block_table]  # (batch, width, kv_heads, bs, hd)
        if scale_leaf is not None:
            blocks = (blocks.astype(jnp.float32) * scale_leaf[block_table]).astype(out_dtype)
        return jnp.moveaxis(blocks, 2, 1).reshape(batch, kv_heads, capacity, pool_leaf.shape[-1])

    k_pos = jnp.arange(capacity)
    q_pos = base_positions.astype(jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, :, :]
    keys = gather(k, k_scale)
    if v is not None:
        values = gather(v, v_scale)
    elif keys.shape[-1] > head_dim:
        keys, values = keys[..., :head_dim], keys[..., head_dim:]
    else:
        values = keys
    if group > 1:
        # a key head's query heads side by side as rows (head-major), one mask each
        q = q.reshape(batch, kv_heads, group * S, head_dim)
        mask = jnp.tile(mask, (1, 1, group, 1))
    out = xla_attention(q, keys, values, mask=mask, sm_scale=sm_scale)
    return out.reshape(batch, heads, S, values.shape[-1])


#: VMEM the kernel may hold, handed to Mosaic as the call's limit (a v5e core
#: has 128 MiB; the compiler's own default scope is 16). The tiling below keeps
#: the footprint it can count under half of it; the rest is the compiler's.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_LANES = 128


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _tiling(heads, rows, q_len, block_size, head_dim, width, pool_itemsize, quantized):
    """``(key heads, query rows, table entries)`` a grid step takes, for a call's shapes.

    ``heads`` are the pool's (key) heads and ``rows`` the query rows that meet
    one of them: its query heads side by side, ``q_len`` tokens each (plain
    multi-head attention: ``rows == q_len``). A grid step takes as many table
    entries as fill the 128 lanes of the score matrix with keys (8 blocks of
    16; never more than the table has), and the most heads, a divisor of them,
    whose footprint stays under half of :data:`_VMEM_LIMIT_BYTES`: every
    entry's K and V block double-buffered as VMEM pads it, the scales' padded
    tiles, the tile's dequantized copy, the f32 scores and weights, and the
    query, output and softmax state over the rows. Decode, verify and the
    64-token chunks the repo runs take all 16 heads of GPT-2 medium; a chunk of
    hundreds of tokens splits them (512 tokens: 4). Where one head's rows alone
    are too many (32 query heads of a 1024-token chunk over one latent key
    head), the rows split too, into blocks that hold whole query spans or
    divide one, so that a block's positions are a range.
    """
    tile = max(1, min(width, _LANES // block_size))
    keys = _round_up(tile * block_size, _LANES)
    lanes = _round_up(head_dim, _LANES)
    # a (block_size, head_dim) slab of the pool in VMEM: sublanes pad to 32 bytes' worth
    slab = _round_up(block_size, 32 // pool_itemsize) * lanes * pool_itemsize

    def per_head(block_rows):
        padded = _round_up(block_rows, 8)
        return (
            2 * 2 * tile * slab  # K and V blocks, double-buffered
            + (2 * 2 * tile * 8 * _LANES * 4 if quantized else 0)  # scale tiles
            + 2 * tile * block_size * lanes * 4 * (2 if quantized else 1)  # the tile's working copies
            + 4 * padded * keys * 4  # scores, mask, weights and their cast
            + 8 * padded * lanes * 4  # q and o blocks (double-buffered), acc, m, l
        )

    budget = _VMEM_LIMIT_BYTES // 2
    for gh in range(heads, 0, -1):
        if heads % gh == 0 and gh * per_head(rows) <= budget:
            return gh, rows, tile
    # one head a step, and of its rows a block: whole spans, or a divisor of one
    spans = rows // q_len
    blocks = [q_len * n for n in range(spans, 0, -1) if spans % n == 0]
    blocks += [q_len // n for n in range(2, q_len + 1) if q_len % n == 0 and (q_len // n) % 8 == 0]
    for block_rows in blocks:
        if per_head(block_rows) <= budget:
            return 1, block_rows, tile
    return 1, blocks[-1], tile


def _paged_kernel(
    table_ref,  # scalar prefetch: (batch, width) int32
    base_ref,  # scalar prefetch: (batch,) int32 query base positions
    q_ref,  # (1, gh, rows, hd)
    *rest,  # tile K blocks, [tile V blocks], [tile K scales, tile V scales], o_ref, scratch
    tile: int,
    block_size: int,
    sm_scale: float,
    quantized: bool,
    shared_kv: bool,
    q_len: int,
    row_blocks: int,
    out_dtype,
):
    """One (batch row, head group and row block, table tile) program of the
    online softmax.

    The scalar-prefetched table row already steered this tile's DMAs (see the
    index maps in :func:`_paged_forward`): ``tile`` consecutive table entries,
    each one pool block with all ``gh`` key heads, (1, gh, bs, hd). The body
    joins them into one (gh, tile * bs, hd) K and V, so the scores fill the
    lanes, and folds them into the (acc, m, l) scratch — the flash-attention
    recurrence of ``attention._flash_kernel``, walked over the table instead of
    a dense KV. It runs only for tiles that hold a key some query of the block
    may see (``tile start <= base + the block's last offset``); for the tiles
    past that the index maps repeat the last live tile, so nothing is fetched
    either. With ``shared_kv`` the K tile is the V tile too (one DMA).

    The block's ``rows`` query rows are the query heads that share a key head,
    ``q_len`` tokens each, head-major: row ``r`` of the head's rows sits at
    position ``base + r % q_len``. A block holds whole spans of ``q_len``, or a
    divisor of one (:func:`_tiling`).

    Dequant mirrors the XLA gather arm bit for bit on VALUES:
    ``(codes.astype(f32) * scale).astype(out_dtype)`` — the cast to the compute
    dtype is the same value quantization ``gather_table`` applied, so both arms
    attend over identical K/V elements and differ only in summation order.
    """
    k_refs, rest = rest[:tile], rest[tile:]
    v_refs = k_refs
    if not shared_kv:
        v_refs, rest = rest[:tile], rest[tile:]
    k_scale_refs = v_scale_refs = (None,) * tile
    if quantized:
        k_scale_refs, v_scale_refs, rest = rest[:tile], rest[tile:2 * tile], rest[2 * tile:]
    o_ref, acc_ref, m_ref, l_ref = rest

    b, w = pl.program_id(0), pl.program_id(2)
    rows = q_ref.shape[2]
    tile_keys = tile * block_size
    # the table's last key: a short last tile repeats its last entry past it
    end = table_ref.shape[1] * block_size - 1
    first, span = _block_span(pl.program_id(1), rows, q_len, row_blocks)
    last = jnp.minimum(base_ref[b] + first + (span - 1), end)  # the last key any query of the block sees

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

        q = q_ref[0]  # (gh, rows, hd)
        k = joined(k_refs, k_scale_refs)  # (gh, tile_keys, hd)
        v = k if shared_kv else joined(v_refs, v_scale_refs)
        operand = jnp.promote_types(q.dtype, k.dtype)
        scores = jax.lax.dot_general(
            q.astype(operand), k.astype(operand), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (gh, rows, tile_keys)
        k_pos = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        offset = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if rows > q_len:  # several query heads' spans in one block
            offset = offset % q_len
        q_pos = base_ref[b] + first + offset
        valid = k_pos <= jnp.minimum(q_pos, end)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]  # (gh, rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # a query with no key yet (an empty live range inside a chunk) must add
        # exactly 0: m_new is still _NEG_INF there and exp(0) would be 1
        probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        # what the tile holds past the block's last key (the rest of its last
        # block, a repeated entry) has weight 0, and 0 x NaN would still be NaN
        seen = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(seen <= last, v, jnp.zeros_like(v))
        pv = jax.lax.dot_general(
            probs.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (gh, rows, hd)
        acc_ref[...] = acc_ref[...] * correction + pv
        l_ref[...] = l_prev * correction + jnp.sum(probs, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(w == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _block_span(h, rows, q_len, row_blocks):
    """``(first offset, offsets spanned)`` of the query positions, relative to
    the row's base, in the row block that grid index ``h`` of axis 1 names: a
    block of whole query spans starts at 0 and spans ``q_len``; a divisor of a
    span is the range ``[first, first + rows)``."""
    if rows >= q_len:
        return 0, q_len
    return ((h % row_blocks) * rows) % q_len, rows


def _paged_forward(
    q, k, v, block_table, base_positions, k_scale, v_scale, out_dtype, sm_scale, q_len, interpret,
):
    """``q`` is ``(batch, key heads, rows, head_dim)``: each key head's query
    heads side by side, ``q_len`` tokens each (see :func:`paged_attention`)."""
    batch, heads, rows_all, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    quantized = k_scale is not None
    shared_kv = v is None
    out_dim = head_dim if shared_kv else v.shape[-1]
    gh, rows, tile = _tiling(
        heads, rows_all, q_len, block_size, head_dim, width, k.dtype.itemsize, quantized
    )
    row_blocks = rows_all // rows

    kernel = functools.partial(
        _paged_kernel,
        tile=tile,
        block_size=block_size,
        sm_scale=sm_scale,
        quantized=quantized,
        shared_kv=shared_kv,
        q_len=q_len,
        row_blocks=row_blocks,
        out_dtype=out_dtype,
    )

    def entry(t):
        """Index map of a tile's ``t``-th table entry: (b, h, w, table, base) to
        the pool block to DMA — this indirection IS the kernel's reason to exist
        (no gathered copy). Past the block's last live column the walk stands
        still: the entry repeats, and a repeated block index skips its DMA."""

        def index(b, h, w, table, base):
            first, span = _block_span(h, rows, q_len, row_blocks)
            live = jnp.clip(base[b] + first + (span - 1), 0, width * block_size - 1) // block_size
            column = jnp.minimum(jnp.minimum(w, live // tile) * tile + t, live)
            return table[b, column], h // row_blocks, 0, 0

        return index

    def by_row(b, h, w, table, base):
        return b, h // row_blocks, h % row_blocks, 0

    pool_specs = [pl.BlockSpec((1, gh, block_size, head_dim), entry(t)) for t in range(tile)]
    in_specs = [pl.BlockSpec((1, gh, rows, head_dim), by_row)] + pool_specs
    operands = [q] + [k] * tile
    if not shared_kv:
        in_specs += [pl.BlockSpec((1, gh, block_size, out_dim), entry(t)) for t in range(tile)]
        operands += [v] * tile
    if quantized:
        # the scales keep the pool's own rank-4 (blocks, heads, 1, 1) layout: a
        # (1, gh, 1, 1) block's last two dims equal the array's, which is the
        # one sub-(8, 128) block shape the Mosaic lowering accepts (a (1, gh)
        # block of a (blocks, heads) view is refused)
        in_specs += [pl.BlockSpec((1, gh, 1, 1), entry(t)) for t in range(tile)] * 2
        operands += [k_scale] * tile + [v_scale] * tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, heads // gh * row_blocks, -(-width // tile)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gh, rows, out_dim), by_row),
        scratch_shapes=[
            pltpu.VMEM((gh, rows, out_dim), jnp.float32),
            pltpu.VMEM((gh, rows, 1), jnp.float32),
            pltpu.VMEM((gh, rows, 1), jnp.float32),
        ],
    )
    codes_bytes = width * heads * block_size * (head_dim + (0 if shared_kv else out_dim)) * k.dtype.itemsize
    scale_bytes = 2 * width * heads * 4 if quantized else 0
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, rows_all, out_dim), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        # a full table: what rows of the greatest length cost
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * rows_all * width * block_size * (head_dim + out_dim),
            bytes_accessed=batch * (q.size // batch * 2 * q.dtype.itemsize + codes_bytes + scale_bytes),
            transcendentals=batch * heads * rows_all * width * block_size,
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
    """Spec of a rank-4 kernel operand with ``heads`` heads under ``mesh``:
    heads on ``tensor`` when the axis divides them — the engine's pool layout
    (:func:`unionml_tpu.models.gpt.kv_block_spec`) — else replicated."""
    from jax.sharding import PartitionSpec as P

    from unionml_tpu.parallel.mesh import TENSOR_AXIS

    size = int(mesh.shape.get(TENSOR_AXIS, 1))
    return P(None, TENSOR_AXIS, None, None) if size > 1 and heads % size == 0 else P()


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: Optional[jax.Array],
    block_table: jax.Array,
    base_positions: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    out_dtype=None,
    impl: str = "auto",
    interpret: bool = False,
    mesh=None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Attend ``q`` over a row's paged KV through its block-table row.

    :param q: ``(batch, heads, S, head_dim)`` queries (``S == 1`` decode; the
        batch-1 chunk-prefill path passes the whole chunk).
    :param k / v: pool leaves ``(num_blocks, key_heads, block_size, dim)`` —
        int8 codes when ``k_scale``/``v_scale`` ride along, else the compute
        dtype. ``key_heads`` divides ``heads``: consecutive query heads share a
        key head (one DMA a tile for all of them; 1 for a latent cache). The
        key leaf's ``dim`` is ``head_dim`` and the value leaf's may differ.
        ``v=None``: one leaf. Where its rows are as wide as the query, the keys
        are the values too (absorbed latent attention, whose values are the
        leading columns of the key row: the caller slices the ``(batch, heads,
        S, head_dim)`` output). Where they are wider, a row is ``[key |
        value]``, the key in its leading ``head_dim`` columns
        (:func:`unionml_tpu.models.gpt.init_block_pool`'s joined leaf), and the
        output is ``(batch, heads, S, dim - head_dim)``: the kernel scores the
        zero-padded query against whole rows (the value columns meet zeros: the
        same sum), accumulates ``probs @ rows`` and keeps the value columns; a
        tile is still one DMA. (The speculative-verify
        path passes its gathered local state reshaped to this layout with an
        identity table; codes may then be f32 holding exact integers — the
        dequant arithmetic is dtype-agnostic.)
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
    :param sm_scale: what the scores are multiplied by; ``head_dim ** -0.5``
        when not given.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if v is None and k_scale is not None:
        raise ValueError("keys that are the values too (v=None) take no int8 scales")
    out_dtype = q.dtype if out_dtype is None else out_dtype
    batch, heads, S, head_dim = q.shape
    key_heads, block_size, row = k.shape[1], k.shape[2], k.shape[3]
    if heads % key_heads:
        raise ValueError(f"{heads} query heads do not divide over {key_heads} key heads")
    width = block_table.shape[1]
    impl = resolve_paged_impl(impl, width, block_size, heads, row)
    # both arms carry one scope name, so that a trace finds the kernel's
    # operations whichever arm ran
    if impl == "xla":
        with jax.named_scope("paged_attention"):
            return xla_paged_attention(
                q, k, v, block_table, base_positions,
                k_scale=k_scale, v_scale=v_scale, out_dtype=out_dtype, sm_scale=sm_scale,
            )
    if not interpret and not on_tpu():
        raise RuntimeError(
            f"paged_attention(impl='pallas') needs a TPU backend, found "
            f"{jax.default_backend()!r}; use impl='auto'/'xla', or interpret=True in tests"
        )
    scale = 1.0 / np.sqrt(head_dim) if sm_scale is None else sm_scale
    shared_kv = v is None
    joined = shared_kv and row > head_dim  # rows of [key | value]
    if joined:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, row - head_dim),))

    def kernel(q, k, *rest):
        # a key head's query heads side by side as rows, head-major: free of
        # any copy (the heads axis splits, its inner part merges with S)
        local_heads, local_keys = q.shape[1], k.shape[1]
        rest = list(rest)
        v = None if shared_kv else rest.pop(0)
        block_table, base_positions, *scales = rest
        k_scale, v_scale = scales or (None, None)
        out = _paged_forward(
            q.reshape(batch, local_keys, local_heads // local_keys * S, q.shape[-1]),
            k, v, block_table, base_positions, k_scale, v_scale, out_dtype, scale, S, interpret,
        )
        return out.reshape(batch, local_heads, S, out.shape[-1])

    operands = [q, k] + ([] if shared_kv else [v])
    operands += [block_table, jnp.asarray(base_positions, jnp.int32).reshape(batch)]
    if k_scale is not None:
        operands += [k_scale, v_scale]
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from unionml_tpu.parallel._compat import shard_map

        # query heads follow their key heads onto the shards; one key head for
        # all of them (a latent cache) is every shard's, whole
        by_key = _head_spec(mesh, key_heads)
        by_head = _head_spec(mesh, heads) if key_heads == 1 or by_key != P() else P()
        pool = (by_key,) * (1 if shared_kv else 2)
        in_specs = (by_head,) + pool + (P(), P()) + (by_key,) * (2 if k_scale is not None else 0)
        # check_vma=False: pallas_call has no replication rule to check against
        kernel = shard_map(
            kernel, mesh=mesh, in_specs=in_specs, out_specs=by_head, check_vma=False
        )
    with jax.named_scope("paged_attention"):
        out = kernel(*operands)
    return out[..., head_dim:] if joined else out


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
