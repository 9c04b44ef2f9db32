"""Paged-attention decode: fused Pallas dequant-attend straight off the block pool.

The paged serving path (PRs 11/14) keeps every slot's KV in a shared block pool
— one leaf a layer whose rows hold a head's key beside its value, or int8 codes
plus per-(block, head) scales under ``kv_quantize`` — and the XLA decode step
pays a ``pool[table]`` gather that materializes a dense, dequantized KV copy
before attending (``models/gpt.py`` ``gather_table``). On real HBM that copy is
~4x the bytes the int8 codes occupy, per step, per layer. The kernel here
deletes it: a step DMAs the pool blocks that eight entries of the slot's
block-table row name (scalar-prefetched, so the index feeds the DMA engine),
every local head of each block at once, dequantizes in VMEM, and folds the 128
keys into an online-softmax accumulation — flash-decoding over the table
indirection. HBM traffic per step is the rows (or the int8 codes + scales) of
the blocks that hold a row's keys; the full-precision variant simply skips the
dequant.

Two implementations behind one dispatcher (the ``ops/attention.py`` contract):

- ``impl="pallas"``: the fused kernel. Its unit of work is a segment (a batch
  row, a group of key heads, a block of the query rows that meet them) and, of
  the segment, a tile: consecutive table entries whose blocks are joined into
  one K and V so that the scores fill the lanes. VMEM scratch carries the
  (m, l, acc) softmax state across a segment's tiles, initialized before the
  first and normalized and written after the last. How many heads, rows and
  table entries a step takes follows from the shapes of the call
  (:func:`_tiling`): all local heads at every shape the repo runs, 8 of GPT-2
  medium's 16-token blocks, 3 of the latent leaf's 128-token blocks.
- ``impl="xla"``: gather-dequant-attend, arithmetic-identical to the historical
  ``gather_table`` + ``xla_attention`` path (the reference the kernel is pinned
  against, and what runs off-TPU).
- ``impl="auto"``: on a TPU backend the verdict of
  :func:`unionml_tpu.ops.tuning.pick_paged_impl` for the shape class (pallas
  unless the table says otherwise), XLA on every other backend. The choice is
  made once, from the backend and the shapes — there is no runtime fallback
  between the arms: ``impl="pallas"`` off a TPU raises unless the caller asked
  for the Pallas interpreter (``interpret=True``, a test argument).

**The walk is the live tiles and nothing else.** A row with base position ``p``
and ``S`` query tokens can see keys in table columns ``0 .. (p + S - 1) //
block_size`` and in no other: the walk of a segment is the tiles that hold
those columns (:func:`_live_column`), at least one. Over a pool whose leaves
are whole lanes wide (the joined leaf, the latent leaf) the kernel fetches them
itself (:func:`_walk_kernel`): the grid is ``(batch, segments)``, the pool
stays in HBM (``pl.ANY``), and a loop over the segment's live tiles copies
each tile's blocks into one of two VMEM slots (``make_async_copy``, the block
index read from the scalar-prefetched table) while the tile before it is
folded; after a segment's last tile the copy in flight is the first tile of
the next segment, so that a grid step starts on keys that are already there. Of
the last tile only the entries up to the last live column are copied. A table
column past a row's keys therefore costs nothing: no grid step, no index map,
no descriptor. (Until PR 31 the grid had a step for every tile of the table,
``batch x ceil(width / tile)``, and six in seven of them were past the rows'
keys: fetched nothing, computed nothing and still paid a grid step's fixed
cost, 0.83 us with eight pool operands. ``PERF.md`` section 6, PR 31.)
:func:`walk_steps` counts the steps a call takes, on the host. A retired row
carries the engine's sentinel base ``(width - 1) * block_size``: its live
range is the whole table, so it walks all of its tiles, every entry its
scratch block: the cost of a full row.

**Why not a one-dimensional grid over a list of live tiles** (a dynamic grid
extent with scalar prefetch, as ``megablox.gmm`` has): jax 0.9.0 hands a
dynamic extent to the Mosaic call in front of every other operand, and the
benchmark's trace readers find the decode kernel by its first operand, the
block table (``custom-call(s32[32,65]``). So the extent of the walk is a loop
bound inside a grid step, not a grid bound, and the table stays first.

**The leaves Mosaic will not slice keep BlockSpecs.** Mosaic refuses any slice
of an HBM ref whose last dimension is not a multiple of 128 lanes (an int8
pool's ``head_dim`` of 64, its ``(blocks, heads, 1, 1)`` scales), the
whole-block slice included; a BlockSpec whose last two dims equal the array's
is the form it takes. Those pools (:func:`_copied` says which) run the grid
``(batch, segments, ceil(width / tile))`` with one operand per table entry of a
tile (:func:`_paged_kernel`): past the row's last live tile the index maps
repeat it, a block index that repeats skips its DMA and ``pl.when`` skips the
body, but the grid step's fixed cost (index maps and DMA bookkeeping of 16 code
operands and 16 scales) is paid ``tiles`` times a row whatever it holds. That
walk ends when the int8 pool is joined into 128 lanes (``ROADMAP.md`` S14). The
fold (:func:`_fold_tile`) is one function for both walks.

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
    window: Optional[int] = None,
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
    mask = k_pos[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask = mask & (k_pos[None, None, :] > q_pos[:, :, None] - window)
    mask = mask[:, None, :, :]
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


#: bytes of pool a step of the copied walk fetches and folds, all heads of the
#: step and every pool leaf together: enough that a step's fixed cost (the loop's
#: scalar work, a descriptor an entry, the fold's matrix set-up) is small beside
#: its HBM time, and two of them (the walk double-buffers) are a small part of
#: the VMEM budget. See ``PERF.md`` section 6, PR 31, for the tiles it was set
#: from.
_TILE_BYTES = 512 * 1024


def _copied(*leaves) -> bool:
    """Whether the walk may fetch these pool leaves with copies of its own
    (:func:`_walk_kernel`): every leaf whole lanes wide, which leaves out an
    int8 pool's scales and its 64-wide codes (Mosaic slices no HBM ref whose
    last dimension is not a multiple of 128). Those take the BlockSpec walk
    (:func:`_paged_kernel`)."""
    return all(leaf.shape[-1] % _LANES == 0 for leaf in leaves if leaf is not None)


def _tiling(heads, rows, q_len, block_size, head_dim, width, pool_itemsize, quantized, copied=False):
    """``(key heads, query rows, table entries)`` a step takes, for a call's shapes.

    ``heads`` are the pool's (key) heads and ``rows`` the query rows that meet
    one of them: its query heads side by side, ``q_len`` tokens each (plain
    multi-head attention: ``rows == q_len``). A step takes the most heads, a
    divisor of them, whose footprint stays under half of
    :data:`_VMEM_LIMIT_BYTES`: every entry's K and V block double-buffered as
    VMEM pads it, the scales' padded tiles, the tile's dequantized copy, the
    f32 scores and weights, and the query, output and softmax state over the
    rows. Decode, verify and the 64-token chunks the repo runs take all 16
    heads of GPT-2 medium; a chunk of hundreds of tokens splits them (512
    tokens: 4). Where one head's rows alone are too many (32 query heads of a
    1024-token chunk over one latent key head), the rows split too, into blocks
    that hold whole query spans or divide one, so that a block's positions are
    a range.

    The table entries of a step are as many as fill the 128 lanes of the score
    matrix with keys (8 blocks of 16; never more than the table has). The
    BlockSpec walk (``copied`` false) stops there, because it pays for every
    entry as an operand of every grid step. The copied walk pays for an entry
    only where it fetches one, so where the whole query fits beside it (decode
    and verify: a step's time is its fetch and its fixed cost, not its
    products) it takes :data:`_TILE_BYTES` of pool a step in whole 128-key
    groups: still 8 of GPT-2 medium's 64 KB blocks, and 3 of the latent leaf's
    160 KB blocks where the lanes alone gave 1.
    """
    lanes = _round_up(head_dim, _LANES)
    # a (block_size, head_dim) slab of the pool in VMEM: sublanes pad to 32 bytes' worth
    slab = _round_up(block_size, 32 // pool_itemsize) * lanes * pool_itemsize
    fill = max(1, _LANES // block_size)

    def footprint(block_rows, tile):
        keys = _round_up(tile * block_size, _LANES)
        padded = _round_up(block_rows, 8)
        return (
            2 * 2 * tile * slab  # K and V blocks, double-buffered
            + (2 * 2 * tile * 8 * _LANES * 4 if quantized else 0)  # scale tiles
            + 2 * tile * block_size * lanes * 4 * (2 if quantized else 1)  # the tile's working copies
            + 4 * padded * keys * 4  # scores, mask, weights and their cast
            + 8 * padded * lanes * 4  # q and o blocks (double-buffered), acc, m, l
        )

    budget = _VMEM_LIMIT_BYTES // 2
    tile = max(1, min(width, fill))
    if copied:
        by_bytes = min(width, _TILE_BYTES // (heads * slab) // fill * fill)
        if by_bytes > tile and heads * footprint(rows, by_bytes) <= budget:
            return heads, rows, by_bytes
    for gh in range(heads, 0, -1):
        if heads % gh == 0 and gh * footprint(rows, tile) <= budget:
            return gh, rows, tile
    # one head a step, and of its rows a block: whole spans, or a divisor of one
    spans = rows // q_len
    blocks = [q_len * n for n in range(spans, 0, -1) if spans % n == 0]
    blocks += [q_len // n for n in range(2, q_len + 1) if q_len % n == 0 and (q_len // n) % 8 == 0]
    for block_rows in blocks:
        if footprint(block_rows, tile) <= budget:
            return 1, block_rows, tile
    return 1, blocks[-1], tile


def _block_span(h, rows, q_len, row_blocks):
    """``(first offset, offsets spanned)`` of the query positions, relative to
    the row's base, in the row block that segment ``h`` of a batch row names: a
    block of whole query spans starts at 0 and spans ``q_len``; a divisor of a
    span is the range ``[first, first + rows)``."""
    if rows >= q_len:
        return 0, q_len
    return ((h % row_blocks) * rows) % q_len, rows


def _live_column(base, first, span, width, block_size, xp=jnp):
    """The last table column that holds a key some query of a segment sees:
    ``base`` is the batch row's position, ``first`` and ``span`` the segment's
    (:func:`_block_span`). The segment's walk fetches the columns ``0 .. live``
    and its tiles are ``0 .. live // tile``; one that sees no key (a negative
    position) still takes column 0, so that every segment has a tile. One
    function for the kernels' scalars and, with ``xp=numpy``, for the host's
    count (:func:`walk_steps`)."""
    return xp.clip(base + first + (span - 1), 0, width * block_size - 1) // block_size


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _fold_tile(q, k, v, w, base, first, last, end, acc_ref, m_ref, l_ref, *, q_len, sm_scale, window=None):
    """Fold tile ``w`` of a segment's walk, ``k`` and ``v`` ``(gh, tile_keys,
    dim)``, into the (acc, m, l) scratch: the flash-attention recurrence of
    ``attention._flash_kernel``, walked over the table instead of a dense KV.

    The block's ``rows`` query rows are the query heads that share a key head,
    ``q_len`` tokens each, head-major: row ``r`` of the head's rows sits at
    position ``base + first + r % q_len``.
    """
    rows, tile_keys = q.shape[1], k.shape[1]
    operand = jnp.promote_types(q.dtype, k.dtype)
    scores = jax.lax.dot_general(
        q.astype(operand), k.astype(operand), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # (gh, rows, tile_keys)
    k_pos = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    offset = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    if rows > q_len:  # several query heads' spans in one block
        offset = offset % q_len
    q_pos = base + first + offset
    valid = k_pos <= jnp.minimum(q_pos, end)
    if window is not None:  # the last ``window`` keys and none before them
        valid = jnp.logical_and(valid, k_pos > q_pos - window)
    scores = jnp.where(valid, scores, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]  # (gh, rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # a query with no key yet (an empty live range inside a chunk) must add
    # exactly 0: m_new is still _NEG_INF there and exp(0) would be 1
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    correction = jnp.exp(m_prev - m_new)
    # what the tile holds past the block's last key (the rest of its last
    # block, an entry that was not fetched) has weight 0, and 0 x NaN would
    # still be NaN
    seen = w * tile_keys + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v = jnp.where(seen <= last, v, jnp.zeros_like(v))
    pv = jax.lax.dot_general(
        probs.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (gh, rows, hd)
    acc_ref[...] = acc_ref[...] * correction + pv
    l_ref[...] = l_prev * correction + jnp.sum(probs, axis=-1, keepdims=True)
    m_ref[...] = m_new


def _walk_kernel(
    table_ref,  # scalar prefetch: (batch, width) int32
    base_ref,  # scalar prefetch: (batch,) int32 query base positions
    q_ref,  # (1, gh, rows, hd)
    *rest,  # the pool's leaves where they lie (K, [V]), o_ref, scratch
    tile: int,
    sm_scale: float,
    shared_kv: bool,
    q_len: int,
    row_blocks: int,
    window: Optional[int] = None,
):
    """One segment of the copied walk: a batch row's head group and row block,
    over the tiles that hold keys it can see and over no other.

    The pool's leaves stay in HBM. A tile is ``tile`` consecutive table
    entries, each one pool block with the step's ``gh`` key heads, copied into
    one of two VMEM slots; of the segment's last tile only the entries up to
    its last live column are copied. While a tile is folded
    (:func:`_fold_tile`) the next one is on its way: the segment's next tile,
    or after its last the first tile of the next segment, whose grid step finds
    it waiting. So the grid's steps are the segments, the loop's the live tiles
    (:func:`walk_steps` counts them), and a table column past a row's keys
    costs nothing at all.
    """
    pools, rest = rest[: 1 if shared_kv else 2], rest[1 if shared_kv else 2:]
    o_ref, rest = rest[0], rest[1:]
    buffers, (arrived, slot_ref, acc_ref, m_ref, l_ref) = rest[: len(pools)], rest[len(pools):]

    b, h = pl.program_id(0), pl.program_id(1)
    segments = pl.num_programs(1)
    step = b * segments + h
    heads, block_size = pools[0].shape[1], pools[0].shape[2]
    gh, rows = q_ref.shape[1], q_ref.shape[2]
    width = table_ref.shape[1]
    tile_keys = tile * block_size
    end = width * block_size - 1  # the table's last key

    def live_column(b, h):
        first, span = _block_span(h, rows, q_len, row_blocks)
        return _live_column(base_ref[b], first, span, width, block_size)

    def each_copy(b, h, w, slot, act):
        """Start, or wait for, the copies of tile ``w`` of segment ``(b, h)``:
        its entries up to the segment's last live column."""
        entries = jnp.minimum(live_column(b, h) - w * tile + 1, tile)

        def entry(t, carry):
            block = table_ref[b, w * tile + t]
            for pool, buffer in zip(pools, buffers):
                source = pool.at[block]
                if gh < heads:
                    source = source.at[pl.ds(h // row_blocks * gh, gh)]
                act(pltpu.make_async_copy(source, buffer.at[slot, t], arrived.at[slot]))
            return carry

        jax.lax.fori_loop(0, entries, entry, 0)

    start = functools.partial(each_copy, act=lambda copy: copy.start())
    wait = functools.partial(each_copy, act=lambda copy: copy.wait())

    @pl.when(step == 0)
    def _first_of_all():
        slot_ref[0] = 0
        start(b, h, 0, 0)

    first, span = _block_span(h, rows, q_len, row_blocks)
    last = jnp.minimum(base_ref[b] + first + (span - 1), end)  # the last key any query of the block sees
    tiles = live_column(b, h) // tile + 1
    slot0 = slot_ref[0]
    _init_state(acc_ref, m_ref, l_ref)

    def walk(w, carry):
        slot = (slot0 + w) % 2
        more = w + 1 < tiles
        # the segment that follows in the grid's order, and its first tile
        wrap = h + 1 == segments
        nb, nh = jnp.where(wrap, b + 1, b), jnp.where(wrap, 0, h + 1)

        @pl.when(jnp.logical_or(more, step + 1 < pl.num_programs(0) * segments))
        def _next_tile():
            start(jnp.where(more, b, nb), jnp.where(more, h, nh), jnp.where(more, w + 1, 0), 1 - slot)

        wait(b, h, w, slot)

        @pl.when(w * tile_keys <= last)
        def _fold():
            joined = [
                jnp.concatenate([buffer[slot, t] for t in range(tile)], axis=1) for buffer in buffers
            ]  # (gh, tile_keys, dim) a leaf
            _fold_tile(
                q_ref[0], joined[0], joined[-1], w, base_ref[b], first, last, end,
                acc_ref, m_ref, l_ref, q_len=q_len, sm_scale=sm_scale, window=window,
            )

        return carry

    jax.lax.fori_loop(0, tiles, walk, 0)
    slot_ref[0] = (slot0 + tiles) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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
    window: Optional[int] = None,
):
    """One (batch row, head group and row block, table tile) program of the
    BlockSpec walk: the pool leaves Mosaic will not slice (an int8 pool's).

    The scalar-prefetched table row already steered this tile's DMAs (see the
    index maps in :func:`_paged_forward`): ``tile`` consecutive table entries,
    each one pool block with all ``gh`` key heads, (1, gh, bs, hd). The body
    joins them into one (gh, tile * bs, hd) K and V, so the scores fill the
    lanes, and folds them (:func:`_fold_tile`). It runs only for tiles that
    hold a key some query of the block may see (``tile start <= base + the
    block's last offset``); for the tiles past that the index maps repeat the
    last live tile, so nothing is fetched either, and what is left of the grid
    step is its fixed cost. With ``shared_kv`` the K tile is the V tile too.

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
    width = table_ref.shape[1]
    tile_keys = tile * block_size
    # the table's last key: a short last tile repeats its last entry past it
    end = width * block_size - 1
    first, span = _block_span(pl.program_id(1), rows, q_len, row_blocks)
    last = jnp.minimum(base_ref[b] + first + (span - 1), end)  # the last key any query of the block sees

    @pl.when(w == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

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

        k = joined(k_refs, k_scale_refs)  # (gh, tile_keys, hd)
        v = k if shared_kv else joined(v_refs, v_scale_refs)
        _fold_tile(
            q_ref[0], k, v, w, base_ref[b], first, last, end,
            acc_ref, m_ref, l_ref, q_len=q_len, sm_scale=sm_scale, window=window,
        )

    @pl.when(w == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _plan(heads, rows_all, q_len, head_dim, width, k, v, k_scale):
    """``(copied, key heads, query rows, table entries)`` of a call of
    :func:`_paged_forward`: which walk its pool leaves take and how
    :func:`_tiling` cuts its shapes. Shapes and dtypes only."""
    copied = _copied(k, v, k_scale)
    tiling = _tiling(
        heads, rows_all, q_len, k.shape[2], head_dim, width, jnp.dtype(k.dtype).itemsize,
        k_scale is not None, copied,
    )
    return (copied,) + tiling


@functools.partial(jax.jit, static_argnames=("out_dtype", "sm_scale", "q_len", "interpret", "window"))
def _paged_forward(
    q, k, v, block_table, base_positions, k_scale, v_scale, *, out_dtype, sm_scale, q_len, interpret,
    window=None,
):
    """``q`` is ``(batch, key heads, rows, head_dim)``: each key head's query
    heads side by side, ``q_len`` tokens each (see :func:`paged_attention`).

    Jitted, so that a model's layers share one trace of the kernel and a
    program one lowering of it (a ``func.call`` a layer): the kernel's body was
    most of what tracing and lowering a decode step cost, 24 times over."""
    batch, heads, rows_all, head_dim = q.shape
    block_size = k.shape[2]
    width = block_table.shape[1]
    quantized = k_scale is not None
    shared_kv = v is None
    out_dim = head_dim if shared_kv else v.shape[-1]
    copied, gh, rows, tile = _plan(heads, rows_all, q_len, head_dim, width, k, v, k_scale)
    row_blocks = rows_all // rows
    segments = heads // gh * row_blocks

    def by_row(b, h, *_):
        return b, h // row_blocks, h % row_blocks, 0

    def entry(t):
        """Index map of a tile's ``t``-th table entry in the BlockSpec walk:
        (b, h, w, table, base) to the pool block to DMA. Past the block's last
        live column the walk stands still: the entry repeats, and a repeated
        block index skips its DMA."""

        def index(b, h, w, table, base):
            first, span = _block_span(h, rows, q_len, row_blocks)
            live = _live_column(base[b], first, span, width, block_size)
            column = jnp.minimum(jnp.minimum(w, live // tile) * tile + t, live)
            return table[b, column], h // row_blocks, 0, 0

        return index

    common = dict(
        tile=tile, sm_scale=sm_scale, shared_kv=shared_kv, q_len=q_len, row_blocks=row_blocks, window=window
    )
    in_specs = [pl.BlockSpec((1, gh, rows, head_dim), by_row)]
    scratch = [
        pltpu.VMEM((gh, rows, out_dim), jnp.float32),
        pltpu.VMEM((gh, rows, 1), jnp.float32),
        pltpu.VMEM((gh, rows, 1), jnp.float32),
    ]
    if copied:
        # the leaves where they lie; two slots a leaf, a DMA semaphore a slot,
        # and the slot the segment's first tile is in, carried from step to step
        kernel = functools.partial(_walk_kernel, **common)
        grid = (batch, segments)
        operands = [q, k] + ([] if shared_kv else [v])
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1)
        scratch = [
            pltpu.VMEM((2, tile, gh) + leaf.shape[2:], leaf.dtype) for leaf in operands[1:]
        ] + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)] + scratch
    else:
        kernel = functools.partial(
            _paged_kernel, block_size=block_size, quantized=quantized, out_dtype=out_dtype, **common
        )
        grid = (batch, segments, -(-width // tile))
        in_specs += [pl.BlockSpec((1, gh, block_size, head_dim), entry(t)) for t in range(tile)]
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
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gh, rows, out_dim), by_row),
        scratch_shapes=scratch,
    )
    codes_bytes = width * heads * block_size * (head_dim + (0 if shared_kv else out_dim)) * k.dtype.itemsize
    scale_bytes = 2 * width * heads * 4 if quantized else 0
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, rows_all, out_dim), out_dtype),
        # sequential: a segment's first tile is fetched by the segment before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        # a full table: what rows of the greatest length cost
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * rows_all * width * block_size * (head_dim + out_dim),
            bytes_accessed=batch * (q.size // batch * 2 * q.dtype.itemsize + codes_bytes + scale_bytes),
            transcendentals=batch * heads * rows_all * width * block_size,
        ),
        interpret=interpret,
    )
    # the scope again, innermost: XLA names the Mosaic call after it, so a trace
    # shows the kernel as ``paged_attention`` whatever jit wraps it
    with jax.named_scope("paged_attention"):
        return call(
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
    window: Optional[int] = None,
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
    :param window: a query at logical position ``p`` sees the keys at ``p -
        window < k <= p`` and none before them. The walk still begins at the
        table's first column: a caller whose rows keep a ring of blocks hands
        over the row's table ROTATED, its first column the block that holds
        the window's first key, and base positions counted from that block's
        first key (``models/phi4flash.py::ring_view``): the walk then starts at
        the window's first live tile and covers ``window / block_size + 1``
        columns whatever the row's length.
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
                window=window,
            )
    if not interpret and not on_tpu():
        raise RuntimeError(
            f"paged_attention(impl='pallas') needs a TPU backend, found "
            f"{jax.default_backend()!r}; use impl='auto'/'xla', or interpret=True in tests"
        )
    # hashable: they are static arguments of the jitted forward
    scale = float(1.0 / np.sqrt(head_dim) if sm_scale is None else sm_scale)
    out_dtype = jnp.dtype(out_dtype)
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
            k, v, block_table, base_positions, k_scale, v_scale,
            out_dtype=out_dtype, sm_scale=scale, q_len=S, interpret=interpret,
            window=window,
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


def walk_steps(
    q, k, v, block_table, base_positions, k_scale=None, v_scale=None, shards: int = 1,
) -> int:
    """Steps one call of the kernel takes over these operands: the tiles it
    fetches and folds, of every segment (batch row x head group x row block).

    The operands are :func:`paged_attention`'s, of which only ``base_positions``
    is read (a host array: this is numpy on the host, the engine's count for
    ``/stats`` ``kernel_grid_steps``); the others give shapes and dtypes, so
    ``jax.ShapeDtypeStruct`` will do. ``shards``: the ``tensor`` axis of the
    mesh the call is shard-mapped over; the count is one shard's. The copied
    walk takes ``live // tile + 1`` steps a segment, ``live`` the last table
    column that holds a key the segment sees (:func:`_live_column`): the loop of
    :func:`_walk_kernel` runs that often, in so many grid steps as there are
    segments. The BlockSpec walk takes a grid step for every tile of the table,
    whatever the rows hold.
    """
    del v_scale
    batch, heads, q_len, head_dim = q.shape
    key_heads, block_size, row = k.shape[1:]
    width = block_table.shape[1]
    if shards > 1 and key_heads % shards == 0:
        heads, key_heads = heads // shards, key_heads // shards
    elif shards > 1 and key_heads == 1 and heads % shards == 0:
        heads //= shards
    if v is None and row > head_dim:
        head_dim = row  # rows of [key | value]: the query is padded to them
    rows_all = heads // key_heads * q_len
    copied, gh, rows, tile = _plan(key_heads, rows_all, q_len, head_dim, width, k, v, k_scale)
    row_blocks = rows_all // rows
    if not copied:
        return batch * (key_heads // gh) * row_blocks * -(-width // tile)
    first, span = _block_span(np.arange(row_blocks), rows, q_len, row_blocks)
    base = np.asarray(base_positions, np.int64).reshape(batch, 1)
    live = _live_column(base, first, span, width, block_size, xp=np)
    tiles = np.broadcast_to(live // tile + 1, (batch, row_blocks))
    return int(tiles.sum()) * (key_heads // gh)


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
