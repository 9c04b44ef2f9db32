"""Attention ops: pallas flash-attention TPU kernel with an XLA fallback.

The reference delegates all math to user frameworks (SURVEY.md §2: "no CUDA/C++
anywhere"); in the TPU rebuild the attention hot op is owned by the framework. Two
implementations behind one dispatcher:

- ``impl="pallas"``: blocked flash attention (online softmax) keeping the working set
  in VMEM, f32 accumulation on the MXU, O(seq) memory. Grid: (batch*heads, q_blocks);
  the KV scan runs inside the kernel with ``jax.lax.fori_loop``. The BACKWARD is also
  pallas: the forward saves per-row logsumexp residuals and the dq / dk+dv kernels
  recompute probabilities blockwise (flash-attention-2 style), so training never
  materializes the (seq x seq) score matrix either.
- ``impl="xla"``: the standard fused-by-XLA softmax(QK^T)V — the exact reference, the
  dense-mask path, and the fallback for non-tile-aligned shapes (fwd and bwd).
- ``impl="auto"``: pallas on TPU backends, XLA elsewhere (CPU tests run the fallback).

Shapes follow the (batch, num_heads, seq, head_dim) convention.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """(batch, seq) packed segment ids -> (batch, 1, seq, seq) attention mask.

    Convention (t5x/flax): ``0`` marks padding, positive ints mark segments; a
    query attends a key iff they carry the same positive id. This dense mask is
    what packing costs on the XLA path — O(seq^2) HBM per row — and what the
    pallas kernel's blockwise comparison avoids.
    """
    same = segment_ids[:, :, None] == segment_ids[:, None, :]
    valid = same & (segment_ids > 0)[:, None, :] & (segment_ids > 0)[:, :, None]
    return valid[:, None, :, :]


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference attention; XLA fuses the softmax chain. Used as fallback + backward."""
    *_, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(head_dim)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        logits = jnp.where(causal_mask[None, None], logits, _NEG_INF)
    if segment_ids is not None:
        # sliced per axis so cross-length (seq_q != seq_k) calls mask correctly,
        # matching the pallas path's _segment_arrays slicing
        ids_q = segment_ids[:, :seq_q]
        ids_k = segment_ids[:, :seq_k]
        valid = (
            (ids_q[:, :, None] == ids_k[:, None, :])
            & (ids_q > 0)[:, :, None]
            & (ids_k > 0)[:, None, :]
        )
        logits = jnp.where(valid[:, None], logits, _NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (padding in a packed batch) softmax to uniform garbage;
    # zero them so packed outputs match the per-sequence reference exactly
    if segment_ids is not None:
        weights = jnp.where((ids_q > 0)[:, None, :, None], weights, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


def _flash_kernel(
    kv_len_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    block_k: int,
    seq_k: int,
    causal: bool,
    sm_scale: float,
    block_q: int,
    packed: bool = False,
    heads: int = 1,
):
    """One (batch*head, q_block) program: stream KV blocks with an online softmax.

    ``kv_len_ref`` is the whole (batch*heads,) valid-KV-length vector in SMEM
    (Mosaic only allows rank-1 blocks that are whole-array or lane-tile multiples,
    so it is passed unblocked and indexed by the grid's batch*head coordinate);
    K positions >= kv_len contribute nothing. When pallas passes a second output
    ref (``lse_ref``), the per-row logsumexp is written as the backward residual.

    ``packed`` prepends four extra input refs: packed segment ids in
    Mosaic-friendly layouts — (1, block_q, 1) and (1, 1, seq_k) blocks of the
    (batch, seq, 1) / (batch, 1, seq) id arrays — adding the blockwise
    same-segment constraint that packing needs WITHOUT a dense (seq, seq) mask,
    plus the rank-1 SMEM block-skip bounds from :func:`_segment_block_bounds`
    (this q block's live KV range), so cross-segment KV blocks are never even
    loaded — per-row work is O(sum seg_len^2), not O(seq^2).
    """
    if packed:
        seg_q_ref, seg_k_ref, kvb_start_ref, kvb_stop_ref, o_ref, *maybe_lse = rest
    else:
        seg_q_ref = seg_k_ref = kvb_start_ref = kvb_stop_ref = None
        o_ref, *maybe_lse = rest
    lse_ref = maybe_lse[0] if maybe_lse else None

    q = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, head_dim)
    q_index = pl.program_id(1)
    kv_len = kv_len_ref[pl.program_id(0)]
    seg_q = None if seg_q_ref is None else seg_q_ref[0].reshape(block_q, 1)

    acc = jnp.zeros((block_q, q.shape[-1]), dtype=jnp.float32)
    row_max = jnp.full((block_q, 1), _NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((block_q, 1), dtype=jnp.float32)

    num_k_blocks = seq_k // block_k

    def body(k_idx, carry):
        acc, row_max, row_sum = carry
        k_block = k_ref[0, pl.ds(k_idx * block_k, block_k), :].astype(jnp.float32)
        v_block = v_ref[0, pl.ds(k_idx * block_k, block_k), :].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k_block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)

        k_pos = k_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = k_pos < kv_len
        if seg_q is not None:
            seg_k = seg_k_ref[0, :, pl.ds(k_idx * block_k, block_k)]  # (1, block_k)
            valid = jnp.logical_and(valid, jnp.logical_and(seg_q == seg_k, seg_q > 0))
        if causal:
            q_pos = q_index * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        scores = jnp.where(valid, scores, _NEG_INF)

        new_max = jnp.maximum(row_max, jnp.max(scores, axis=-1, keepdims=True))
        correction = jnp.exp(row_max - new_max)
        # masked slots must contribute exactly 0: for a live row exp(scores - new_max)
        # already underflows to 0 there, but for a FULLY-masked row (packed padding)
        # new_max == scores == _NEG_INF and exp(0) would be 1 — the where() is what
        # keeps row_sum at 0 so such rows divide to zeros below
        probs = jnp.where(valid, jnp.exp(scores - new_max), 0.0)
        acc = acc * correction + jax.lax.dot_general(
            probs, v_block, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        row_sum = row_sum * correction + jnp.sum(probs, axis=-1, keepdims=True)
        return acc, new_max, row_sum

    # bound the scan: skip fully-masked KV blocks (padding tail; causal upper
    # triangle; packed: everything outside this q block's own segments)
    first_block = jnp.int32(0)
    last_block = jnp.minimum(num_k_blocks, pl.cdiv(kv_len, block_k))
    if causal:
        last_block = jnp.minimum(last_block, pl.cdiv((q_index + 1) * block_q, block_k))
    if packed:
        num_q_blocks = pl.num_programs(1)
        bounds_row = (pl.program_id(0) // heads) * num_q_blocks + q_index
        first_block = jnp.maximum(first_block, kvb_start_ref[bounds_row])
        last_block = jnp.minimum(last_block, kvb_stop_ref[bounds_row])
    acc, row_max, row_sum = jax.lax.fori_loop(
        first_block, last_block, body, (acc, row_max, row_sum)
    )
    # fully-masked rows (packed padding) carry acc == row_sum == 0 — the masked probs
    # above guarantee it — so the guarded divide emits the zeros the XLA reference
    # and the ring kernel produce for such rows
    o_ref[0] = (acc / jnp.maximum(row_sum, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        # logsumexp of the (scaled, masked) scores — the residual the backward needs
        lse = row_max + jnp.log(jnp.maximum(row_sum, 1e-30))
        lse_ref[0] = lse.reshape(lse_ref.shape[1:]).astype(jnp.float32)


def _tile_aligned(seq_q: int, seq_k: int, head_dim: int, block_q: int, block_k: int) -> bool:
    # irregular shapes fall back to XLA for exactness; head_dim down to 64 is allowed
    # (mosaic pads the lane dim), smaller/odd head dims are not worth the kernel
    return not (seq_q % block_q or seq_k % block_k or head_dim % 64)


def _segment_arrays(segment_ids: jax.Array, seq_q: int, seq_k: int):
    """Packed ids -> the kernels' Mosaic-friendly operands.

    Returns ``(seg_q3, seg_k3, kv_lens)``: (batch, seq_q, 1) and (batch, 1, seq_k)
    int32 views (the trailing/leading singleton keeps blocks on the proven
    (block, 1)/(1, block) tilings) plus the per-row valid length. kv_len is the
    last-nonzero index + 1 (not the nonzero COUNT): pack_sequences emits padding
    as a contiguous zero suffix where the two agree, but hand-built ids with
    interior zeros must degrade to in-block masking — counting would silently
    skip trailing live blocks.
    """
    ids = segment_ids.astype(jnp.int32)
    seg_q3 = ids[:, :seq_q, None]
    seg_k3 = ids[:, None, :seq_k]
    positions = jnp.arange(seq_k, dtype=jnp.int32)[None, :]
    kv_lens = jnp.max(jnp.where(ids[:, :seq_k] > 0, positions + 1, 0), axis=-1)
    return seg_q3, seg_k3, kv_lens


def _segment_block_bounds(segment_ids, block: int, other_block: int):
    """Per-chunk live range of the other axis — the packed kernels' block-skip map.

    ``segment_ids`` is a ``(block_axis_ids, other_axis_ids)`` pair — e.g. the
    q-side slice and the kv-side slice of the packed id array; lengths may
    differ (cross-length attention slices both from one array). A chunk of
    ``block`` positions on the block axis may only interact with other-axis
    positions of the segments it contains (plus nothing, for pure padding). For
    each row and chunk this computes the union of its segments' TRUE other-axis
    extents — scatter-min/max over segment IDS, not run boundaries, so rows
    that reuse an id non-contiguously get the full (conservative) extent and
    stay exact — and returns ``(start, stop)`` int32 arrays of shape
    (batch, s_block // block), in units of ``other_block``,
    flattened-rank-1-ready for SMEM. Empty chunks (and ids absent from the
    other axis) get start >= stop (the fori_loop runs zero iterations).
    Out-of-range ids clamp into one shared bucket: merged extents are
    supersets, and in-block masking keeps supersets exact.

    This is where packing pays on TPU: total kernel work drops from
    O(seq^2) to O(sum_i seg_len_i^2) per row — the XLA path cannot skip, it
    materializes the dense mask and computes every pair.
    """
    block_ids, other_ids = (x.astype(jnp.int32) for x in segment_ids)
    batch, s_other = other_ids.shape
    s_block = block_ids.shape[1]
    cap = max(s_block, s_other)  # shared clip bucket for out-of-range ids
    pos_o = jnp.broadcast_to(jnp.arange(s_other, dtype=jnp.int32)[None, :], other_ids.shape)
    rows_o = jnp.broadcast_to(jnp.arange(batch, dtype=jnp.int32)[:, None], other_ids.shape)
    safe_o = jnp.clip(other_ids, 0, cap)
    first_of_id = jnp.full((batch, cap + 1), s_other, jnp.int32).at[rows_o, safe_o].min(pos_o)
    end_of_id = jnp.zeros((batch, cap + 1), jnp.int32).at[rows_o, safe_o].max(pos_o + 1)
    safe_b = jnp.clip(block_ids, 0, cap)
    seg_start = jnp.take_along_axis(first_of_id, safe_b, axis=1)  # (batch, s_block)
    seg_end = jnp.take_along_axis(end_of_id, safe_b, axis=1)
    live = block_ids > 0
    n_chunks = s_block // block
    chunk_start = jnp.min(
        jnp.where(live, seg_start, s_other).reshape(batch, n_chunks, block), axis=2
    )
    chunk_end = jnp.max(jnp.where(live, seg_end, 0).reshape(batch, n_chunks, block), axis=2)
    start_blocks = chunk_start // other_block
    stop_blocks = -(-chunk_end // other_block)  # cdiv
    return start_blocks.reshape(-1), stop_blocks.reshape(-1)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: Optional[jax.Array],
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    return_residuals: bool = False,
    segment_ids: Optional[jax.Array] = None,
):
    batch, heads, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]

    if not _tile_aligned(seq_q, seq_k, head_dim, block_q, block_k):
        mask = _kv_lens_to_mask(kv_lens, seq_k) if kv_lens is not None else None
        out = xla_attention(
            q, k, v, mask=mask, causal=causal, sm_scale=sm_scale, segment_ids=segment_ids
        )
        return (out, None) if return_residuals else out

    bh = batch * heads
    q3 = q.reshape(bh, seq_q, head_dim)
    k3 = k.reshape(bh, seq_k, head_dim)
    v3 = v.reshape(bh, seq_k, head_dim)
    packed = segment_ids is not None
    if packed:
        seg_q3, seg_k3, kv_lens = _segment_arrays(segment_ids, seq_q, seq_k)
    if kv_lens is None:
        kv_lens = jnp.full((batch,), seq_k, dtype=jnp.int32)
    kv_lens_bh = jnp.repeat(kv_lens.astype(jnp.int32), heads)

    kernel = functools.partial(
        _flash_kernel,
        block_k=block_k,
        seq_k=seq_k,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        packed=packed,
        heads=heads,
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # whole kv_lens vector, unblocked
        pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
    ]
    operands = [kv_lens_bh, q3, k3, v3]
    if packed:
        # segment ids are per-batch-row; the index map folds the head axis away
        in_specs.append(pl.BlockSpec((1, block_q, 1), lambda b, i: (b // heads, i, 0)))
        in_specs.append(pl.BlockSpec((1, 1, seq_k), lambda b, i: (b // heads, 0, 0)))
        operands.extend([seg_q3, seg_k3])
        # per-q-block live KV ranges: rank-1 SMEM, row = batch * n_q_blocks + i
        ids32 = segment_ids.astype(jnp.int32)
        kvb_start, kvb_stop = _segment_block_bounds(
            (ids32[:, :seq_q], ids32[:, :seq_k]), block_q, block_k
        )
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.extend([kvb_start, kvb_stop])
    out_shape = [jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0))]
    if return_residuals:
        # trailing singleton keeps the block's last-two dims Mosaic-tileable:
        # (block_q, 1) has last dim == array dim and block_q % 8 == 0
        out_shape.append(jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)))
    result = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // block_q),
        in_specs=in_specs,
        out_specs=out_specs if return_residuals else out_specs[0],
        out_shape=out_shape if return_residuals else out_shape[0],
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * seq_q * seq_k * head_dim,
            bytes_accessed=(q3.size + k3.size + v3.size + q3.size) * q3.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
        interpret=interpret,
    )(*operands)
    if return_residuals:
        out, lse = result
        return out.reshape(batch, heads, seq_q, head_dim), lse.reshape(batch, heads, seq_q)
    return result.reshape(batch, heads, seq_q, head_dim)


def _kv_lens_to_mask(kv_lens: jax.Array, seq_k: int) -> jax.Array:
    """(batch,) valid lengths -> (batch, 1, 1, seq_k) boolean padding mask."""
    positions = jnp.arange(seq_k)[None, :]
    return (positions < kv_lens[:, None])[:, None, None, :]


def _bwd_dq_kernel(
    kv_len_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *rest,
    block_k: int,
    seq_k: int,
    causal: bool,
    sm_scale: float,
    block_q: int,
    packed: bool = False,
    heads: int = 1,
):
    """dQ for one (batch*head, q_block): stream KV blocks, recompute probabilities."""
    if packed:
        seg_q_ref, seg_k_ref, kvb_start_ref, kvb_stop_ref, dq_ref = rest
    else:
        seg_q_ref = seg_k_ref = kvb_start_ref = kvb_stop_ref = None
        (dq_ref,) = rest
    qs = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, d); scores are pre-scaled
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0].reshape(block_q, 1)
    delta = delta_ref[0].reshape(block_q, 1)
    q_index = pl.program_id(1)
    kv_len = kv_len_ref[pl.program_id(0)]
    seg_q = None if seg_q_ref is None else seg_q_ref[0].reshape(block_q, 1)

    dq = jnp.zeros((block_q, qs.shape[-1]), dtype=jnp.float32)
    num_k_blocks = seq_k // block_k

    def body(k_idx, dq):
        k_block = k_ref[0, pl.ds(k_idx * block_k, block_k), :].astype(jnp.float32)
        v_block = v_ref[0, pl.ds(k_idx * block_k, block_k), :].astype(jnp.float32)
        scores = jax.lax.dot_general(
            qs, k_block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        k_pos = k_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = k_pos < kv_len
        if seg_q is not None:
            seg_k = seg_k_ref[0, :, pl.ds(k_idx * block_k, block_k)]  # (1, block_k)
            valid = jnp.logical_and(valid, jnp.logical_and(seg_q == seg_k, seg_q > 0))
        if causal:
            q_pos = q_index * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        probs = jnp.where(valid, jnp.exp(scores - lse), 0.0)
        dp = jax.lax.dot_general(do, v_block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dscores = probs * (dp - delta)
        return dq + jax.lax.dot_general(
            dscores, k_block, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    first_block = jnp.int32(0)
    last_block = jnp.minimum(num_k_blocks, pl.cdiv(kv_len, block_k))
    if causal:
        last_block = jnp.minimum(last_block, pl.cdiv((q_index + 1) * block_q, block_k))
    if packed:
        # same per-q-block live KV range the forward used (see _segment_block_bounds)
        bounds_row = (pl.program_id(0) // heads) * pl.num_programs(1) + q_index
        first_block = jnp.maximum(first_block, kvb_start_ref[bounds_row])
        last_block = jnp.minimum(last_block, kvb_stop_ref[bounds_row])
    dq = jax.lax.fori_loop(first_block, last_block, body, dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    kv_len_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *rest,
    block_q: int,
    seq_q: int,
    causal: bool,
    sm_scale: float,
    block_k: int,
    packed: bool = False,
    heads: int = 1,
):
    """dK/dV for one (batch*head, kv_block): stream Q blocks, recompute probabilities."""
    if packed:
        seg_q_ref, seg_k_ref, qb_start_ref, qb_stop_ref, dk_ref, dv_ref = rest
    else:
        seg_q_ref = seg_k_ref = qb_start_ref = qb_stop_ref = None
        dk_ref, dv_ref = rest
    k_block = k_ref[0].astype(jnp.float32)  # (block_k, d)
    v_block = v_ref[0].astype(jnp.float32)
    kv_index = pl.program_id(1)
    kv_len = kv_len_ref[pl.program_id(0)]
    # this program's fixed (1, block_k) key-segment row
    seg_k = None if seg_k_ref is None else seg_k_ref[0]

    dk = jnp.zeros_like(k_block)
    dv = jnp.zeros_like(v_block)
    num_q_blocks = seq_q // block_q

    def body(q_idx, carry):
        dk, dv = carry
        qs = q_ref[0, pl.ds(q_idx * block_q, block_q), :].astype(jnp.float32) * sm_scale
        do = do_ref[0, pl.ds(q_idx * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_idx * block_q, block_q)].reshape(block_q, 1)
        delta = delta_ref[0, pl.ds(q_idx * block_q, block_q)].reshape(block_q, 1)

        scores = jax.lax.dot_general(
            qs, k_block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        k_pos = kv_index * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = k_pos < kv_len
        if seg_k is not None:
            seg_q = seg_q_ref[0, pl.ds(q_idx * block_q, block_q), :]  # (block_q, 1)
            valid = jnp.logical_and(valid, jnp.logical_and(seg_q == seg_k, seg_q > 0))
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        probs = jnp.where(valid, jnp.exp(scores - lse), 0.0)

        dv = dv + jax.lax.dot_general(
            probs, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v_block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dscores = probs * (dp - delta)
        # qs already carries sm_scale, so this is the gradient wrt the original K
        dk = dk + jax.lax.dot_general(
            dscores, qs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    # causal: q blocks strictly above this kv block's diagonal contribute nothing;
    # kv blocks entirely beyond kv_len (padding tail) skip the whole scan; packed
    # rows additionally scan only the q blocks whose segments touch this kv block
    # (transposed _segment_block_bounds map — same O(sum seg_len^2) economics as
    # the forward)
    first_block = (kv_index * block_k) // block_q if causal else jnp.int32(0)
    in_range = kv_index * block_k < kv_len
    num_live_q_blocks = num_q_blocks
    if packed:
        # the transposed _segment_block_bounds map is the exact live-q-block
        # bound; a kv_len-derived bound would measure KV length in Q-block
        # units and drop dk/dv rows whenever seq_q > seq_k (ADVICE round 4)
        bounds_row = (pl.program_id(0) // heads) * pl.num_programs(1) + kv_index
        first_block = jnp.maximum(first_block, qb_start_ref[bounds_row])
        num_live_q_blocks = jnp.minimum(num_live_q_blocks, qb_stop_ref[bounds_row])
    last_block = jnp.where(in_range, num_live_q_blocks, first_block)
    dk, dv = jax.lax.fori_loop(first_block, last_block, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: Optional[jax.Array],
    out: jax.Array,
    lse: jax.Array,
    g: jax.Array,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    segment_ids: Optional[jax.Array] = None,
):
    """Pallas flash backward: dq/dk/dv with O(seq) memory, probabilities recomputed."""
    batch, heads, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]
    bh = batch * heads

    reshape3 = lambda x: x.reshape(bh, x.shape[-2], x.shape[-1])
    q3, k3, v3, do3 = reshape3(q), reshape3(k), reshape3(v), reshape3(g)
    # trailing singleton: see the forward's residual out_spec comment
    lse3 = lse.reshape(bh, seq_q, 1)
    # delta_i = rowsum(dO * O): the softmax-jacobian correction term
    delta3 = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(bh, seq_q, 1)
    packed = segment_ids is not None
    if packed:
        seg_q3, seg_k3, kv_lens = _segment_arrays(segment_ids, seq_q, seq_k)
    if kv_lens is None:
        kv_lens_bh = jnp.full((bh,), seq_k, dtype=jnp.int32)
    else:
        kv_lens_bh = jnp.repeat(kv_lens.astype(jnp.int32), heads)

    seg_operands = [seg_q3, seg_k3] if packed else []
    seg_specs = (
        [
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b // heads, i, 0)),
            pl.BlockSpec((1, 1, seq_k), lambda b, i: (b // heads, 0, 0)),
        ]
        if packed
        else []
    )

    if packed:
        ids32 = segment_ids.astype(jnp.int32)
        kvb_start, kvb_stop = _segment_block_bounds(
            (ids32[:, :seq_q], ids32[:, :seq_k]), block_q, block_k
        )
        qb_start, qb_stop = _segment_block_bounds(
            (ids32[:, :seq_k], ids32[:, :seq_q]), block_k, block_q
        )
        dq_seg_operands = [*seg_operands, kvb_start, kvb_stop]
        dq_seg_specs = seg_specs + [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
    else:
        dq_seg_operands = seg_operands
        dq_seg_specs = seg_specs

    dq_kernel = functools.partial(
        _bwd_dq_kernel,
        block_k=block_k,
        seq_k=seq_k,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        packed=packed,
        heads=heads,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_q // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole kv_lens vector, unblocked
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ]
        + dq_seg_specs,
        out_specs=pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=6 * bh * seq_q * seq_k * head_dim,  # scores + dp + dq matmuls
            bytes_accessed=(q3.size + k3.size + v3.size + 2 * do3.size) * q3.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
        interpret=interpret,
    )(kv_lens_bh, q3, k3, v3, do3, lse3, delta3, *dq_seg_operands)

    # the dkv grid iterates kv blocks: the key-segment operand is blocked, the
    # query-segment row streams whole
    dkv_seg_specs = (
        [
            pl.BlockSpec((1, seq_q, 1), lambda b, j: (b // heads, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, j: (b // heads, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # per-kv-block live q range
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
        if packed
        else []
    )
    dkv_seg_operands = [*seg_operands, qb_start, qb_stop] if packed else seg_operands
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel,
        block_q=block_q,
        seq_q=seq_q,
        causal=causal,
        sm_scale=sm_scale,
        block_k=block_k,
        packed=packed,
        heads=heads,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, seq_k // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole kv_lens vector, unblocked

            pl.BlockSpec((1, seq_q, head_dim), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, seq_q, head_dim), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq_q, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq_q, 1), lambda b, j: (b, 0, 0)),
        ]
        + dkv_seg_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, head_dim), v.dtype),
        ],
        cost_estimate=pl.CostEstimate(
            flops=8 * bh * seq_q * seq_k * head_dim,  # scores + dv + dp + dk matmuls
            bytes_accessed=(2 * q3.size + k3.size + v3.size + 2 * do3.size) * q3.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
        interpret=interpret,
    )(kv_lens_bh, q3, k3, v3, do3, lse3, delta3, *dkv_seg_operands)

    unshape = lambda x, s: x.reshape(batch, heads, s, head_dim)
    return unshape(dq, seq_q), unshape(dk, seq_k), unshape(dv, seq_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Blocked flash attention (pallas), fully differentiable.

    Backward also runs pallas kernels (probabilities recomputed from the saved
    logsumexp residual — O(seq) memory both ways); irregular shapes fall back to the
    XLA path in both directions.

    :param kv_lens: optional (batch,) int32 valid KV lengths — the padding-mask case
        (keys at positions >= kv_lens[b] are masked for every head/query of batch b).
    :param segment_ids: optional (batch, seq) int32 packed segment ids (0 =
        padding, positive = segment; t5x convention): queries attend only keys of
        their own segment, blockwise in-kernel — the packed-training regime where
        the XLA path would need a dense (seq, seq) mask per row. Mutually exclusive
        with ``kv_lens`` (padding is already encoded as id 0).
    :param block_q / block_k: Mosaic tile edges; ``None`` resolves through
        :func:`unionml_tpu.ops.tuning.pick_block_sizes` (measured winners when a
        ``bench_kernels.py`` sweep has recorded them, aligned defaults otherwise).
    """
    if segment_ids is not None and kv_lens is not None:
        raise ValueError("segment_ids already encodes padding; pass kv_lens=None")
    block_q, block_k = _resolve_blocks(q, k, block_q, block_k, packed=segment_ids is not None)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    return _flash_forward(
        q, k, v, kv_lens, causal, scale, block_q, block_k, interpret, segment_ids=segment_ids
    )


def _resolve_blocks(q, k, block_q, block_k, packed=False):
    if block_q is None or block_k is None:
        from unionml_tpu.ops.tuning import pick_block_sizes

        tuned_q, tuned_k = pick_block_sizes(
            q.shape[-2], k.shape[-2], q.shape[-1], packed=packed
        )
        block_q = block_q if block_q is not None else tuned_q
        block_k = block_k if block_k is not None else tuned_k
    return block_q, block_k


def _flash_fwd(q, k, v, kv_lens, segment_ids, causal, sm_scale, block_q, block_k, interpret):
    if segment_ids is not None and kv_lens is not None:
        raise ValueError("segment_ids already encodes padding; pass kv_lens=None")
    block_q, block_k = _resolve_blocks(q, k, block_q, block_k, packed=segment_ids is not None)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_forward(
        q,
        k,
        v,
        kv_lens,
        causal,
        scale,
        block_q,
        block_k,
        interpret,
        return_residuals=True,
        segment_ids=segment_ids,
    )
    # the XLA-fallback backward recomputes from q/k/v: don't keep `out` alive for it
    residual_out = out if lse is not None else None
    return out, (q, k, v, kv_lens, segment_ids, residual_out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, residuals, g):
    q, k, v, kv_lens, segment_ids, out, lse = residuals
    block_q, block_k = _resolve_blocks(q, k, block_q, block_k, packed=segment_ids is not None)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if lse is not None:
        dq, dk, dv = _flash_backward(
            q, k, v, kv_lens, out, lse, g, causal, scale, block_q, block_k, interpret,
            segment_ids=segment_ids,
        )
        return dq, dk, dv, None, None
    # irregular-shape path: differentiate the XLA reference instead
    mask = _kv_lens_to_mask(kv_lens, k.shape[-2]) if kv_lens is not None else None
    _, vjp = jax.vjp(
        lambda q_, k_, v_: xla_attention(
            q_, k_, v_, mask=mask, causal=causal, sm_scale=scale, segment_ids=segment_ids
        ),
        q,
        k,
        v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    kv_lens: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatching attention entrypoint used by the model zoo.

    ``impl="auto"`` consults the measured per-shape verdicts
    (:data:`unionml_tpu.ops.tuning.MEASURED_IMPL` — "xla" at every listed shape).
    Dense ``mask`` arrays and non-TPU backends always take the XLA path;
    ``impl="pallas"`` forces the flash kernel with its tuned block sizes.

    ``segment_ids`` selects the packed-sequence regime: on TPU the verdict comes
    from :data:`unionml_tpu.ops.tuning.MEASURED_PACKED_IMPL` — here the pallas
    kernel's blockwise segment comparison avoids the dense O(seq^2) mask the XLA
    path must materialize per row.
    """
    if segment_ids is not None and kv_lens is not None:
        # enforced here (not only in flash_attention) so the XLA path rejects the
        # combination identically instead of silently combining both masks
        raise ValueError("segment_ids already encodes padding; pass kv_lens=None")
    if impl == "auto":
        if on_tpu() and mask is None:
            from unionml_tpu.ops.tuning import pick_impl, pick_packed_impl

            if segment_ids is not None:
                impl = pick_packed_impl(q.shape[-2], k.shape[-2], q.shape[-1])
            else:
                impl = pick_impl(q.shape[-2], k.shape[-2], q.shape[-1])
        else:
            impl = "xla"
    if impl == "pallas":
        if mask is not None:
            raise ValueError(
                "attention(impl='pallas') does not support dense masks; pass kv_lens "
                "(right-padding) / segment_ids (packing) / causal, or use impl='xla' "
                "for arbitrary masks."
            )
        return flash_attention(q, k, v, kv_lens, segment_ids, causal, sm_scale)
    if impl == "xla":
        if mask is None and kv_lens is not None:
            mask = _kv_lens_to_mask(kv_lens, k.shape[-2])
        return xla_attention(
            q, k, v, mask=mask, causal=causal, sm_scale=sm_scale, segment_ids=segment_ids
        )
    raise ValueError(f"Unknown attention impl {impl!r}; expected 'auto', 'pallas', or 'xla'")
