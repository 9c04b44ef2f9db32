"""GPT-style causal decoder (flax) with KV-cache generation.

Completes the model-family coverage next to the BERT encoder: pre-LN transformer
decoder blocks over the framework's causal flash attention for training, and an
explicit functional KV cache for O(1)-per-token greedy/temperature decoding under
``lax.scan`` (static shapes; the cache is a pytree argument, not module state, so the
whole generate loop jit-compiles). Prefill is chunked: one forward over the whole
prompt fills every layer's cache before the decode scan starts.

TPU-first choices: bfloat16 compute / f32 params, rotary-free learned positions (the
GPT-2 recipe), logits in f32, weight tying between embedding and LM head.
"""

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.models.moe import MoEMlp
from unionml_tpu.ops.attention import attention, xla_attention
from unionml_tpu.ops.paged_attention import paged_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    #: "auto" | "xla" | "pallas" | "ring" | "ulysses" — the last two are the
    #: sequence-parallel long-context TRAINING paths and require ``sp_mesh``
    #: (generation/KV-cache paths fall back to per-token attention)
    attention_impl: str = "auto"
    #: paged DECODE attention backend ("auto" | "pallas" | "xla"): the fused
    #: dequant-attend kernel vs the gather-dequant reference — see
    #: :mod:`unionml_tpu.ops.paged_attention`. "auto" = pallas on TPU
    #: (measured verdicts override per shape class), XLA elsewhere.
    paged_attn_impl: str = "auto"
    #: the serving mesh, set by ``DecodeEngine(mesh=...)`` on its own copy of
    #: the model: the paged kernel runs under ``shard_map`` over it, heads
    #: local to each ``tensor`` shard (a Mosaic call cannot be auto-partitioned)
    tp_mesh: Any = None
    #: mesh carrying a "sequence" axis for ring/ulysses attention
    sp_mesh: Any = None
    #: remat (jax.checkpoint) decoder blocks during TRAINING forwards: activations
    #: recompute in the backward instead of living in HBM — the standard lever for
    #: bigger batches/longer sequences (mirrors BertConfig.remat)
    remat: bool = False
    #: sparse (mixture-of-experts) variant: every Nth block swaps its dense MLP for
    #: a routed :class:`unionml_tpu.models.moe.MoEMlp` (0 = fully dense). Router
    #: aux losses sow under "intermediates" — fold them into the training loss with
    #: :func:`unionml_tpu.models.moe.collect_aux_losses`.
    moe_every: int = 0
    num_experts: int = 8
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_router_noise: float = 0.0
    #: "gshard" (default) or "a2a" — see :class:`unionml_tpu.models.moe.MoEMlp`.
    #: "a2a" needs ``ep_mesh`` (an "expert" axis, optionally "data"): tokens are
    #: sharded and only routed tokens move, via explicit all-to-alls over ICI.
    moe_dispatch: str = "gshard"
    #: mesh for expert-parallel MoE dispatch (required by moe_dispatch="a2a")
    ep_mesh: Any = None

    @classmethod
    def tiny(cls, **overrides) -> "GPTConfig":
        defaults = dict(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4, max_position_embeddings=128
        )
        defaults.update(overrides)
        return cls(**defaults)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _paged_append_quantized(pool_q, pool_scale, dst, off, vals):
    """(jit-traceable) Single-token decode append into an int8 pool tail block.

    ``dst`` (batch,) pool block per row, ``off`` (batch,) offset inside it,
    ``vals`` (batch, heads, head_dim) the new token's K or V. Monotone-scale
    read-modify-write: a block's per-head scale resets on its first write
    (``off == 0``), afterwards only ever GROWS (``max(old, |token|/127)``), and
    the block's existing int8 content is rescaled only on an actual growth
    event — when the scale is unchanged the ratio is exactly 1.0 and the
    rescale is a bit-exact no-op, so rounding error does not compound across
    appends. Offsets past the write point are zeroed, scrubbing whatever a
    previous owner left in a reused block. Rows retired to the scratch block
    carry sentinel positions with ``off == 0`` (see the paged contract), so
    their collisions write self-consistent garbage to scratch only.
    """
    bs = pool_q.shape[2]
    old_q = pool_q[dst].astype(jnp.float32)  # (batch, heads, bs, hd)
    old_scale = pool_scale[dst]  # (batch, heads, 1, 1)
    vals32 = vals.astype(jnp.float32)[:, :, None, :]  # (batch, heads, 1, hd)
    tok_scale = jnp.max(jnp.abs(vals32), axis=-1, keepdims=True) / 127.0
    fresh = (off == 0)[:, None, None, None]
    eff_old = jnp.where(fresh, 0.0, old_scale)
    new_scale = jnp.maximum(eff_old, tok_scale)
    safe = jnp.where(new_scale > 0, new_scale, 1.0)
    rescaled = jnp.round(old_q * (eff_old / safe))
    tok_q = jnp.round(vals32 / safe)
    slot_idx = jnp.arange(bs)[None, None, :, None]
    off_b = off[:, None, None, None]
    new_q = jnp.where(slot_idx < off_b, rescaled, jnp.where(slot_idx == off_b, tok_q, 0.0))
    new_q = jnp.clip(new_q, -127, 127).astype(jnp.int8)
    return pool_q.at[dst].set(new_q), pool_scale.at[dst].set(new_scale)


def _paged_chunk_quantized(pool_q, pool_scale, table_row, position, vals):
    """(jit-traceable) Batch-1 chunk prefill into an int8 pool.

    ``vals`` (heads, seq, head_dim) is the chunk's K or V for positions
    ``[position, position + seq)``; ``table_row`` (width,) maps logical blocks
    to pool blocks. Touches only the ``ceil(seq/bs) + 1`` blocks the chunk can
    reach from ``position // bs`` (a straddling chunk spans one extra) — blocks
    BEFORE the write range are never read or written, which is what keeps a
    spliced shared prefix intact. The same monotone-scale discipline as the
    decode append applies: the first block may be mid-block (fresh only when
    the chunk starts at its offset 0), later blocks are fresh by construction.
    Logical blocks past the row's table width clamp to the trailing scratch
    column. Positions past the chunk's end are zeroed (stale-content scrub).
    """
    heads, seq, head_dim = vals.shape
    bs = pool_q.shape[2]
    width = table_row.shape[0]
    nb = -(-seq // bs) + 1  # static: touched blocks, incl. the straddle block
    position = jnp.asarray(position, jnp.int32)
    blk_idx = position // bs + jnp.arange(nb, dtype=jnp.int32)
    dst = jnp.take(table_row, jnp.clip(blk_idx, 0, width - 1))
    old_q = pool_q[dst].astype(jnp.float32)  # (nb, heads, bs, hd)
    old_scale = pool_scale[dst]  # (nb, heads, 1, 1)
    gpos = blk_idx[:, None] * bs + jnp.arange(bs)[None, :]  # (nb, bs) logical positions
    rel = gpos - position
    write = ((rel >= 0) & (rel < seq))[:, None, :, None]  # chunk content lands here
    live = (gpos < position + seq)[:, None, :, None]  # beyond: scrub to zero
    chunk = jnp.moveaxis(vals, 1, 0).astype(jnp.float32)  # (seq, heads, hd)
    take = jnp.take(chunk, jnp.clip(rel.reshape(-1), 0, seq - 1), axis=0)
    take = jnp.moveaxis(take.reshape(nb, bs, heads, head_dim), 2, 1)  # (nb, heads, bs, hd)
    fresh = (blk_idx * bs >= position)[:, None, None, None]
    eff_old = jnp.where(fresh, 0.0, old_scale)
    chunk_absmax = jnp.max(
        jnp.abs(jnp.where(write, take, 0.0)), axis=(2, 3), keepdims=True
    )
    new_scale = jnp.maximum(eff_old, chunk_absmax / 127.0)
    safe = jnp.where(new_scale > 0, new_scale, 1.0)
    rescaled = jnp.round(old_q * (eff_old / safe))
    new_q = jnp.where(write, jnp.round(take / safe), rescaled)
    new_q = jnp.clip(jnp.where(live, new_q, 0.0), -127, 127).astype(jnp.int8)
    return pool_q.at[dst].set(new_q), pool_scale.at[dst].set(new_scale)


def _pool_block_size(layer) -> int:
    """Tokens a block holds, from one layer's pool leaves (its joined ``"kv"``
    leaf, or an int8 layer's ``"k"`` codes)."""
    return layer["kv" if "kv" in layer else "k"].shape[2]


def _paged_append_rows(pool_leaf, dst, off, rows):
    """(jit-traceable) Write one token row per head into a full-precision pool
    leaf, in place.

    ``pool_leaf`` is ``(blocks, heads, block_size, width)``; token ``i`` goes
    to offset ``off[i]`` of pool block ``dst[i]`` (both ``(n,)``), ``rows`` is
    ``(n, heads, width)``. The scatter runs through the view ``(blocks, heads *
    block_size, width)`` so that its two indexed axes are leading: a scatter
    with a full slice BETWEEN indexed axes (``.at[dst, :, off, :]``) makes XLA
    re-lay the whole pool out around it, whatever the width (three pool-sized
    copies a leaf a step on a v5e). Heads stay the major part of the merged
    axis, so a pool sharded by heads stays sharded through the view.
    """
    blocks, heads, block_size, width = pool_leaf.shape
    view = pool_leaf.reshape(blocks, heads * block_size, width)
    cols = jnp.arange(heads, dtype=jnp.int32)[None, :] * block_size + off[:, None]
    view = view.at[dst[:, None], cols].set(rows.astype(pool_leaf.dtype))
    return view.reshape(pool_leaf.shape)


def _paged_verify_chunk(cache, block_table, position, q, k, v, out_dtype, impl="auto", mesh=None):
    """(jit-traceable) Speculative verify: attention context for ``S`` chunk
    tokens per row over the row's paged prefix, WITHOUT writing the pool.

    ``q``/``k``/``v`` are ``(batch, heads, S, head_dim)`` fresh projections for
    chunk tokens at per-row positions ``[position, position + S)``. The pool
    leaves in ``cache`` stay untouched — a rejected proposal must never perturb
    the pool, and in the int8 layout even an overwritten junk token would
    permanently inflate a block's monotone absmax scale. Numerics are
    BIT-IDENTICAL to feeding the chunk one token at a time through the decode
    append: each scan step mirrors the append arithmetic
    (:func:`_paged_append_quantized` / :func:`_paged_append_rows`) into a LOCAL
    gathered copy of the row's blocks — ``(batch, width, heads, bs, row)``, the
    pool's own block layout — and attends through
    :func:`unionml_tpu.ops.paged_attention.paged_attention` over an identity
    table, so the verify step runs the SAME per-block arithmetic (same
    ``impl``) vanilla decode runs and accepted tokens score exactly as they
    would have under plain decoding; the engine's commit
    (:func:`paged_commit_chunk`) replays the same appends into the real pool.
    The attention rows serialize over ``S`` (tiny, bandwidth-equal to S vanilla
    steps); the win stays in the dense projections/MLP, which batch all S
    tokens per dispatch.
    """
    batch, heads, S, head_dim = q.shape
    block_size = _pool_block_size(cache)
    width = block_table.shape[1]
    capacity = width * block_size
    quantized = "k_scale" in cache
    b_idx = jnp.arange(batch)
    pos0 = position.astype(jnp.int32)
    # after the flatten below, row b's logical block w is local block b*width+w
    local_table = (b_idx[:, None] * width + jnp.arange(width)[None, :]).astype(jnp.int32)

    def local(leaf):
        # (batch, width, heads, bs, row): the row's blocks, block structure kept
        return leaf[block_table]

    def flat(x):
        # the local state viewed as a (batch*width)-block pool for paged_attention
        return x.reshape((batch * width,) + x.shape[2:])

    if quantized:
        state = (
            local(cache["k"]).astype(jnp.float32), local(cache["k_scale"]),
            local(cache["v"]).astype(jnp.float32), local(cache["v_scale"]),
        )
    else:
        state = flat(local(cache["kv"]))

    def append_q(codes, scales, blk, off, vals):
        # _paged_append_quantized on the gathered layout, arithmetic bit for bit
        # (codes live as exact integers in f32, so round/clip/rescale match)
        old_q = codes[b_idx, blk]  # (batch, heads, bs, hd)
        old_scale = scales[b_idx, blk]
        vals32 = vals.astype(jnp.float32)[:, :, None, :]
        tok_scale = jnp.max(jnp.abs(vals32), axis=-1, keepdims=True) / 127.0
        fresh = (off == 0)[:, None, None, None]
        eff_old = jnp.where(fresh, 0.0, old_scale)
        new_scale = jnp.maximum(eff_old, tok_scale)
        safe = jnp.where(new_scale > 0, new_scale, 1.0)
        rescaled = jnp.round(old_q * (eff_old / safe))
        tok_q = jnp.round(vals32 / safe)
        slot_idx = jnp.arange(block_size)[None, None, :, None]
        off_b = off[:, None, None, None]
        new_q = jnp.where(slot_idx < off_b, rescaled, jnp.where(slot_idx == off_b, tok_q, 0.0))
        new_q = jnp.clip(new_q, -127, 127)
        return codes.at[b_idx, blk].set(new_q), scales.at[b_idx, blk].set(new_scale)

    def step(state, j):
        pos = jnp.clip(pos0 + j, 0, capacity - 1)
        blk, off = pos // block_size, pos % block_size
        kj = jax.lax.dynamic_index_in_dim(k, j, axis=2, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j, axis=2, keepdims=False)
        qj = jax.lax.dynamic_index_in_dim(q, j, axis=2)  # (batch, heads, 1, hd)
        if quantized:
            kc, ks, vc, vs = state
            kc, ks = append_q(kc, ks, blk, off, kj)
            vc, vs = append_q(vc, vs, blk, off, vj)
            state = (kc, ks, vc, vs)
            ctx = paged_attention(
                qj, flat(kc), flat(vc), local_table, pos,
                k_scale=flat(ks), v_scale=flat(vs), out_dtype=out_dtype, impl=impl,
                mesh=mesh,
            )
        else:
            state = _paged_append_rows(
                state, b_idx * width + blk, off, jnp.concatenate([kj, vj], axis=-1)
            )
            ctx = paged_attention(
                qj, state, None, local_table, pos, out_dtype=out_dtype, impl=impl, mesh=mesh,
            )
        return state, ctx[:, :, 0, :]

    _, rows = jax.lax.scan(step, state, jnp.arange(S, dtype=jnp.int32))
    return jnp.moveaxis(rows, 0, 2)  # (batch, heads, S, head_dim)


def paged_commit_chunk(layer_cache, block_table, position, counts, ck, cv):
    """(jit-traceable) Commit the first ``counts[row]`` verified chunk tokens
    of one layer into the paged pool as SEQUENTIAL single-token appends.

    ``ck``/``cv`` are the ``(batch, heads, S, head_dim)`` fresh K/V a verify
    pass stashed (see :func:`_paged_verify_chunk`); row positions start at
    ``position`` (the row's pre-round length). Chunk indices ``j >=
    counts[row]`` — rejected proposals and everything past a retirement — and
    fully inactive rows (``counts == 0``) route through the trailing scratch
    column, so the pool never learns a rejected token existed and the int8
    block-scale trajectory is exactly the one plain decoding would have
    produced for the accepted prefix.
    """
    quantized = "k_scale" in layer_cache
    block_size = _pool_block_size(layer_cache)
    width = block_table.shape[1]
    capacity = width * block_size
    sentinel = (width - 1) * block_size
    S = ck.shape[2]
    pos0 = position.astype(jnp.int32)

    def step(carry, j):
        live = j < counts
        pos = jnp.clip(jnp.where(live, pos0 + j, sentinel), 0, capacity - 1)
        blk, off = pos // block_size, pos % block_size
        dst = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
        kj = jax.lax.dynamic_index_in_dim(ck, j, axis=2, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(cv, j, axis=2, keepdims=False)
        if quantized:
            kq, ks, vq, vs = carry
            kq, ks = _paged_append_quantized(kq, ks, dst, off, kj)
            vq, vs = _paged_append_quantized(vq, vs, dst, off, vj)
            return (kq, ks, vq, vs), None
        return _paged_append_rows(carry, dst, off, jnp.concatenate([kj, vj], axis=-1)), None

    if quantized:
        carry = (
            layer_cache["k"], layer_cache["k_scale"],
            layer_cache["v"], layer_cache["v_scale"],
        )
        (kq, ks, vq, vs), _ = jax.lax.scan(step, carry, jnp.arange(S, dtype=jnp.int32))
        return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    leaf, _ = jax.lax.scan(step, layer_cache["kv"], jnp.arange(S, dtype=jnp.int32))
    return {"kv": leaf}


class DecoderBlock(nn.Module):
    config: GPTConfig
    use_moe: bool = False

    @nn.compact
    def __call__(
        self,
        hidden,
        cache: Optional[Dict[str, jax.Array]],
        position,
        deterministic: bool,
        pad_offsets: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ):
        """Full-sequence (cache=None) or single-token incremental (cache given) step.

        Incremental contract: ``hidden`` is (batch, 1, d); ``cache`` holds
        ``{"k","v"}`` of shape (batch, heads, max_len, head_dim) plus the write
        ``position`` — a scalar (all rows at the same decode step) or a (batch,)
        int vector (continuous batching: each row at its OWN step, writing its own
        cache column; requires seq == 1). ``pad_offsets`` is a (batch,) count of
        LEFT-pad tokens per row (ragged-prompt batching): key positions below a
        row's offset are masked for that row. ``segment_ids`` (batch, seq) selects
        packed-sequence training (cache=None only): causal attention additionally
        confined to same-segment tokens. Returns (hidden, new_cache).

        Paged contract (``block_table`` given): ``cache`` holds the layer's pool
        leaf ``{"kv"}`` of shape (num_blocks, heads, block_size, 2 * head_dim)
        shared by every row — a token's key and value of one head side by side
        in one row (an int8 layer holds ``{"k","v"}`` code leaves (..., head_dim)
        and their scales instead: :func:`init_block_pool`) — and ``block_table``
        is an int32 (batch, width) map from a row's logical block index to its
        pool block. Token position ``p`` lives at block ``table[row, p //
        block_size]``, offset ``p % block_size``. Writes scatter rows into the
        tail block in place (:func:`_paged_append_rows`); reads gather the row's
        table — contiguous logical order, so the mask arithmetic is identical to
        the dense path and outputs match it bitwise (masked columns hit
        exp(-inf)=0 exactly). The engine keeps the last table column pointed at
        a scratch block and encodes retired rows' positions past
        ``(width-1)*block_size``, so their unavoidable scatter lands in scratch,
        never in a reused block.
        """
        cfg = self.config
        batch, seq, _ = hidden.shape
        normed = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="attn_norm")(hidden)
        qkv = nn.Dense(3 * cfg.hidden_size, dtype=cfg.dtype, name="qkv")(normed)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda x: x.reshape(batch, seq, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)

        def pad_mask(k_positions):
            # (batch, 1, 1, Lk): keys in a row's left-pad region contribute nothing
            return (k_positions[None, :] >= pad_offsets[:, None])[:, None, None, :]

        if cache is None:
            if segment_ids is not None:
                if pad_offsets is not None or cfg.attention_impl in ("ring", "ulysses"):
                    raise ValueError(
                        "segment_ids (packed training) composes with neither pad_offsets "
                        "(left-padded ragged batches) nor sequence-parallel attention"
                    )
                context = attention(
                    q, k, v, segment_ids=segment_ids, causal=True, impl=cfg.attention_impl
                )
            elif cfg.attention_impl in ("ring", "ulysses"):
                # sequence-parallel long-context training: activations shard over
                # the mesh's "sequence" axis; causal masking is handled inside
                if pad_offsets is not None:
                    # silently dropping to dense attention would defeat the O(seq/N)
                    # memory the sp layout exists for (and GPT's LEFT padding does
                    # not map onto the kernels' right-padding kv_lens contract)
                    raise ValueError(
                        "ring/ulysses attention does not support pad_offsets (left-padded "
                        "ragged batches); train sequence-parallel configs on uniform-length "
                        "batches or use a dense attention_impl."
                    )
                from unionml_tpu.parallel import sp_attention

                context = sp_attention(q, k, v, cfg.sp_mesh, cfg.attention_impl, causal=True)
            elif pad_offsets is None:
                context = attention(q, k, v, causal=True, impl=cfg.attention_impl)
            else:
                # causal=True supplies the triangular part; only the pad mask is ours
                context = xla_attention(q, k, v, causal=True, mask=pad_mask(jnp.arange(seq)))
            new_cache = None
        elif block_table is not None:
            per_row = not isinstance(position, int) and jnp.ndim(position) == 1
            if pad_offsets is not None:
                raise ValueError("paged decode does not support pad_offsets (left-padded rows)")
            if per_row and seq != 1:
                # speculative verify: score S chunk tokens per row against the
                # row's paged prefix without writing the pool; the engine commits
                # accepted tokens afterwards (paged_commit_chunk) from the fresh
                # K/V stashed alongside the untouched pool leaves
                context = _paged_verify_chunk(
                    cache, block_table, position, q, k, v, cfg.dtype,
                    impl=cfg.paged_attn_impl, mesh=cfg.tp_mesh,
                )
                new_cache = {**cache, "ck": k, "cv": v}
            else:
                # an int8-quantized pool announces itself structurally: scale leaves
                # ride next to its k/v code leaves (see init_block_pool), so
                # skip-listed layers fall through to the full-precision leaf
                # with zero config plumbing
                quantized = "k_scale" in cache
                block_size = _pool_block_size(cache)
                width = block_table.shape[1]
                capacity = width * block_size
                if per_row:
                    # decode: each row appends one token into its own tail block
                    pos = jnp.clip(position.astype(jnp.int32), 0, capacity - 1)
                    blk, off = pos // block_size, pos % block_size
                    dst = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
                    if quantized:
                        k_cache, k_scale = _paged_append_quantized(
                            cache["k"], cache["k_scale"], dst, off, k[:, :, 0, :]
                        )
                        v_cache, v_scale = _paged_append_quantized(
                            cache["v"], cache["v_scale"], dst, off, v[:, :, 0, :]
                        )
                    else:
                        rows = jnp.concatenate([k[:, :, 0, :], v[:, :, 0, :]], axis=-1)
                        kv_cache = _paged_append_rows(cache["kv"], dst, off, rows)
                else:
                    # chunked prefill through the table (batch=1): scatter the chunk's
                    # K/V at positions [position, position+seq) of row 0's blocks
                    if batch != 1:
                        raise ValueError("paged chunk prefill requires batch == 1")
                    if quantized:
                        k_cache, k_scale = _paged_chunk_quantized(
                            cache["k"], cache["k_scale"], block_table[0], position, k[0]
                        )
                        v_cache, v_scale = _paged_chunk_quantized(
                            cache["v"], cache["v_scale"], block_table[0], position, v[0]
                        )
                    else:
                        pos = jnp.clip((position + jnp.arange(seq)).astype(jnp.int32), 0, capacity - 1)
                        blk, off = pos // block_size, pos % block_size
                        dst = jnp.take(block_table[0], blk)
                        rows = jnp.moveaxis(jnp.concatenate([k[0], v[0]], axis=-1), 1, 0)
                        kv_cache = _paged_append_rows(cache["kv"], dst, off, rows)

                # attend through the table: impl="xla" is the historical
                # gather-dequant-attend (bitwise-preserved in
                # ops.paged_attention.xla_paged_attention); "pallas"/"auto"-on-TPU
                # runs the fused kernel that reads the pool where it lies (int8
                # codes + scales, or the joined rows) — no dense gathered copy
                # in HBM. The positional mask is base-position arithmetic either
                # way: query token s of row b sits at base[b] + s.
                if per_row:
                    base = position.astype(jnp.int32)
                else:
                    base = jnp.reshape(jnp.asarray(position, jnp.int32), (1,))
                if quantized:
                    context = paged_attention(
                        q, k_cache, v_cache, block_table, base,
                        k_scale=k_scale, v_scale=v_scale,
                        out_dtype=cfg.dtype, impl=cfg.paged_attn_impl, mesh=cfg.tp_mesh,
                    )
                    new_cache = {"k": k_cache, "v": v_cache, "k_scale": k_scale, "v_scale": v_scale}
                else:
                    context = paged_attention(
                        q, kv_cache, None, block_table, base,
                        out_dtype=cfg.dtype, impl=cfg.paged_attn_impl, mesh=cfg.tp_mesh,
                    )
                    new_cache = {"kv": kv_cache}
        else:
            per_row = not isinstance(position, int) and jnp.ndim(position) == 1
            if per_row and seq != 1:
                raise ValueError("per-row cache positions require single-token decode (seq=1)")
            if per_row:
                # continuous batching: each row writes its next token's K/V at its
                # own column (one scatter; out-of-range rows clamp to the last
                # column, which the engine only allows for finished slots)
                max_cache_len = cache["k"].shape[2]
                cols = jnp.clip(position.astype(jnp.int32), 0, max_cache_len - 1)
                rows = jnp.arange(batch)
                k_cache = cache["k"].at[rows, :, cols, :].set(k[:, :, 0, :].astype(cache["k"].dtype))
                v_cache = cache["v"].at[rows, :, cols, :].set(v[:, :, 0, :].astype(cache["v"].dtype))
            else:
                # write the new K/V block at `position`; works for single-token decode
                # (seq=1) AND chunked prefill (seq=prompt_len, position=0)
                k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, position, 0))
                v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, position, 0))
            if seq > 1 and isinstance(position, int) and position == 0 and pad_offsets is None:
                # start-of-sequence prefill: no earlier keys exist, so plain causal
                # attention over the chunk (the flash kernel on TPU) is exact — no
                # dense mask, no scoring against empty cache slots. Sequence-parallel
                # impls are a TRAINING layout; cache paths fall back to standard
                # (non-sequence-parallel) attention.
                impl = "auto" if cfg.attention_impl in ("ring", "ulysses") else cfg.attention_impl
                context = attention(q, k, v, causal=True, impl=impl)
            elif seq > 1 and isinstance(position, int) and position == 0:
                # ragged prefill: attend over the chunk, causal + left-pad masked
                context = xla_attention(q, k, v, causal=True, mask=pad_mask(jnp.arange(seq)))
            else:
                # decode step / mid-sequence chunk: attend over the cache with a
                # causal mask built from the write position(s) — shared scalar, or
                # per-row columns (continuous batching: each row sees exactly its
                # own [0, position_r] prefix) — plus the left-pad mask when ragged
                k_pos = jnp.arange(k_cache.shape[2])
                if per_row:
                    q_pos = position[:, None] + jnp.arange(seq)[None, :]  # (batch, seq)
                    mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, :, :]
                else:
                    q_pos = position + jnp.arange(seq)
                    mask = (k_pos[None, :] <= q_pos[:, None])[None, None, :, :]
                if pad_offsets is not None:
                    mask = mask & pad_mask(k_pos)
                context = xla_attention(q, k_cache, v_cache, mask=mask)
            new_cache = {"k": k_cache, "v": v_cache}

        context = context.transpose(0, 2, 1, 3).reshape(batch, seq, cfg.hidden_size)
        attn_out = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="attn_out")(context)
        attn_out = nn.Dropout(cfg.dropout)(attn_out, deterministic=deterministic)
        hidden = hidden + attn_out

        normed = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="mlp_norm")(hidden)
        if self.use_moe:
            # deterministic (eval/generate) disables the capacity drop: a trained,
            # imbalanced router must not silently zero overflow tokens at inference,
            # and capacity depends on the per-call token count, which differs
            # between prefill, decode steps, and full forwards
            down = MoEMlp(
                num_experts=cfg.num_experts,
                hidden_size=4 * cfg.hidden_size,
                k=cfg.moe_k,
                capacity_factor=cfg.moe_capacity_factor,
                router_noise=cfg.moe_router_noise,
                dispatch=cfg.moe_dispatch,
                mesh=cfg.ep_mesh,
                dtype=cfg.dtype,
                name="moe_mlp",
            )(normed, dropless=deterministic, deterministic=deterministic)
        else:
            up = nn.Dense(4 * cfg.hidden_size, dtype=cfg.dtype, name="mlp_up")(normed)
            up = nn.gelu(up, approximate=True)
            down = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlp_down")(up)
        down = nn.Dropout(cfg.dropout)(down, deterministic=deterministic)
        return hidden + down, new_cache


class GPTLMHeadModel(nn.Module):
    """Decoder LM: token+position embeddings, N blocks, tied LM head."""

    config: GPTConfig

    def cache_layout(self) -> "KVCacheLayout":
        """What a serving engine asks about this model's cache."""
        return KVCacheLayout(self.config)

    @nn.compact
    def __call__(
        self,
        input_ids,
        cache: Optional[Dict[str, Any]] = None,
        position: Optional[jax.Array] = None,
        deterministic: bool = True,
        pad_offsets: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        logit_rows: Optional[jax.Array] = None,
    ):
        """``pad_offsets`` (batch,) enables ragged-prompt batching: rows are LEFT-
        padded, each row's position embeddings start at its first real token, and
        attention never sees a row's pad region. Requires ``deterministic=True`` on
        sparse configs: capacity-bounded expert dispatch has no row isolation (pad
        tokens would compete for expert capacity slots against real tokens).

        ``segment_ids`` (batch, seq) enables PACKED training (cache=None): several
        short sequences share a row (t5x convention: 0 = padding, positive ids =
        segments), attention is confined to same-segment tokens (flash-kernel
        blockwise masking — no dense (seq, seq) mask), and position embeddings
        restart at each segment start. See :func:`unionml_tpu.ops.packing.pack_sequences`.

        A ``cache`` carrying a ``"table"`` key selects PAGED decoding: the layer
        entries are shared block-pool leaves (see :func:`init_block_pool`) and
        ``cache["table"]`` is the int32 (batch, width) block table every layer
        reads/writes through (one table, all layers — the pool is per-layer, the
        logical layout is not). The table rides through ``new_cache`` unchanged.

        ``logit_rows`` (batch,) names the one position a row whose logits the
        caller reads (a prefill reads its prompt's last token): the head then
        runs over those alone and the logits are (batch, 1, vocab).
        """
        cfg = self.config
        if pad_offsets is not None and cfg.moe_every > 0 and not deterministic:
            raise ValueError(
                "pad_offsets with a MoE config requires deterministic=True: "
                "capacity-bounded expert dispatch lets pad tokens evict real tokens."
            )
        if segment_ids is not None and cache is not None:
            raise ValueError("segment_ids is a packed-TRAINING feature; decode caches are unpacked")
        batch, seq = input_ids.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="wte")
        if segment_ids is not None:
            # positions restart at each segment boundary: subtract the running
            # index of the latest boundary (cummax of boundary positions)
            idx = jnp.arange(seq, dtype=jnp.int32)[None, :]
            ids = segment_ids.astype(jnp.int32)
            change = jnp.concatenate(
                [jnp.ones((batch, 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1
            )
            seg_start = jax.lax.cummax(jnp.where(change, idx, 0), axis=1)
            positions = idx - seg_start
        elif cache is None:
            positions = jnp.arange(seq)[None, :]
        elif not isinstance(position, int) and jnp.ndim(position) == 1:
            # per-row decode positions (continuous batching)
            positions = (position[:, None] + jnp.arange(seq)[None, :]).astype(jnp.int32)
            positions = jnp.clip(positions, 0, cfg.max_position_embeddings - 1)
        else:
            positions = (position + jnp.arange(seq))[None, :].astype(jnp.int32)
        if pad_offsets is not None:
            # each row's first REAL token gets position 0 (pad slots clamp to 0 —
            # they are masked out of attention, the embedding just needs to be valid)
            positions = jnp.maximum(positions - pad_offsets[:, None].astype(jnp.int32), 0)
        hidden = embed(input_ids) + nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, dtype=cfg.dtype, name="wpe"
        )(positions)
        hidden = nn.Dropout(cfg.dropout)(hidden, deterministic=deterministic)

        new_cache: Dict[str, Any] = {}
        block_table = cache.get("table") if cache is not None else None
        block_cls = DecoderBlock
        if cfg.remat and cache is None:
            # training forwards only: decode steps are tiny and cache-carrying
            # (deterministic is arg 4 counting self; it steers python control flow)
            block_cls = nn.remat(DecoderBlock, static_argnums=(4,))
        for i in range(cfg.num_layers):
            layer_cache = None if cache is None else cache[f"layer_{i}"]
            use_moe = cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0
            hidden, layer_cache = block_cls(cfg, use_moe=use_moe, name=f"layer_{i}")(
                hidden, layer_cache, position, deterministic, pad_offsets, segment_ids,
                block_table,
            )
            if layer_cache is not None:
                new_cache[f"layer_{i}"] = layer_cache
        if block_table is not None:
            new_cache["table"] = block_table

        if logit_rows is not None:
            hidden = jnp.take_along_axis(hidden, logit_rows.astype(jnp.int32)[:, None, None], axis=1)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="final_norm")(hidden)
        # tied head with genuinely-f32 logits: Embed.attend would promote back to the
        # compute dtype (bf16), costing mantissa over a large vocab
        logits = jnp.dot(
            hidden.astype(jnp.float32),
            embed.embedding.astype(jnp.float32).T,
            preferred_element_type=jnp.float32,
        )
        return (logits, new_cache) if cache is not None else logits


def init_cache(
    config: GPTConfig, batch: int, max_len: Optional[int] = None, dtype: Any = None
) -> Dict[str, Any]:
    """Zeroed KV cache pytree for incremental decoding (config's compute dtype)."""
    max_len = max_len or config.max_position_embeddings
    dtype = dtype if dtype is not None else config.dtype
    shape = (batch, config.num_heads, max_len, config.head_dim)
    return {
        f"layer_{i}": {
            "k": jnp.zeros(shape, dtype=dtype),
            "v": jnp.zeros(shape, dtype=dtype),
        }
        for i in range(config.num_layers)
    }


def kv_cache_spec(config: GPTConfig, mesh_axis_names: Tuple[str, ...]) -> Any:
    """PartitionSpec for KV-cache leaves ``(batch, heads, max_len, head_dim)``.

    Serving shards the cache over attention HEADS on the ``tensor`` axis — the
    same split :func:`param_shardings` gives the fused qkv kernel, so each
    device holds exactly the K/V rows its attention shards produce and the
    decode step runs without resharding the cache. Heads stay replicated when
    the ``tensor`` axis is absent or does not divide the head count (a
    wrong-divisor shard would silently pad heads).
    """
    from jax.sharding import PartitionSpec as P

    from unionml_tpu.parallel.mesh import TENSOR_AXIS

    tensor = TENSOR_AXIS if TENSOR_AXIS in mesh_axis_names else None
    return P(None, tensor, None, None)


def init_block_pool(
    config: GPTConfig,
    num_blocks: int,
    block_size: int,
    dtype: Any = None,
    kv_quantize: Optional[str] = None,
    kv_quantize_skip_layers: Tuple[int, ...] = (),
) -> Dict[str, Any]:
    """Zeroed KV block pool: one leaf a layer, ``"kv"``, ``(num_blocks, heads,
    block_size, 2 * head_dim)`` — the paged engine's only KV storage and the
    prefix cache's reuse store.

    A token's key and value of one head lie side by side in one row: key in
    columns ``[:head_dim]``, value in ``[head_dim:]``. At GPT-2's ``head_dim``
    of 64 (small to XL) a row is exactly the TPU's 128 lanes, which is what
    lets XLA append to the leaf in place and hand it to the Mosaic kernel where
    it lies: a leaf whose rows are not a multiple of 128 lanes is re-laid-out
    around every scatter and call (a whole-pool copy each), and stays correct.
    The writers keep their indexed axes leading for the same reason
    (:func:`_paged_append_rows`; block-wise ``.at[block_ids].set``).

    Heads sit on the same axis as :func:`init_cache` leaves, so the pool shards
    with the identical head-sharded spec (:func:`kv_block_spec`) and pool↔slot
    copies stay shard-local on a mesh (gather/scatter over the unsharded block
    axis only).

    ``kv_quantize="int8"`` stores K and V as symmetric int8 code leaves
    ``"k"``/``"v"`` ``(blocks, heads, block_size, head_dim)`` with
    per-block-per-head f32 scales resident alongside (``k_scale``/``v_scale``,
    shape ``(blocks, heads, 1, 1)`` — rank-4 so the one head-sharded spec covers
    every leaf and scale gathers stay shard-local). Layers listed in
    ``kv_quantize_skip_layers`` keep the full-precision ``"kv"`` leaf (no scale
    entries) — the attention layer detects the mode structurally per layer, so
    mixed pools need no extra plumbing. (The int8 leaves are 64 lanes wide and
    their block re-quantising append is no row scatter: XLA still copies them.)
    """
    dtype = dtype if dtype is not None else config.dtype
    if kv_quantize not in (None, "int8"):
        raise ValueError(f"kv_quantize must be None or 'int8', got {kv_quantize!r}")
    skip = frozenset(int(i) for i in kv_quantize_skip_layers)
    code_shape = (num_blocks, config.num_heads, block_size, config.head_dim)
    scale_shape = (num_blocks, config.num_heads, 1, 1)
    pool: Dict[str, Any] = {}
    for i in range(config.num_layers):
        if kv_quantize == "int8" and i not in skip:
            pool[f"layer_{i}"] = {
                "k": jnp.zeros(code_shape, dtype=jnp.int8),
                "v": jnp.zeros(code_shape, dtype=jnp.int8),
                "k_scale": jnp.zeros(scale_shape, dtype=jnp.float32),
                "v_scale": jnp.zeros(scale_shape, dtype=jnp.float32),
            }
        else:
            pool[f"layer_{i}"] = {"kv": jnp.zeros(code_shape[:3] + (2 * config.head_dim,), dtype=dtype)}
    return pool


def kv_block_bytes(
    config: GPTConfig,
    block_size: int,
    dtype: Any = None,
    kv_quantize: Optional[str] = None,
    kv_quantize_skip_layers: Tuple[int, ...] = (),
) -> int:
    """Bytes one pool block costs across ALL layers under the given layout —
    the unit of the equal-KV-byte comparison of an int8 pool with a bf16 one
    (``test_int8_equal_byte_pool_doubles_capacity_and_reports_it``) and of pool
    sizing: ``pool_bytes = kv_block_bytes(...) * num_blocks``."""
    dtype = dtype if dtype is not None else config.dtype
    full_itemsize = jnp.dtype(dtype).itemsize
    per_head = block_size * config.head_dim
    skip = frozenset(int(i) for i in kv_quantize_skip_layers)
    total = 0
    for i in range(config.num_layers):
        if kv_quantize == "int8" and i not in skip:
            # int8 k + v, plus one f32 scale each per head
            total += config.num_heads * (2 * per_head * 1 + 2 * 4)
        else:
            # the joined leaf: a key and a value a row
            total += config.num_heads * 2 * per_head * full_itemsize
    return total


def kv_pool_bytes(pool: Dict[str, Any], dense_dtype: Any) -> Tuple[int, int]:
    """(bytes_as_stored, bytes_if_full_precision) of a block pool, from shapes
    only (no device sync). The second number prices the same K/V positions at
    ``dense_dtype`` with no scale arrays — what the capacity doubling is
    measured against on dashboards."""
    stored = full = 0
    for layer in pool.values():
        for name, leaf in layer.items():
            stored += leaf.size * jnp.dtype(leaf.dtype).itemsize
            if not name.endswith("_scale"):
                full += leaf.size * jnp.dtype(dense_dtype).itemsize
    return stored, full


class KVCacheLayout:
    """A model's cache as a serving engine sees it: per-head keys and values,
    the functions above behind the names every layout answers to (the latent
    layout of :mod:`unionml_tpu.models.latent_moe` is the other one). The
    engine takes its dense cache, its block pool, their sharding, their bytes
    and the paged kernel's shape key from ``model.cache_layout()``; tables,
    scatters and gathers are the engine's own and work on any layout whose
    leaves are ``(rows | blocks, heads, tokens, dim)``.

    The dense cache (a slot's rows, a prefill's workspace) keeps a ``"k"`` and a
    ``"v"`` leaf a layer; a full-precision pool holds them joined in one
    ``"kv"`` leaf (:func:`init_block_pool`). :meth:`join` and :meth:`split`
    carry a tree from the one naming to the other, whatever its leading axes."""

    #: what a slot keeps beside its blocks under the table (a layout with
    #: recurrent state or a window ring names them: the engine then refuses, by
    #: these names, what moves blocks and nothing else): nothing here
    slot_state: Tuple[str, ...] = ()
    #: layers that a prefill runs for the position it reads alone: none
    tail_layers = 0

    def __init__(self, config: GPTConfig) -> None:
        self.config = config
        #: heads of a cache leaf: what a ``tensor`` mesh axis has to divide
        self.kv_heads = config.num_heads
        #: ``(heads, last dimension)`` of the paged kernel's call over the
        #: joined leaf (an int8 layer's call is ``head_dim`` wide)
        self.kernel_key = (config.num_heads, 2 * config.head_dim)

    def join(self, cache: Dict[str, Any]) -> Dict[str, Any]:
        """Dense-cache layers ``{"k", "v"}`` (..., head_dim) as the pool's
        ``{"kv"}`` (..., 2 * head_dim): each key beside its value."""
        return {
            name: {"kv": jnp.concatenate([layer["k"], layer["v"]], axis=-1)}
            for name, layer in cache.items()
        }

    def split(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        """The inverse of :meth:`join`: pool-named layers back to ``{"k", "v"}``."""
        head_dim = self.config.head_dim
        return {
            name: {"k": layer["kv"][..., :head_dim], "v": layer["kv"][..., head_dim:]}
            for name, layer in tree.items()
        }

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        return init_cache(self.config, batch, max_len)

    def init_block_pool(
        self, num_blocks: int, block_size: int, kv_quantize: Optional[str] = None,
        kv_quantize_skip_layers: Tuple[int, ...] = (), num_slots: Optional[int] = None,
    ) -> Dict[str, Any]:
        """``num_slots`` sizes per-slot state, of which this layout has none."""
        return init_block_pool(
            self.config, num_blocks, block_size, kv_quantize=kv_quantize,
            kv_quantize_skip_layers=kv_quantize_skip_layers,
        )

    def paged(self, pool: Dict[str, Any]) -> Dict[str, Any]:
        """Of a pool, the layers whose blocks lie under the slots' table: one
        group, every layer."""
        return pool

    def insert_slot_state(self, pool, local_cache, slots, lengths):
        """(jit-traceable) A prefill's per-slot state into ``slots``: none to write."""
        return pool

    def slot_bytes(self, block_size: int) -> Dict[str, int]:
        """Bytes a slot holds beside its blocks under the table, whatever its length."""
        return {"state": 0, "ring": 0}

    def cache_spec(self, mesh_axis_names: Tuple[str, ...]) -> Any:
        return kv_cache_spec(self.config, mesh_axis_names)

    def block_bytes(
        self, block_size: int, kv_quantize: Optional[str] = None,
        kv_quantize_skip_layers: Tuple[int, ...] = (),
    ) -> int:
        return kv_block_bytes(
            self.config, block_size, kv_quantize=kv_quantize,
            kv_quantize_skip_layers=kv_quantize_skip_layers,
        )

    def pool_bytes(self, pool: Dict[str, Any]) -> Tuple[int, int]:
        return kv_pool_bytes(pool, self.config.dtype)


def init_slot_state(num_slots: int) -> Tuple[jax.Array, jax.Array]:
    """Zeroed device-resident slot lifecycle state for the serving engine.

    ``(active, remaining)`` — a bool activity mask and an int32 token budget per
    decode slot. The serving engine keeps these ON DEVICE and updates them
    *inside* the compiled decode step (:func:`advance_slot_state`), so a next
    step can be dispatched before the previous step's tokens are fetched: the
    host never has to round-trip slot lifecycle between device steps.
    """
    return (
        jnp.zeros((num_slots,), dtype=jnp.bool_),
        jnp.zeros((num_slots,), dtype=jnp.int32),
    )


def advance_slot_state(
    active: jax.Array,
    remaining: jax.Array,
    new_lens: jax.Array,
    tokens: jax.Array,
    max_len: int,
    eos_token_id: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(jit-traceable) One decode step's slot retirement, the device-side rule.

    Mirrors the host's per-token accounting exactly — budget exhausted, cache
    room (``max_len - 1``) reached, or ``eos_token_id`` decoded — so a step
    program carrying ``(active, remaining)`` retires slots identically to a
    host replaying the fetched tokens. Inactive rows pass through unchanged.
    """
    new_remaining = jnp.where(active, remaining - 1, remaining)
    finished = (new_remaining <= 0) | (new_lens >= max_len - 1)
    if eos_token_id is not None:
        finished = finished | (tokens == eos_token_id)
    return active & ~finished, new_remaining


def block_table_width(max_len: int, block_size: int) -> int:
    """Columns in a slot's block-table row: ``ceil(max_len / block_size)`` data
    blocks plus one trailing scratch column (always mapped to the engine's
    scratch block) that absorbs the masked scatter of retired rows."""
    return -(-max_len // block_size) + 1


def init_block_tables(
    num_slots: int, max_len: int, block_size: int, scratch_id: int
) -> jax.Array:
    """int32 ``(num_slots, width)`` block tables, every entry on the scratch
    block: a fresh table maps nothing, and any write through it lands in
    scratch. See :func:`block_table_width` for the trailing scratch column."""
    width = block_table_width(max_len, block_size)
    return jnp.full((num_slots, width), scratch_id, dtype=jnp.int32)


def kv_block_spec(config: GPTConfig, mesh_axis_names: Tuple[str, ...]) -> Any:
    """PartitionSpec for KV block-pool leaves ``(blocks, heads, block_size,
    row)``: heads on ``tensor``, exactly like :func:`kv_cache_spec`, so
    restoring a pool block into a slot's cache rows never reshards."""
    return kv_cache_spec(config, mesh_axis_names)


def gather_block_prefix(pool: Dict[str, Any], block_ids: jax.Array, pad_len: int) -> Dict[str, Any]:
    """(jit-traceable) Gather pool blocks into a batch-1 cache holding the prefix.

    ``block_ids`` is ``(n,)``; the result is a pytree of ``(1, heads, pad_len,
    row)`` leaves, named and as wide as the pool's (a layout's ``split`` makes a
    dense cache of them), whose first ``n * block_size`` columns are the
    gathered blocks in order (the rest zero, to be written by the suffix
    prefill). The gather indexes the unsharded block axis, so under a
    head-sharded mesh layout the copy is shard-local.
    """

    def gather(leaf):
        blocks = leaf[block_ids]  # (n, heads, block_size, row)
        n, heads, block_size, row = blocks.shape
        prefix = jnp.moveaxis(blocks, 0, 1).reshape(heads, n * block_size, row)
        out = jnp.zeros((1, heads, pad_len, row), leaf.dtype)
        return out.at[0, :, : n * block_size, :].set(prefix)

    return jax.tree_util.tree_map(gather, pool)


def slice_cache_blocks(
    cache: Dict[str, Any], row: jax.Array, start_block: jax.Array, num_blocks: int, block_size: int
) -> Dict[str, Any]:
    """(jit-traceable) Slice blocks ``[start, start + num_blocks)`` of one cache
    row into block order ``(num_blocks, heads, block_size, head_dim)`` per leaf
    (a layout's ``join`` makes pool leaves of them).

    ``row`` and ``start_block`` may be traced scalars (one compile per
    ``num_blocks`` count, not per slot or offset); the slice covers cache
    columns ``[start_block * block_size, (start_block + num_blocks) * block_size)``.
    """

    def take(leaf):
        r = leaf[row]  # (heads, max_len, head_dim)
        heads, _, head_dim = r.shape
        src = jax.lax.dynamic_slice_in_dim(
            r, start_block * block_size, num_blocks * block_size, axis=1
        )
        return jnp.moveaxis(src.reshape(heads, num_blocks, block_size, head_dim), 1, 0)

    return jax.tree_util.tree_map(take, cache)


def generate(
    model: GPTLMHeadModel,
    variables: Any,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    prompt_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive decoding with a KV cache; one compiled scan, O(1) per token.

    ``temperature=0`` is greedy; otherwise samples with the given temperature,
    optionally filtered by ``top_k`` (0 = off) / ``top_p`` (1.0 = off) — same
    semantics as :mod:`unionml_tpu.ops.sampling` and the serving engine.
    ``prompt_mask`` (batch, prompt_len; 1 = real token) batches RAGGED prompts:
    rows must be LEFT-padded, so shorter prompts carry leading pad tokens that
    attention ignores and position embeddings skip — each row decodes exactly as it
    would alone. Returns (batch, prompt_len + max_new_tokens) token ids.
    """
    config = model.config
    batch, prompt_len = prompt_ids.shape
    total_len = prompt_len + max_new_tokens
    max_len = max_len or total_len
    # silent clamping here would corrupt the KV write slot and the position gather:
    # reject out-of-range requests loudly instead
    if total_len > max_len:
        raise ValueError(
            f"prompt_len + max_new_tokens ({total_len}) exceeds max_len ({max_len})"
        )
    if max_len > config.max_position_embeddings:
        raise ValueError(
            f"max_len ({max_len}) exceeds max_position_embeddings ({config.max_position_embeddings})"
        )
    from unionml_tpu.ops.sampling import validate_sampling

    temperature, top_k, top_p = validate_sampling(temperature, top_k, top_p)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    pad_offsets = None
    if prompt_mask is not None:
        # left padding means each row's pad count is its number of leading zeros
        pad_offsets = (prompt_len - jnp.sum(prompt_mask.astype(jnp.int32), axis=1)).astype(jnp.int32)

    cache = init_cache(config, batch, max_len)

    # chunked prefill: one forward over the whole prompt fills every layer's cache
    logits, cache = model.apply(
        variables, prompt_ids, cache=cache, position=0, pad_offsets=pad_offsets
    )
    last_logits = logits[:, -1, :]

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        from unionml_tpu.ops.sampling import sample_logits

        rows = logits.shape[0]
        # statically-disabled filters pass None: sample_logits skips them, so
        # temperature-only sampling stays a plain categorical (no vocab sorts)
        return sample_logits(
            logits,
            key,
            jnp.full((rows,), temperature, jnp.float32),
            jnp.full((rows,), top_k, jnp.int32) if top_k > 0 else None,
            jnp.full((rows,), top_p, jnp.float32) if top_p < 1.0 else None,
        )

    def decode_step(carry, t):
        cache, logits, key = carry
        key, subkey = jax.random.split(key)
        token = sample(logits, subkey)
        new_logits, cache = model.apply(
            variables, token[:, None], cache=cache, position=prompt_len + t, pad_offsets=pad_offsets
        )
        return (cache, new_logits[:, -1, :], key), token

    (_, _, _), tokens = jax.lax.scan(
        decode_step, (cache, last_logits, rng), jnp.arange(max_new_tokens)
    )
    return jnp.concatenate([prompt_ids, tokens.T], axis=1)


def init_params(config: GPTConfig, rng: Optional[jax.Array] = None, seq_len: int = 32) -> Any:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = GPTLMHeadModel(config)
    return model.init({"params": rng}, jnp.zeros((1, seq_len), dtype=jnp.int32), deterministic=True)


def lm_loss(
    logits: jax.Array,
    input_ids: jax.Array,
    mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token cross-entropy: logits at t predict input_ids at t+1 (padding masked).

    With ``segment_ids`` (packed batches), cross-segment transitions are masked
    too: the last token of one packed sequence must not be trained to predict the
    first token of the next.
    """
    from unionml_tpu.ops.losses import cross_entropy_with_integer_labels

    shifted_logits = logits[:, :-1, :]
    targets = input_ids[:, 1:]
    weights = None if mask is None else mask[:, 1:]
    if segment_ids is not None:
        same_segment = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (segment_ids[:, 1:] > 0)
        seg_weights = same_segment.astype(shifted_logits.dtype)
        weights = seg_weights if weights is None else weights * seg_weights
    return cross_entropy_with_integer_labels(shifted_logits, targets, weights)


def param_shardings(params: Any, mesh_axis_names: Tuple[str, ...] = ("data", "tensor")) -> Any:
    """PartitionSpec tree for the GPT parameter pytree (Megatron-style split).

    Mirrors :func:`unionml_tpu.models.bert.param_shardings` for the decoder family:

    - fused qkv kernel and MLP up-projection: shard the OUTPUT dim over ``tensor``
    - attention output and MLP down-projection: shard the INPUT dim over ``tensor``
    - token/position embeddings: shard the vocab/position dim over ``tensor``
    - MoE expert kernels (E, d, h)/(E, h, d): expert dim over ``expert`` when that
      axis exists, inner dims Megatron-split like the dense MLP
    - everything else replicated, or FSDP-sharded over ``fsdp`` when present
    - :class:`~unionml_tpu.ops.quant.QuantizedArray` leaves (weight-only int8):
      the int8 payload takes the kernel's spec; the scale keeps only the axes
      where it has extent (the channel axis), so it co-shards with the payload's
      output columns and the ``q * scale`` dequant runs without resharding

    XLA inserts the matching all-reduces over ICI; nothing else is needed.
    """
    from jax.sharding import PartitionSpec as P

    from unionml_tpu.ops.quant import QuantizedArray
    from unionml_tpu.parallel.ep import EXPERT_AXIS
    from unionml_tpu.parallel.mesh import FSDP_AXIS, TENSOR_AXIS

    tensor = TENSOR_AXIS if TENSOR_AXIS in mesh_axis_names else None
    fsdp = FSDP_AXIS if FSDP_AXIS in mesh_axis_names else None
    expert = EXPERT_AXIS if EXPERT_AXIS in mesh_axis_names else None

    def dense_spec(path_str: str, leaf) -> P:
        ndim = getattr(leaf, "ndim", 0)
        if "w_in" in path_str and ndim == 3:
            return P(expert, fsdp, tensor)
        if "w_out" in path_str and ndim == 3:
            return P(expert, tensor, fsdp)
        if ndim < 2:
            return P()
        if ("wte" in path_str or "wpe" in path_str) and "embedding" in path_str:
            return P(tensor, None)
        if ("qkv" in path_str or "mlp_up" in path_str) and path_str.endswith("kernel"):
            return P(fsdp, tensor)
        if ("attn_out" in path_str or "mlp_down" in path_str) and path_str.endswith("kernel"):
            return P(tensor, fsdp)
        if path_str.endswith("kernel"):
            return P(fsdp, None)
        return P()

    def spec_for(path: Tuple[str, ...], leaf):
        path_str = "/".join(str(p) for p in path)
        if isinstance(leaf, QuantizedArray):
            base = dense_spec(path_str, leaf.q)
            entries = tuple(base) + (None,) * (leaf.q.ndim - len(tuple(base)))
            scale_spec = P(
                *(
                    axis if i < leaf.scale.ndim and leaf.scale.shape[i] > 1 else None
                    for i, axis in enumerate(entries)
                )
            )
            # a spec-valued QuantizedArray node: same treedef (incl. dtype aux)
            # as the params node, so device_put/with_sharding_constraint zip them
            return QuantizedArray(q=base, scale=scale_spec, dtype=leaf.dtype)
        return dense_spec(path_str, leaf)

    from unionml_tpu.models._sharding import shard_by_rules

    return shard_by_rules(
        params, spec_for, is_leaf=lambda leaf: isinstance(leaf, QuantizedArray)
    )


def import_hf_weights(hf_state_dict: Dict[str, Any], config: GPTConfig) -> Dict[str, Any]:
    """Map a HuggingFace GPT-2 state dict (torch tensors or numpy) onto this module.

    Accepts ``GPT2Model`` or ``GPT2LMHeadModel`` state dicts. HF GPT-2 uses Conv1D
    projections whose weights are already (in, out) — no transpose, unlike torch
    Linear — and ties the LM head to ``wte``, matching this module's tied head.
    Mirrors :func:`unionml_tpu.models.bert.import_hf_weights` for the encoder family.
    """

    if config.moe_every > 0:
        raise ValueError(
            "import_hf_weights supports dense GPT-2 checkpoints only: a sparse config "
            "(moe_every > 0) has expert parameters with no HF counterpart."
        )

    def t(name: str) -> np.ndarray:
        value = hf_state_dict[name]
        if hasattr(value, "detach"):
            value = value.detach().cpu().numpy()
        return np.asarray(value)

    def conv1d(prefix: str) -> Dict[str, np.ndarray]:
        # HF Conv1D stores weight as (in_features, out_features): flax kernel layout
        return {"kernel": t(f"{prefix}.weight"), "bias": t(f"{prefix}.bias")}

    def norm(prefix: str) -> Dict[str, np.ndarray]:
        return {"scale": t(f"{prefix}.weight"), "bias": t(f"{prefix}.bias")}

    prefix = "transformer." if any(key.startswith("transformer.") for key in hf_state_dict) else ""
    params: Dict[str, Any] = {
        "wte": {"embedding": t(f"{prefix}wte.weight")},
        "wpe": {"embedding": t(f"{prefix}wpe.weight")},
        "final_norm": norm(f"{prefix}ln_f"),
    }
    for i in range(config.num_layers):
        hf_layer = f"{prefix}h.{i}"
        params[f"layer_{i}"] = {
            "attn_norm": norm(f"{hf_layer}.ln_1"),
            "qkv": conv1d(f"{hf_layer}.attn.c_attn"),
            "attn_out": conv1d(f"{hf_layer}.attn.c_proj"),
            "mlp_norm": norm(f"{hf_layer}.ln_2"),
            "mlp_up": conv1d(f"{hf_layer}.mlp.c_fc"),
            "mlp_down": conv1d(f"{hf_layer}.mlp.c_proj"),
        }
    return {"params": params}
