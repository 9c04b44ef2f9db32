"""A hybrid decoder of state-space, window, full and cross-attention layers with
gated memory units and differential attention (SambaY, arXiv:2507.06607, as
``microsoft/Phi-4-mini-flash-reasoning`` publishes it), served by
:class:`~unionml_tpu.serving.continuous.DecodeEngine` through the incremental
and paged contract of :mod:`unionml_tpu.models.gpt` (``cache=``, ``position=``,
``cache["table"]``, ``logit_rows``).

Every layer ``i`` of ``L``: ``h = x + Mixer_i(LN(x)); out = h + W2 (SiLU(g) *
u)`` with ``[g ; u] = LN'(h) W1``; a final LayerNorm and the tied embedding as
head, logits float32; no positional encoding. The mixers (:func:`layer_kind`):

========================  ==================================================
even ``i <= L/2``         Mamba-1 (:mod:`unionml_tpu.ops.ssm`); layer ``L/2``
                          also gives the memory ``m = y``, before its gate
odd ``i < L/2``           differential attention over the last ``window`` keys
``L/2 + 1``               differential attention over all keys: its keys and
                          values are the model's one full cache
even ``i > L/2 + 1``      gated memory unit ``(m * SiLU(LN(x) W_a)) W_b``
odd ``i > L/2 + 1``       differential cross attention: a query projection,
                          lambdas and sub-norm of its own over layer ``L/2 +
                          1``'s keys and values
========================  ==================================================

**Differential attention** (arXiv:2410.05258; layer index ``l``, head size
``d``). Query heads ``2h, 2h+1`` are the pair ``q1_h, q2_h``; key heads ``2g,
2g+1`` are ``k1_g, k2_g`` and value heads ``2g, 2g+1`` joined are ``V_g``; pair
``h`` uses group ``g = h // 2``. ``A1 = softmax(q1 k1^T / sqrt(d)) V``, ``A2``
alike; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``O_h = (1 - lambda_init)
RMSNorm(A1 - lambda A2)``; output ``concat_h(O_h) W_o``. A cache row of a key
group is ``[k1 | k2 | v1 | v2]`` (four head sizes: 256 lanes at ``d`` 64), and
a group's four query heads, zero-padded to ``[q1 | 0]`` and ``[0 | q2]``, are
four query heads of ``2 d`` over one key head whose row is ``[key | value]``
with ``sm_scale = d ** -0.5``: the joined-leaf call of
:func:`unionml_tpu.ops.paged_attention.paged_attention`.

**What the cache is** (:class:`HybridCacheLayout`; the grown ``cache_layout()``
contract, which the two other layouts answer with one group and no state):

- layer ``L/2 + 1``: one leaf ``"kv"`` of blocks under the slots' table, as the
  other models' layers have, allocated by need;
- the window layers: a leaf ``"kv"`` of ``ring_blocks`` blocks a slot
  (``ceil(window / block) + 1``) used as a ring, whatever the slot's length:
  position ``p`` lies in the slot's ring block ``(p // block) % ring_blocks``.
  The blocks of slot ``s`` are ``s * ring_blocks ..``: a table of its own that
  never changes and is reckoned where it is needed (:func:`ring_view`);
- the Mamba layers: per-slot leaves that are not paged, ``"ssm"`` ``(slots,
  d_state, d_inner)`` float32 and ``"conv"`` ``(slots, d_conv - 1, d_inner)``;
- the gated memory units and the cross layers: no leaf. The memory is the same
  token's, and the cross layers read layer ``L/2 + 1``'s leaf.

**Four ways through the model**, told apart by ``cache`` and ``position``:
``cache=None``, the whole sequence from empty state; a dense workspace from
:meth:`HybridCacheLayout.init_cache` with ``position=0``, a bucket prefill
whose state and keys the engine then writes to the slots
(:meth:`HybridCacheLayout.insert_slot_state`); ``cache["table"]`` with a scalar
``position``, one chunk of one slot's prompt (``cache["slots"]`` names the
slot) from the state and the ring as the chunk before left them, a chunk at
position 0 from empty state; ``cache["table"]`` with per-row positions, a
decode step of every slot, in place, in which a row whose position is the
engine's sentinel (a retired or reserved slot) keeps its state and writes its
keys to scratch. With a cache, ``logit_rows`` also says where a row's real
tokens end: what follows position ``logit_rows`` is bucket padding and enters
neither the recurrent state nor the convolution's tail. And the layers after
``L/2 + 1`` run for ``logit_rows`` alone: a prompt position's output there is
read by nothing (the architecture's linear prefill).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unionml_tpu.models.gpt import _paged_append_rows, kv_pool_bytes
from unionml_tpu.ops.paged_attention import paged_attention
from unionml_tpu.ops.ssm import causal_conv1d, selective_scan, selective_step

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    #: ``"auto"`` | ``"pallas"`` | ``"xla"``: see :mod:`unionml_tpu.ops.paged_attention`
    paged_attn_impl: str = "auto"
    #: the same three for the decode step's state update (:mod:`unionml_tpu.ops.ssm`)
    ssm_impl: str = "auto"
    #: run the kernels under the Pallas interpreter (CPU tests of the kernel arms)
    interpret: bool = False

    @classmethod
    def tiny(cls, **overrides) -> "Phi4FlashConfig":
        base = dict(
            vocab_size=256, hidden_size=32, num_layers=8, num_heads=8, num_kv_heads=4,
            intermediate_size=48, sliding_window=8, d_state=4, max_position_embeddings=256,
            dtype=jnp.float32,
        )
        base.update(overrides)
        return cls(**base)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @property
    def groups(self) -> int:
        """Key groups: a pair of key heads and the pair of value heads beside them."""
        return self.num_kv_heads // 2

    @property
    def full_layer(self) -> int:
        """The one layer whose keys and values every later attention reads."""
        return self.num_layers // 2 + 1


def layer_kind(i: int, layers: int) -> str:
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross`` for layer ``i`` of ``layers``."""
    half = layers // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    if i < half:
        return "window"
    return "full" if i == half + 1 else "cross"


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def ring_blocks(window: int, block_size: int) -> int:
    """Blocks a slot's ring holds: enough that the last ``window`` keys are all
    resident whatever block the newest lies in."""
    return -(-window // block_size) + 1


def ring_view(position, live, slots, window: int, block_size: int, scratch: int):
    """A row's ring as :func:`paged_attention` takes it: ``(table (rows, ring
    blocks), base (rows,), block, offset)`` for rows whose newest key lies at
    ``position``. The table is the slot's ring blocks ROTATED so that its first
    column is the block that holds the window's first key, and ``base`` is the
    newest key's position counted from that block's first key: logical order,
    so the kernel's positional mask and its window are plain arithmetic, and
    its walk starts at the window's first live tile. ``block`` and ``offset``
    say where the newest key is written. A row that is not ``live`` gets the
    scratch block for all of it."""
    count = ring_blocks(window, block_size)
    position = position.astype(jnp.int32)
    first = jnp.maximum(position - (window - 1), 0) // block_size
    columns = (first[:, None] + jnp.arange(count, dtype=jnp.int32)[None, :]) % count
    own = slots.astype(jnp.int32)[:, None] * count
    table = jnp.where(live[:, None], own + columns, scratch)
    base = jnp.where(live, position - first * block_size, 0)
    block = jnp.where(live, own[:, 0] + (position // block_size) % count, scratch)
    return table, base, block, position % block_size


class HybridCacheLayout:
    """What a serving engine asks this model about its cache (see the module
    docstring for the leaves). Beside what every layout answers
    (:class:`unionml_tpu.models.gpt.KVCacheLayout`) it has per-slot state."""

    #: what a slot keeps beside its blocks under the table: an engine refuses by
    #: these names what moves blocks and nothing else (prefix cache, preemption,
    #: speculative rollback, an int8 pool, a ``tensor`` mesh)
    slot_state: Tuple[str, ...] = ("recurrent state", "window ring")

    def __init__(self, config: Phi4FlashConfig) -> None:
        self.config = config
        self.kv_heads = config.groups
        #: ``(heads, last dimension)`` of the paged kernel's call over the joined leaf
        self.kernel_key = (config.num_heads, 4 * config.head_dim)
        #: layers that run for ``logit_rows`` alone in a prefill
        self.tail_layers = config.num_layers - config.full_layer - 1
        self._kinds = [layer_kind(i, config.num_layers) for i in range(config.num_layers)]

    def _row_shape(self, lead: Tuple[int, ...], tokens: int) -> Tuple[int, ...]:
        return lead + (self.config.groups, tokens, 4 * self.config.head_dim)

    def _state(self, rows: int) -> Dict[str, Any]:
        cfg = self.config
        return {
            "ssm": jnp.zeros((rows, cfg.d_state, cfg.d_inner), jnp.float32),
            "conv": jnp.zeros((rows, cfg.d_conv - 1, cfg.d_inner), cfg.dtype),
        }

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        """A prefill's dense workspace: the attention layers' rows for every
        position, the Mamba layers' state."""
        cache: Dict[str, Any] = {}
        for i, kind in enumerate(self._kinds):
            if kind == "mamba":
                cache[f"layer_{i}"] = self._state(batch)
            elif kind in ("window", "full"):
                cache[f"layer_{i}"] = {"kv": jnp.zeros(self._row_shape((batch,), max_len), self.config.dtype)}
        return cache

    def init_block_pool(
        self, num_blocks: int, block_size: int, kv_quantize: Optional[str] = None,
        kv_quantize_skip_layers: Tuple[int, ...] = (), num_slots: Optional[int] = None,
    ) -> Dict[str, Any]:
        if kv_quantize is not None:
            raise ValueError(
                f"kv_quantize={kv_quantize!r} with per-slot {' and '.join(self.slot_state)}: the int8 "
                "pool's block scales have no counterpart for a ring that is overwritten in place"
            )
        if num_slots is None:
            raise ValueError("a layout with per-slot state needs num_slots to size it")
        cfg = self.config
        ring = num_slots * ring_blocks(cfg.sliding_window, block_size) + 1  # the last is scratch
        pool: Dict[str, Any] = {}
        for i, kind in enumerate(self._kinds):
            if kind == "mamba":
                pool[f"layer_{i}"] = self._state(num_slots)
            elif kind in ("window", "full"):
                blocks = ring if kind == "window" else num_blocks
                pool[f"layer_{i}"] = {"kv": jnp.zeros(self._row_shape((blocks,), block_size), cfg.dtype)}
        return pool

    def join(self, cache: Dict[str, Any]) -> Dict[str, Any]:
        """Of a dense workspace, the layers whose blocks lie under the slots'
        table, as the pool names them: the one full layer."""
        name = f"layer_{self.config.full_layer}"
        return {name: cache[name]}

    def split(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        return tree

    def paged(self, pool: Dict[str, Any]) -> Dict[str, Any]:
        """Of a pool, the layers whose blocks lie under the slots' table."""
        return self.join(pool)

    def insert_slot_state(self, pool, local_cache, slots, lengths):
        """(jit-traceable) Write a bucket prefill's workspace into ``slots``:
        the Mamba layers' state whole (a reused slot keeps nothing of its last
        occupant), and of each window layer the blocks that hold the last
        ``window`` keys of a row of ``lengths`` tokens, each into the ring
        block its positions map to. Leaves under the table are the engine's."""
        cfg = self.config
        block_size = pool[f"layer_{cfg.full_layer}"]["kv"].shape[2]
        count = ring_blocks(cfg.sliding_window, block_size)
        slots = slots.astype(jnp.int32)
        last = (jnp.maximum(lengths.astype(jnp.int32), 1) - 1) // block_size
        ring = jnp.arange(count, dtype=jnp.int32)[None, :]
        # the newest position block that maps to ring block r, for every r
        source = jnp.maximum(last[:, None] - (last[:, None] - ring) % count, 0)  # (rows, count)
        target = slots[:, None] * count + ring
        out = dict(pool)
        for i, kind in enumerate(self._kinds):
            name = f"layer_{i}"
            if kind == "mamba":
                out[name] = {
                    key: leaf.at[slots].set(local_cache[name][key].astype(leaf.dtype))
                    for key, leaf in pool[name].items()
                }
            elif kind == "window":
                local = local_cache[name]["kv"]  # (rows, groups, bucket, row)
                rows, groups, bucket, width = local.shape
                blocks = -(-bucket // block_size)
                local = jnp.pad(local, ((0, 0), (0, 0), (0, blocks * block_size - bucket), (0, 0)))
                local = local.reshape(rows, groups, blocks, block_size, width).transpose(0, 2, 1, 3, 4)
                picked = jnp.take_along_axis(
                    local, jnp.minimum(source, blocks - 1)[:, :, None, None, None], axis=1
                )  # (rows, count, groups, block, row)
                leaf = pool[name]["kv"]
                out[name] = {"kv": leaf.at[target].set(picked.astype(leaf.dtype))}
        return out

    def cache_spec(self, mesh_axis_names: Tuple[str, ...]) -> Any:
        from jax.sharding import PartitionSpec

        return PartitionSpec()

    def block_bytes(self, block_size: int, kv_quantize: Optional[str] = None,
                    kv_quantize_skip_layers: Tuple[int, ...] = ()) -> int:
        """Bytes of one block under the table, all layers that have one: the full layer's."""
        cfg = self.config
        return cfg.groups * block_size * 4 * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize

    def slot_bytes(self, block_size: int) -> Dict[str, int]:
        """Bytes a slot holds beside its blocks under the table, whatever its length."""
        cfg = self.config
        mamba = sum(kind == "mamba" for kind in self._kinds)
        window = sum(kind == "window" for kind in self._kinds)
        item = jnp.dtype(cfg.dtype).itemsize
        return {
            "state": mamba * (cfg.d_state * cfg.d_inner * 4 + (cfg.d_conv - 1) * cfg.d_inner * item),
            "ring": window * ring_blocks(cfg.sliding_window, block_size) * self.block_bytes(block_size),
        }

    def pool_bytes(self, pool: Dict[str, Any]) -> Tuple[int, int]:
        return kv_pool_bytes(pool, self.config.dtype)


def _param(module: nn.Module, name: str, shape, std: Optional[float] = None, dtype=None):
    std = shape[0] ** -0.5 if std is None else std
    return module.param(name, nn.initializers.normal(std), shape, dtype or module.config.dtype)


def _dense_differential(q, rows, mask):
    """Both softmaxes of every pair, by masks: ``q`` (batch, S, heads, d),
    ``rows`` (batch, T, groups, 4 d) of ``[k1 | k2 | v1 | v2]``, ``mask``
    (batch, S, T) -> (batch, S, pairs, 2, 2 d) float32."""
    batch, seq, heads, dim = q.shape
    keys, groups = rows.shape[1], rows.shape[2]
    q = q.reshape(batch, seq, groups, heads // 2 // groups, 2, dim)
    k = rows[..., : 2 * dim].reshape(batch, keys, groups, 2, dim)
    v = rows[..., 2 * dim :]
    scores = jnp.einsum("bsgpwd,btgwd->bgpwst", q, k, preferred_element_type=jnp.float32) * dim ** -0.5
    scores = jnp.where(mask[:, None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    out = jnp.einsum("bgpwst,btgv->bsgpwv", probs, v, preferred_element_type=jnp.float32)
    return out.reshape(batch, seq, heads // 2, 2, 2 * dim)


def _paged_differential(q, leaf, table, base, cfg: Phi4FlashConfig, window: Optional[int] = None):
    """The same over a pool leaf through a table: the group's four query heads
    zero-padded onto the ``[k1 | k2]`` row (module docstring)."""
    batch, seq, heads, dim = q.shape
    pairs = q.reshape(batch, seq, heads // 2, 2, dim)
    zeros = jnp.zeros_like(pairs[..., 0, :])
    padded = jnp.stack(
        [jnp.concatenate([pairs[..., 0, :], zeros], axis=-1), jnp.concatenate([zeros, pairs[..., 1, :]], axis=-1)],
        axis=-2,
    ).reshape(batch, seq, heads, 2 * dim)
    out = paged_attention(
        padded.transpose(0, 2, 1, 3), leaf, None, table, base, out_dtype=jnp.float32,
        impl=cfg.paged_attn_impl, interpret=cfg.interpret, sm_scale=dim ** -0.5, window=window,
    )  # (batch, heads, S, 2 d)
    return out.transpose(0, 2, 1, 3).reshape(batch, seq, heads // 2, 2, 2 * dim).astype(jnp.float32)


class DifferentialOutput(nn.Module):
    """``lambda``, the per-pair RMSNorm of the difference, and ``W_o``."""

    config: Phi4FlashConfig
    layer: int

    @nn.compact
    def __call__(self, attended):
        """``attended`` (batch, S, pairs, 2, 2 d) float32 -> (batch, S, hidden)."""
        cfg = self.config
        dim = cfg.head_dim
        lam = {
            name: self.param(name, nn.initializers.normal(0.1), (dim,), jnp.float32)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
        }
        init = lambda_init(self.layer)
        weight = jnp.exp(jnp.sum(lam["lambda_q1"] * lam["lambda_k1"])) - jnp.exp(
            jnp.sum(lam["lambda_q2"] * lam["lambda_k2"])) + init
        diff = attended[..., 0, :] - weight * attended[..., 1, :]
        scale = self.param("subln", nn.initializers.ones, (2 * dim,), jnp.float32)
        normed = diff * jax.lax.rsqrt(jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + cfg.layer_norm_eps)
        context = ((1.0 - init) * normed * scale).astype(cfg.dtype)
        context = context.reshape(context.shape[:2] + (cfg.num_heads * dim,))
        kernel = _param(self, "o", (cfg.num_heads * dim, cfg.hidden_size))
        bias = self.param("o_bias", nn.initializers.zeros, (cfg.hidden_size,), cfg.dtype)
        return jnp.dot(context, kernel.astype(cfg.dtype)) + bias.astype(cfg.dtype)


@dataclasses.dataclass
class _Call:
    """How one call goes through the model (module docstring), for the mixers."""

    mode: str  # "full" | "prefill" | "chunk" | "decode"
    position: Any = None  # chunk: traced scalar; decode: (rows,)
    valid: Any = None  # (rows,) real tokens a row where the rest is padding
    table: Any = None
    slot: Any = None  # chunk: the traced slot
    live: Any = None  # decode: (rows,) rows that are not on the sentinel
    rows: Any = None  # the positions the tail layers run for, (batch,), or None: all


class Attention(nn.Module):
    """A differential-attention layer with keys and values of its own: over the
    last ``window`` keys where ``window`` is given, over all of them where not."""

    config: Phi4FlashConfig
    layer: int
    window: Optional[int]

    @nn.compact
    def __call__(self, x, cache, call: _Call):
        cfg = self.config
        batch, seq, _ = x.shape
        dim, heads, groups = cfg.head_dim, cfg.num_heads, cfg.groups
        width = (heads + 2 * cfg.num_kv_heads) * dim
        qkv = jnp.dot(x, _param(self, "qkv", (cfg.hidden_size, width)).astype(cfg.dtype))
        qkv = qkv + self.param("qkv_bias", nn.initializers.zeros, (width,), cfg.dtype).astype(cfg.dtype)
        q = qkv[..., : heads * dim].reshape(batch, seq, heads, dim)
        k = qkv[..., heads * dim : (heads + cfg.num_kv_heads) * dim].reshape(batch, seq, groups, 2 * dim)
        v = qkv[..., (heads + cfg.num_kv_heads) * dim :].reshape(batch, seq, groups, 2 * dim)
        rows = jnp.concatenate([k, v], axis=-1)  # (batch, seq, groups, 4 d): [k1 | k2 | v1 | v2]
        window = self.window
        new_cache = None
        if call.mode in ("full", "prefill"):
            at = jnp.arange(seq)
            mask = at[None, :] <= at[:, None]
            if window is not None:
                mask = mask & (at[None, :] > at[:, None] - window)
            attended = _dense_differential(q, rows, jnp.broadcast_to(mask, (batch, seq, seq)))
            # a workspace's leaf (and, without one, what the cross layers read)
            new_cache = {"kv": rows.transpose(0, 2, 1, 3)}
        elif window is None:
            attended, new_cache = self._paged_full(q, rows, cache, call)
        elif call.mode == "decode":
            leaf = cache["kv"]
            block_size, scratch = leaf.shape[2], leaf.shape[0] - 1
            table, base, block, offset = ring_view(
                call.position, call.live, jnp.arange(batch), window, block_size, scratch
            )
            leaf = _paged_append_rows(leaf, block, offset, rows[:, 0])
            attended = _paged_differential(q, leaf, table, base, cfg, window=window)
            new_cache = {"kv": leaf}
        else:
            attended, new_cache = self._ring_chunk(q, rows, cache, call)
        return DifferentialOutput(cfg, self.layer, name="out")(attended), new_cache

    def _paged_full(self, q, rows, cache, call: _Call):
        """Append through the slots' table and attend over it (decode and chunk)."""
        leaf, table = cache["kv"], call.table
        block_size = leaf.shape[2]
        capacity = table.shape[1] * block_size
        if call.mode == "decode":
            pos = jnp.clip(call.position.astype(jnp.int32), 0, capacity - 1)
            block = jnp.take_along_axis(table, (pos // block_size)[:, None], axis=1)[:, 0]
            leaf = _paged_append_rows(leaf, block, pos % block_size, rows[:, 0])
            base = call.position.astype(jnp.int32)
        else:
            pos = jnp.clip(call.position + jnp.arange(rows.shape[1], dtype=jnp.int32), 0, capacity - 1)
            block = jnp.take(table[0], pos // block_size)
            leaf = _paged_append_rows(leaf, block, pos % block_size, rows[0])
            base = jnp.reshape(jnp.asarray(call.position, jnp.int32), (1,))
        return _paged_differential(q, leaf, table, base, self.config, window=None), {"kv": leaf}

    def _ring_chunk(self, q, rows, cache, call: _Call):
        """One chunk of one slot's prompt over a window layer: its queries see
        the ``window - 1`` keys before the chunk, read from the ring as the
        chunk before left it, and the chunk's own; then the chunk's last real
        keys, as many as the ring holds, go into the ring (what is padding, or
        older than the ring is long, goes to scratch)."""
        cfg, window = self.config, self.window
        leaf = cache["kv"]
        seq = rows.shape[1]
        block_size, scratch = leaf.shape[2], leaf.shape[0] - 1
        count = ring_blocks(window, block_size)
        own = call.slot.astype(jnp.int32) * count
        position = jnp.asarray(call.position, jnp.int32)
        before = position - (window - 1) + jnp.arange(window - 1, dtype=jnp.int32)
        clipped = jnp.maximum(before, 0)
        earlier = leaf[own + (clipped // block_size) % count, :, clipped % block_size, :]  # (window - 1, groups, row)
        keys = jnp.concatenate([earlier[None].astype(rows.dtype), rows], axis=1)
        at = position + jnp.arange(seq, dtype=jnp.int32)
        key_at = jnp.concatenate([before, at])
        mask = (key_at[None, :] >= 0) & (key_at[None, :] <= at[:, None]) & (key_at[None, :] > at[:, None] - window)
        attended = _dense_differential(q, keys, mask[None])
        valid = seq if call.valid is None else call.valid[0].astype(jnp.int32)
        token = jnp.arange(seq, dtype=jnp.int32)
        kept = (token < valid) & (token >= valid - count * block_size)
        block = jnp.where(kept, own + (at // block_size) % count, scratch)
        leaf = _paged_append_rows(leaf, block, at % block_size, rows[0])
        return attended, {"kv": leaf}


class CrossAttention(nn.Module):
    """Differential attention over another layer's keys and values."""

    config: Phi4FlashConfig
    layer: int

    @nn.compact
    def __call__(self, x, shared, call: _Call):
        """``shared`` is the full layer's rows: dense ``(batch, T, groups, 4 d)``
        in a call without a table, its pool leaf in one with."""
        cfg = self.config
        batch, seq, _ = x.shape
        width = cfg.num_heads * cfg.head_dim
        q = jnp.dot(x, _param(self, "q", (cfg.hidden_size, width)).astype(cfg.dtype))
        q = q + self.param("q_bias", nn.initializers.zeros, (width,), cfg.dtype).astype(cfg.dtype)
        q = q.reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        if call.mode in ("full", "prefill"):
            keys = jnp.arange(shared.shape[1])
            at = jnp.arange(seq)[None, :] if call.rows is None else call.rows.astype(jnp.int32)[:, None]
            mask = jnp.broadcast_to(keys[None, None, :] <= at[:, :, None], (batch, seq, keys.size))
            attended = _dense_differential(q, shared, mask)
        else:
            base = jnp.asarray(call.position, jnp.int32)
            if call.mode == "chunk":
                base = jnp.reshape(base, (1,)) + (0 if call.rows is None else call.rows.astype(jnp.int32))
            attended = _paged_differential(q, shared, call.table, base, cfg)
        return DifferentialOutput(cfg, self.layer, name="out")(attended)


class Mamba(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, cache, call: _Call):
        """``x`` (batch, seq, d) -> (output, y (batch, seq, d_inner), layer cache)."""
        cfg = self.config
        dt = cfg.dtype
        batch, seq, _ = x.shape
        d_inner, n, rank, taps = cfg.d_inner, cfg.d_state, cfg.rank, cfg.d_conv
        uz = jnp.dot(x, _param(self, "in_proj", (cfg.hidden_size, 2 * d_inner)).astype(dt))
        u, z = uz[..., :d_inner], uz[..., d_inner:]
        conv_w = self.param("conv_w", nn.initializers.normal(taps ** -0.5), (taps, d_inner), jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (d_inner,), jnp.float32)
        x_proj = _param(self, "x_proj", (d_inner, rank + 2 * n)).astype(dt)
        dt_proj = _param(self, "dt_proj", (rank, d_inner)).astype(dt)
        dt_bias = self.param("dt_bias", nn.initializers.constant(-4.6), (d_inner,), jnp.float32)
        a_log = self.param(
            "A_log", lambda *_: jnp.log(jnp.broadcast_to(jnp.arange(1.0, n + 1), (d_inner, n))), (d_inner, n),
            jnp.float32,
        )
        skip = self.param("D", nn.initializers.ones, (d_inner,), jnp.float32)
        a = -jnp.exp(a_log.astype(jnp.float32)).T  # (n, d_inner): the state's layout

        state, tail = self._carried(cache, call, batch)
        decode = call.mode == "decode"
        conv, new_tail = causal_conv1d(u, conv_w, conv_b, tail, None if decode else call.valid)
        u = jax.nn.silu(conv).astype(dt)
        rbc = jnp.dot(u, x_proj)
        r, b, c = rbc[..., :rank], rbc[..., rank : rank + n], rbc[..., rank + n :]
        delta = jax.nn.softplus(jnp.dot(r, dt_proj, preferred_element_type=jnp.float32) + dt_bias)
        if decode:
            y, new_state = selective_step(
                u[:, 0], delta[:, 0], a, b[:, 0], c[:, 0], skip, state, live=call.live,
                impl=cfg.ssm_impl, interpret=cfg.interpret,
            )
            y = y[:, None, :]
            new_tail = jnp.where(call.live[:, None, None], new_tail, tail)
        else:
            y, new_state = selective_scan(
                u, delta, a, b, c, skip, state, call.valid, impl=cfg.ssm_impl, interpret=cfg.interpret
            )
        y = y.astype(dt)
        gated = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
        out = jnp.dot(gated, _param(self, "out_proj", (d_inner, cfg.hidden_size)).astype(dt))
        return out, y, self._kept(cache, call, new_state, new_tail)

    def _carried(self, cache, call: _Call, batch: int):
        """The state and the convolution's tail this call starts from."""
        cfg = self.config
        if call.mode in ("full", "prefill"):
            return (jnp.zeros((batch, cfg.d_state, cfg.d_inner), jnp.float32),
                    jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), cfg.dtype))
        if call.mode == "decode":
            return cache["ssm"], cache["conv"]
        # a chunk: the slot's, or empty state where the chunk is the prompt's first
        first = jnp.asarray(call.position, jnp.int32) == 0
        take = lambda leaf: jnp.where(first, 0, jax.lax.dynamic_slice_in_dim(leaf, call.slot, 1, axis=0))
        return take(cache["ssm"]), take(cache["conv"])

    def _kept(self, cache, call: _Call, state, tail):
        if call.mode == "full":
            return None
        if call.mode == "chunk":
            put = lambda leaf, new: jax.lax.dynamic_update_slice_in_dim(leaf, new.astype(leaf.dtype), call.slot, axis=0)
            return {"ssm": put(cache["ssm"], state), "conv": put(cache["conv"], tail)}
        return {"ssm": state, "conv": tail.astype(cache["conv"].dtype)}


class GatedMemory(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.config
        gate = jnp.dot(x, _param(self, "in_proj", (cfg.hidden_size, cfg.d_inner)).astype(cfg.dtype))
        gated = (memory.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
        return jnp.dot(gated, _param(self, "out_proj", (cfg.d_inner, cfg.hidden_size)).astype(cfg.dtype))


class SwiGLU(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        inner = cfg.intermediate_size
        gu = jnp.dot(x, _param(self, "up", (cfg.hidden_size, 2 * inner)).astype(cfg.dtype))
        hidden = (jax.nn.silu(gu[..., :inner].astype(jnp.float32)) * gu[..., inner:].astype(jnp.float32))
        return jnp.dot(hidden.astype(cfg.dtype), _param(self, "down", (inner, cfg.hidden_size)).astype(cfg.dtype))


class Phi4FlashBlock(nn.Module):
    config: Phi4FlashConfig
    layer: int

    @nn.compact
    def __call__(self, x, cache, call: _Call, memory, shared):
        """-> (x, layer cache or None, y of a Mamba layer or None)."""
        cfg = self.config
        kind = layer_kind(self.layer, cfg.num_layers)
        norm = lambda name: nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name=name)
        normed = norm("norm")(x)
        new_cache = y = None
        if kind == "mamba":
            out, y, new_cache = Mamba(cfg, name="mixer")(normed, cache, call)
        elif kind in ("window", "full"):
            window = cfg.sliding_window if kind == "window" else None
            out, new_cache = Attention(cfg, self.layer, window, name="mixer")(normed, cache, call)
        elif kind == "gmu":
            out = GatedMemory(cfg, name="mixer")(normed, memory)
        else:
            out = CrossAttention(cfg, self.layer, name="mixer")(normed, shared, call)
        x = x + out.astype(x.dtype)
        x = x + SwiGLU(cfg, name="mlp")(norm("mlp_norm")(x)).astype(x.dtype)
        return x, new_cache, y


class Phi4FlashLMHeadModel(nn.Module):
    """The decoder LM of the module docstring."""

    config: Phi4FlashConfig

    def cache_layout(self) -> HybridCacheLayout:
        """What a serving engine asks about this model's cache."""
        return HybridCacheLayout(self.config)

    def _call(self, input_ids, cache, position, logit_rows) -> _Call:
        batch, seq = input_ids.shape
        if cache is None:
            return _Call("full", rows=logit_rows)
        valid = None if logit_rows is None else logit_rows.astype(jnp.int32) + 1
        table = cache.get("table")
        if table is None:
            if not (isinstance(position, int) and position == 0):
                raise ValueError(
                    "a dense workspace of this model is a prefill from position 0: its recurrent state and "
                    "window ring live in the engine's paged pool (DecodeEngine(paged=True))"
                )
            return _Call("prefill", valid=valid, rows=logit_rows)
        full = cache[f"layer_{self.config.full_layer}"]["kv"]
        if not isinstance(position, int) and jnp.ndim(position) == 1:
            if seq != 1:
                raise ValueError("per-row positions are a decode step: one token a row")
            # the engine's sentinel for a retired or reserved slot's row
            live = position < (table.shape[1] - 1) * full.shape[2]
            return _Call("decode", position=position, table=table, live=live)
        if batch != 1 or "slots" not in cache:
            raise ValueError('a paged prefill chunk is one slot\'s: batch 1, the slot in cache["slots"]')
        slot = jnp.reshape(cache["slots"], (-1,))[0]
        return _Call("chunk", position=position, valid=valid, table=table, slot=slot, rows=logit_rows)

    @nn.compact
    def __call__(
        self,
        input_ids,
        cache: Optional[Dict[str, Any]] = None,
        position: Optional[jax.Array] = None,
        deterministic: bool = True,
        logit_rows: Optional[jax.Array] = None,
    ):
        """Logits (batch, seq, vocab) float32 (``(batch, 1, vocab)`` with
        ``logit_rows``), and with ``cache`` the new cache."""
        cfg = self.config
        call = self._call(input_ids, cache, position, logit_rows)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="embed")
        x = embed(input_ids)
        new_cache: Dict[str, Any] = {}
        memory = shared = None
        for i in range(cfg.num_layers):
            if i == cfg.full_layer + 1 and call.rows is not None:
                # the layers from here on read their own position alone
                rows = call.rows.astype(jnp.int32)[:, None, None]
                x = jnp.take_along_axis(x, rows, axis=1)
                memory = jnp.take_along_axis(memory, rows, axis=1)
            layer_cache = None if cache is None else cache.get(f"layer_{i}")
            x, layer_cache, y = Phi4FlashBlock(cfg, i, name=f"layer_{i}")(x, layer_cache, call, memory, shared)
            if layer_cache is not None and cache is not None:
                new_cache[f"layer_{i}"] = layer_cache
            if i == cfg.num_layers // 2:
                memory = y
            if i == cfg.full_layer:
                # what the cross layers read: the pool leaf where there is a table, this call's rows where not
                shared = layer_cache["kv"] if call.table is not None else layer_cache["kv"].transpose(0, 2, 1, 3)
        if call.table is not None:
            new_cache["table"] = call.table
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="final_norm")(x)
        logits = jnp.einsum(
            "bsd,vd->bsv", x.astype(cfg.dtype), embed.embedding.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        return (logits, new_cache) if cache is not None else logits


def init_params(config: Phi4FlashConfig, rng: Optional[jax.Array] = None, seq_len: int = 8) -> Any:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return Phi4FlashLMHeadModel(config).init({"params": rng}, jnp.zeros((1, seq_len), jnp.int32))
