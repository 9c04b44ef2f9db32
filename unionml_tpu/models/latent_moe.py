"""A current sparse decoder: latent attention, routed and shared experts,
hyper-connection residual streams.

The block that :class:`unionml_tpu.models.gpt.DecoderBlock` cannot express
(RMSNorm, SwiGLU, rotary positions with YaRN, an untied head, hundreds of
thousands of positions), with three mechanisms of its own:

- **Latent attention** (DeepSeek-V2's MLA). Queries come through a low-rank
  bottleneck; keys and values are both expansions of one latent row per token,
  ``[c ; k_r]`` (``kv_lora_rank`` values after their RMSNorm, then
  ``qk_rope_head_dim`` rotary values shared by all heads). The cache holds that
  row and nothing per head: ``kv_lora_rank + qk_rope_head_dim`` values a token
  a layer, padded to a multiple of 128 lanes (:class:`LatentCacheLayout`).
  Against the cache the block attends in the **absorbed** form: the key
  expansion folds into the query (``q~_i = q_nope_i W_kvb,i^K^T``), every head
  scores against the same latent row, the softmax-weighted sum of latent rows
  is expanded to values afterwards (``W_kvb,i^V``): ``num_heads`` query heads
  over ONE key head whose leading columns are the values, which is what
  :func:`unionml_tpu.ops.paged_attention.paged_attention` takes. A sequence that
  starts at position 0 (a full forward, a bucket prefill) attends in the
  **expanded** form (per-head keys and values from the fresh latents), which
  needs a third of the operations a query-key pair.
- **Experts**: a sigmoid router with a selection bias (``noaux_tc``: the bias
  steers the choice and not the weight), top-k of the routed experts computed
  by group (:func:`unionml_tpu.parallel.ep.moe_apply_grouped`: sort the (token,
  expert) pairs, one ``ragged_dot`` a projection), plus shared experts every
  token takes. No token is dropped. The first ``first_k_dense_replace`` layers
  have a dense SwiGLU instead.
- **Hyper-connections** (manifold-constrained, arXiv 2512.24880). A token's
  residual state is ``hc_mult`` streams ``X`` (n x d). Each sublayer reads one
  mix of them (``H_pre X``), writes its output back to all (``H_post^T F``),
  and the streams themselves are remixed by ``H_res``, a matrix made doubly
  stochastic by Sinkhorn iterations; the three maps are functions of the token
  (``x~ Phi``) plus learned constants. All of it in float32.

The incremental and the paged contract are those of
:class:`~unionml_tpu.models.gpt.GPTLMHeadModel` (``cache=``, ``position=``,
``cache["table"]``), so :class:`~unionml_tpu.serving.continuous.DecodeEngine`
serves it; what depends on the cache's layout the engine asks of
:meth:`LatentMoELMHeadModel.cache_layout`. ``logit_rows`` (batch,) names the
only positions whose logits the caller will read, and the head then runs over
those alone (a 1024-token chunk needs one row of a 131072-column head, not
1024). Two flax collections are written where the caller makes them mutable
and cost nothing where it does not: ``"stats"`` holds the int32 scalars
``expert_rows`` (rows routed, summed over the expert layers), ``experts_hit``
(experts with at least one row) and ``expert_rows_max`` (the busiest expert's
rows), which the engine's decode step adds into ``/stats``; ``"routing"``
holds each expert layer's ``chosen`` (tokens, top k), for whoever compares the
choices with a reference's.

Router and hyper-connection maps are float32 (their products at ``highest``
precision: a top-k choice and a 20-step Sinkhorn should not turn on bfloat16
rounding); everything else computes in ``config.dtype``; logits are float32,
from the bfloat16 head by float32 accumulation.
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.models.gpt import kv_pool_bytes
from unionml_tpu.ops.paged_attention import paged_attention
from unionml_tpu.parallel.ep import moe_apply_grouped

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: width of the dense SwiGLU of the first ``first_k_dense_replace`` layers
    intermediate_size: int = 9216
    #: width of one routed (and one shared) expert
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    rope_theta: float = 10000.0
    #: YaRN (DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``); factor 1 = plain RoPE
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    #: residual streams (1 would be a plain residual path with learned gates)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    dtype: Any = jnp.bfloat16
    #: paged attention backend ("auto" | "pallas" | "xla"), as ``GPTConfig``'s
    paged_attn_impl: str = "auto"

    @classmethod
    def tiny(cls, **overrides) -> "LatentMoEConfig":
        """Every mechanism at a size a CPU test runs: 1 dense + 2 expert layers,
        8 experts top 2 and a shared one, rope and nope parts, 4 streams."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, first_k_dense_replace=1, max_position_embeddings=256,
            rope_factor=4.0, rope_original_max_position=32, dtype=jnp.float32,
        )
        defaults.update(overrides)
        return cls(**defaults)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token's cache row holds: the normed latent, then the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        """:attr:`latent_dim` padded to whole 128-lane tiles (zeros), so that XLA
        keeps the pool in its own order and Mosaic can slice it."""
        return -(-self.latent_dim // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5`` times YaRN's ``mscale ** 2`` (from ``mscale_all_dim``)."""
        mscale = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * mscale * mscale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(config: LatentMoEConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(cos, sin)`` of shape ``positions.shape + (qk_rope_head_dim,)``, float32.

    YaRN as DeepSeek-V3 computes it: each of the ``dim / 2`` frequencies is a
    blend of the original (extrapolated) and the ``factor``-times slower
    (interpolated) one, by a linear ramp between the two correction dimensions
    (where ``beta_fast`` and ``beta_slow`` rotations fit the original context);
    cos and sin are scaled by ``mscale / mscale_all_dim``.
    """
    dim = config.qk_rope_head_dim
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / config.rope_theta ** exponents
    if config.rope_factor <= 1.0:
        inv_freq, scale = extrapolated, 1.0
    else:
        def correction_dim(rotations: float) -> float:
            return dim * math.log(config.rope_original_max_position / (rotations * 2 * math.pi)) / (
                2 * math.log(config.rope_theta)
            )

        low = max(math.floor(correction_dim(config.rope_beta_fast)), 0)
        high = min(math.ceil(correction_dim(config.rope_beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
        interpolated = extrapolated / config.rope_factor
        inv_freq = interpolated * ramp + extrapolated * (1.0 - ramp)
        scale = yarn_mscale(config.rope_factor, config.rope_mscale) / yarn_mscale(
            config.rope_factor, config.rope_mscale_all_dim
        )
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the last axis: dimension ``j`` pairs with ``j + dim / 2``."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin


def rms_norm(x: jax.Array, scale: Optional[jax.Array], eps: float, dtype: Any) -> jax.Array:
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    if scale is not None:
        normed = normed * scale.astype(jnp.float32)
    return normed.astype(dtype)


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(logits)`` (..., n, n) made doubly stochastic: ``iters`` rounds of
    rows then columns divided by their sums (``eps`` in the denominators)."""
    matrix = jnp.exp(logits)
    for _ in range(iters):
        matrix = matrix / (jnp.sum(matrix, axis=-1, keepdims=True) + eps)
        matrix = matrix / (jnp.sum(matrix, axis=-2, keepdims=True) + eps)
    return matrix


class LatentCacheLayout:
    """What a serving engine asks a model about its cache: one latent row a
    token a layer (:attr:`LatentMoEConfig.cache_row_dim` wide, one key "head",
    one leaf, no value leaf), in the shapes the per-head layouts have, so that
    tables, scatters and gathers are the engine's own."""

    #: per-slot state beside the blocks under the table, and layers a prefill
    #: runs for one position alone (:class:`unionml_tpu.models.gpt.KVCacheLayout`): none
    slot_state: Tuple[str, ...] = ()
    tail_layers = 0

    def __init__(self, config: LatentMoEConfig) -> None:
        self.config = config
        #: heads of a pool leaf (what a mesh may shard: nothing here)
        self.kv_heads = 1
        #: ``(heads, last dimension)`` of the paged kernel's call
        self.kernel_key = (config.num_heads, config.cache_row_dim)

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        shape = (batch, 1, max_len, self.config.cache_row_dim)
        return {
            f"layer_{i}": {"kv": jnp.zeros(shape, self.config.dtype)}
            for i in range(self.config.num_layers)
        }

    def init_block_pool(
        self, num_blocks: int, block_size: int, kv_quantize: Optional[str] = None,
        kv_quantize_skip_layers: Tuple[int, ...] = (), num_slots: Optional[int] = None,
    ) -> Dict[str, Any]:
        if kv_quantize is not None:
            raise ValueError(
                f"kv_quantize={kv_quantize!r} with a latent cache layout: the int8 pool's "
                "per-head block scales have no latent counterpart yet"
            )
        shape = (num_blocks, 1, block_size, self.config.cache_row_dim)
        return {
            f"layer_{i}": {"kv": jnp.zeros(shape, self.config.dtype)}
            for i in range(self.config.num_layers)
        }

    def join(self, cache: Dict[str, Any]) -> Dict[str, Any]:
        """A dense cache's layers as the pool names them: the same one leaf."""
        return cache

    def split(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        return tree

    def cache_spec(self, mesh_axis_names: Tuple[str, ...]) -> Any:
        from jax.sharding import PartitionSpec

        return PartitionSpec()  # one key head: every shard's, whole

    def paged(self, pool: Dict[str, Any]) -> Dict[str, Any]:
        return pool

    def insert_slot_state(self, pool, local_cache, slots, lengths):
        return pool

    def slot_bytes(self, block_size: int) -> Dict[str, int]:
        return {"state": 0, "ring": 0}

    def block_bytes(self, block_size: int, kv_quantize: Optional[str] = None,
                    kv_quantize_skip_layers: Tuple[int, ...] = ()) -> int:
        itemsize = jnp.dtype(self.config.dtype).itemsize
        return self.config.num_layers * block_size * self.config.cache_row_dim * itemsize

    def pool_bytes(self, pool: Dict[str, Any]) -> Tuple[int, int]:
        return kv_pool_bytes(pool, self.config.dtype)


class HyperConnection(nn.Module):
    """One sublayer's three maps of the residual streams (module docstring)."""

    config: LatentMoEConfig

    @nn.compact
    def __call__(self, streams: jax.Array):
        """``streams`` (..., n, d) float32 -> ``(H_pre (..., n), H_post (..., n),
        H_res (..., n, n))``."""
        cfg = self.config
        n, d = cfg.hc_mult, cfg.hidden_size
        phi = self.param("phi", nn.initializers.normal(0.02), (n * d, 2 * n + n * n), jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (2 * n + n * n,), jnp.float32)
        flat = streams.reshape(streams.shape[:-2] + (n * d,))
        flat = rms_norm(flat, None, cfg.rms_norm_eps, jnp.float32)
        mapped = jnp.matmul(flat, phi, precision=_HIGHEST)
        pre = alpha[0] * mapped[..., :n] + bias[:n]
        post = alpha[1] * mapped[..., n : 2 * n] + bias[n : 2 * n]
        res = alpha[2] * mapped[..., 2 * n :] + bias[2 * n :]
        res = jnp.clip(res.reshape(res.shape[:-1] + (n, n)), *cfg.hc_res_clamp)
        return (
            jax.nn.sigmoid(pre),
            2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps),
        )


def _kernel(module: nn.Module, name: str, shape: Tuple[int, ...], dtype: Any = None) -> jax.Array:
    return module.param(name, nn.initializers.normal(0.02), shape, dtype or jnp.float32)


class LatentAttention(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, hidden, cache, position, block_table):
        """``hidden`` (batch, seq, d) -> (context projected to d, new layer cache).

        ``cache`` is ``None`` (full sequence), a dense ``{"kv": (batch, 1,
        max_len, row)}`` or, with ``block_table``, the pool leaf ``{"kv":
        (blocks, 1, block_size, row)}``; ``position`` as in ``DecoderBlock``.
        """
        cfg = self.config
        dt = cfg.dtype
        batch, seq, _ = hidden.shape
        heads, nope, rope, vdim = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        rank = cfg.kv_lora_rank

        def dense(x, name, shape):
            return jnp.dot(x.astype(dt), _kernel(self, name, shape).astype(dt))

        x = hidden
        c_q = rms_norm(dense(x, "q_a", (cfg.hidden_size, cfg.q_lora_rank)),
                       self.param("q_a_norm", nn.initializers.ones, (cfg.q_lora_rank,), jnp.float32),
                       cfg.rms_norm_eps, dt)
        q = dense(c_q, "q_b", (cfg.q_lora_rank, heads * cfg.qk_head_dim))
        q = q.reshape(batch, seq, heads, cfg.qk_head_dim)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        kv = dense(x, "kv_a", (cfg.hidden_size, cfg.latent_dim))
        latent = rms_norm(kv[..., :rank],
                          self.param("kv_a_norm", nn.initializers.ones, (rank,), jnp.float32),
                          cfg.rms_norm_eps, dt)
        k_rope = kv[..., rank:]
        w_kvb = _kernel(self, "kv_b", (rank, heads * (nope + vdim))).astype(dt)
        w_kvb = w_kvb.reshape(rank, heads, nope + vdim)
        w_k, w_v = w_kvb[..., :nope], w_kvb[..., nope:]  # (rank, heads, nope | vdim)

        per_row = not isinstance(position, int) and position is not None and jnp.ndim(position) == 1
        if cache is None:
            positions = jnp.arange(seq)[None, :]
        elif per_row:
            if seq != 1:
                raise ValueError("per-row cache positions require single-token decode (seq=1)")
            positions = position.astype(jnp.int32)[:, None]
        else:
            positions = (position + jnp.arange(seq))[None, :]
        cos, sin = rope_tables(cfg, positions)  # (batch | 1, seq, rope)
        q_rope = apply_rope(q_rope, cos[:, :, None, :], sin[:, :, None, :]).astype(dt)
        k_rope = apply_rope(k_rope, cos, sin).astype(dt)
        # the token's cache row: normed latent, rotated key, zeros to the lane tile
        row = jnp.concatenate([latent, k_rope], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0), (0, cfg.cache_row_dim - cfg.latent_dim)))

        start_of_sequence = cache is None or (isinstance(position, int) and position == 0 and seq > 1)
        new_cache = None
        if cache is not None and block_table is None:
            leaf = cache["kv"]
            if per_row:
                cols = jnp.clip(position.astype(jnp.int32), 0, leaf.shape[2] - 1)
                leaf = leaf.at[jnp.arange(batch), 0, cols, :].set(row[:, 0, :].astype(leaf.dtype))
            else:
                leaf = jax.lax.dynamic_update_slice(
                    leaf, row[:, None].astype(leaf.dtype), (0, 0, position, 0)
                )
            new_cache = {"kv": leaf}
        elif cache is not None:
            leaf = cache["kv"]
            block_size = leaf.shape[2]
            capacity = block_table.shape[1] * block_size
            if per_row:
                pos = jnp.clip(position.astype(jnp.int32), 0, capacity - 1)
                dst = jnp.take_along_axis(block_table, (pos // block_size)[:, None], axis=1)[:, 0]
                leaf = leaf.at[dst, 0, pos % block_size, :].set(row[:, 0, :].astype(leaf.dtype))
            else:
                if batch != 1:
                    raise ValueError("paged chunk prefill requires batch == 1")
                pos = jnp.clip((position + jnp.arange(seq)).astype(jnp.int32), 0, capacity - 1)
                dst = jnp.take(block_table[0], pos // block_size)
                leaf = leaf.at[dst, 0, pos % block_size, :].set(row[0].astype(leaf.dtype))
            new_cache = {"kv": leaf}

        if start_of_sequence:
            # expanded: per-head keys and values from the fresh latents, causal
            k_nope = jnp.einsum("bsr,rhn->bshn", latent, w_k)
            values = jnp.einsum("bsr,rhv->bshv", latent, w_v)
            scores = (
                jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
                + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope, preferred_element_type=jnp.float32)
            ) * cfg.softmax_scale
            causal = jnp.tril(jnp.ones((seq, seq), bool))
            weights = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
            context = jnp.einsum("bhqk,bkhv->bqhv", weights.astype(dt), values)
        else:
            # absorbed: every head's query against the one latent row a token
            absorbed = jnp.einsum("bshn,rhn->bshr", q_nope, w_k)
            query = jnp.concatenate([absorbed, q_rope], axis=-1)
            query = jnp.pad(query, ((0, 0),) * 3 + ((0, cfg.cache_row_dim - cfg.latent_dim),))
            query = jnp.moveaxis(query, 1, 2)  # (batch, heads, seq, row)
            if block_table is not None:
                base = position.astype(jnp.int32) if per_row else jnp.reshape(
                    jnp.asarray(position, jnp.int32), (1,)
                )
                mixed = paged_attention(
                    query, new_cache["kv"], None, block_table, base, out_dtype=dt,
                    impl=cfg.paged_attn_impl, sm_scale=cfg.softmax_scale,
                )
            else:
                keys = new_cache["kv"]  # (batch, 1, max_len, row)
                q_pos = positions if per_row else positions + jnp.zeros((batch, 1), jnp.int32)
                mask = jnp.arange(keys.shape[2])[None, None, :] <= q_pos[:, :, None]
                scores = jnp.einsum(
                    "bhsr,bkr->bhsk", query, keys[:, 0], preferred_element_type=jnp.float32
                ) * cfg.softmax_scale
                weights = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
                mixed = jnp.einsum("bhsk,bkr->bhsr", weights.astype(dt), keys[:, 0])
            # the weighted sum of latent rows, expanded to each head's values
            context = jnp.einsum("bhsr,rhv->bshv", mixed[..., :rank], w_v)
        context = context.reshape(batch, seq, heads * vdim)
        return dense(context, "o", (heads * vdim, cfg.hidden_size)), new_cache


class SwiGLU(nn.Module):
    width: int
    hidden_size: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        dt = self.dtype
        gate = jnp.dot(x.astype(dt), _kernel(self, "gate", (self.hidden_size, self.width)).astype(dt))
        up = jnp.dot(x.astype(dt), _kernel(self, "up", (self.hidden_size, self.width)).astype(dt))
        return jnp.dot(jax.nn.silu(gate) * up, _kernel(self, "down", (self.width, self.hidden_size)).astype(dt))


def grouped_swiglu(params, rows, group_sizes):
    """``(gate, up, down)`` stacked by expert over rows sorted by expert."""
    gate, up, down = params
    hidden = jax.nn.silu(jax.lax.ragged_dot(rows, gate, group_sizes)) * jax.lax.ragged_dot(
        rows, up, group_sizes
    )
    return jax.lax.ragged_dot(hidden, down, group_sizes)


class RoutedExperts(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, x):
        """``x`` (..., d) -> (routed + shared output, the experts' row counts)."""
        cfg = self.config
        dt = cfg.dtype
        d, width, experts = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
        tokens = x.reshape(-1, d)
        router = _kernel(self, "router", (d, experts))
        selection_bias = self.param("router_bias", nn.initializers.zeros, (experts,), jnp.float32)
        scores = jax.nn.sigmoid(jnp.matmul(tokens.astype(jnp.float32), router, precision=_HIGHEST))
        # the bias steers the choice only (noaux_tc); the weights are the scores
        _, chosen = jax.lax.top_k(scores + selection_bias, cfg.num_experts_per_tok)
        self.sow("routing", "chosen", chosen)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        weights = weights * cfg.routed_scaling_factor
        stacked = tuple(
            _kernel(self, name, shape).astype(dt)
            for name, shape in (
                ("gate", (experts, d, width)), ("up", (experts, d, width)), ("down", (experts, width, d)),
            )
        )
        with jax.named_scope("routed_experts"):
            routed, group_sizes = moe_apply_grouped(
                grouped_swiglu, stacked, tokens.astype(dt), chosen, weights.astype(dt)
            )
        shared = SwiGLU(width * cfg.n_shared_experts, d, dt, name="shared")(tokens)
        return (routed + shared).reshape(x.shape), group_sizes


class LatentMoEBlock(nn.Module):
    config: LatentMoEConfig
    use_experts: bool

    @nn.compact
    def __call__(self, streams, cache, position, block_table):
        """``streams`` (batch, seq, n, d) float32 -> (streams, layer cache, row counts)."""
        cfg = self.config

        def sublayer(streams, name, fn):
            with jax.named_scope("hyper_connection"):
                pre, post, res = HyperConnection(cfg, name=f"{name}_hc")(streams)
                mixed = jnp.einsum("...n,...nd->...d", pre, streams, precision=_HIGHEST)
            scale = self.param(f"{name}_norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
            out, extra = fn(rms_norm(mixed, scale, cfg.rms_norm_eps, cfg.dtype))
            with jax.named_scope("hyper_connection"):
                streams = jnp.einsum("...mn,...nd->...md", res, streams, precision=_HIGHEST) + (
                    post[..., :, None] * out.astype(jnp.float32)[..., None, :]
                )
            return streams, extra

        streams, new_cache = sublayer(
            streams, "attn",
            lambda x: LatentAttention(cfg, name="attn")(x, cache, position, block_table),
        )
        if self.use_experts:
            streams, group_sizes = sublayer(streams, "mlp", RoutedExperts(cfg, name="moe"))
        else:
            mlp = SwiGLU(cfg.intermediate_size, cfg.hidden_size, cfg.dtype, name="mlp")
            streams, group_sizes = sublayer(streams, "mlp", lambda x: (mlp(x), None))
        return streams, new_cache, group_sizes


class LatentMoELMHeadModel(nn.Module):
    """Decoder LM over :class:`LatentMoEBlock`: embedding repeated into the
    streams, N blocks, the streams summed, a final RMSNorm, an untied head."""

    config: LatentMoEConfig

    def cache_layout(self) -> LatentCacheLayout:
        return LatentCacheLayout(self.config)

    @nn.compact
    def __call__(
        self,
        input_ids,
        cache: Optional[Dict[str, Any]] = None,
        position: Optional[jax.Array] = None,
        deterministic: bool = True,
        logit_rows: Optional[jax.Array] = None,
    ):
        """Logits (batch, seq, vocab) float32, and with ``cache`` the new cache:
        see :meth:`unionml_tpu.models.gpt.GPTLMHeadModel.__call__` for the dense
        and the paged (``cache["table"]``) contract and for ``logit_rows``,
        which this keeps."""
        cfg = self.config
        embedding = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size), jnp.float32
        )
        hidden = jnp.take(embedding, input_ids, axis=0).astype(jnp.float32)
        streams = jnp.repeat(hidden[:, :, None, :], cfg.hc_mult, axis=2)

        block_table = cache.get("table") if cache is not None else None
        new_cache: Dict[str, Any] = {}
        stats = {name: jnp.zeros((), jnp.int32) for name in ("expert_rows", "experts_hit", "expert_rows_max")}
        for i in range(cfg.num_layers):
            layer_cache = None if cache is None else cache[f"layer_{i}"]
            streams, layer_cache, group_sizes = LatentMoEBlock(
                cfg, use_experts=i >= cfg.first_k_dense_replace, name=f"layer_{i}"
            )(streams, layer_cache, position, block_table)
            if layer_cache is not None:
                new_cache[f"layer_{i}"] = layer_cache
            if group_sizes is not None:
                stats["expert_rows"] += jnp.sum(group_sizes)
                stats["experts_hit"] += jnp.sum(group_sizes > 0).astype(jnp.int32)
                stats["expert_rows_max"] += jnp.max(group_sizes)
        if block_table is not None:
            new_cache["table"] = block_table
        for name, count in stats.items():
            self.sow("stats", name, count, reduce_fn=lambda _, new: new, init_fn=lambda: None)

        if logit_rows is not None:
            # the caller reads these positions' logits and no others: (batch, 1, vocab)
            rows = logit_rows.astype(jnp.int32)
            streams = jnp.take_along_axis(streams, rows[:, None, None, None], axis=1)
        scale = self.param("final_norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
        hidden = rms_norm(jnp.sum(streams, axis=2), scale, cfg.rms_norm_eps, cfg.dtype)
        head = _kernel(self, "lm_head", (cfg.hidden_size, cfg.vocab_size)).astype(cfg.dtype)
        logits = jnp.dot(hidden, head, preferred_element_type=jnp.float32)
        return (logits, new_cache) if cache is not None else logits


def init_params(config: LatentMoEConfig, rng: Optional[jax.Array] = None, seq_len: int = 8) -> Any:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return LatentMoELMHeadModel(config).init({"params": rng}, jnp.zeros((1, seq_len), jnp.int32))
