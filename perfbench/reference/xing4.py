"""Plain Xing4.0 in ``jax.numpy``: float32, ``highest`` matmul precision, no
kernel, no cache, no grouping of experts. Imports nothing of the program.

It follows the published configuration
(``XingChen-AGI/Xing4.0-29B-A4B`` ``config.json``: DeepSeek-V3's block with
one more mechanism) layer by layer:

- **Latent attention**, always in the *expanded* form: ``c_q = RMSNorm(h W_qa)``;
  ``[q_nope_i ; q_rope_i] = c_q W_qb`` per head; ``[c ; k_r] = h W_kva``;
  ``c <- RMSNorm(c)``; rotary positions on ``q_rope_i`` and on ``k_r`` (one for
  all heads); ``[k_nope_i ; v_i] = c W_kvb``; ``score_i(t, s) = (q_nope_i(t) .
  k_nope_i(s) + q_rope_i(t) . k_r(s)) * qk_head_dim^-1/2 * m^2``; causal
  softmax; the heads' outputs concatenated through ``W_o``. YaRN as
  ``DeepseekV3YarnRotaryEmbedding``: the frequency blend by the linear ramp
  between the two correction dimensions, ``m = 0.1 * mscale_all_dim * ln factor
  + 1``, cos and sin scaled by ``mscale / mscale_all_dim``.
- **Feed-forward**: SwiGLU ``(silu(x W_g) * x W_u) W_d``; in an expert layer
  ``s = sigmoid(x W_r)``, the top k of ``s + b`` chosen (``b`` the ``noaux_tc``
  selection bias, in the choice only; one group, so no group limit), ``w =
  s[chosen] / (sum + 1e-20) * routed_scaling_factor``, ``y = sum_e w_e
  SwiGLU_e(x) + SwiGLU_shared(x)``. Every expert runs over every token and a
  mask keeps the chosen ones: no token is dropped and nothing is sorted.
- **Residual path** (manifold-constrained hyper-connections, arXiv
  2512.24880). A token's state is ``X`` (n x d). For each sublayer ``F``:
  ``x~ = RMSNorm without scale of vec(X)``; ``H~_pre = a_pre (x~ Phi_pre) +
  b_pre``; ``H~_post`` alike; ``H~_res = a_res mat(x~ Phi_res) + B_res`` (n x
  n, rows are the streams written); ``H_pre = sigmoid(H~_pre)``, ``H_post = 2
  sigmoid(H~_post)``, ``H_res = Sinkhorn(clamp(H~_res))``: ``M = exp(.)``, then
  ``iters`` times rows and then columns divided by their sums plus ``hc_eps``;
  ``X <- H_res X + H_post^T F(RMSNorm(H_pre X))``.

Departures, each the program's too and listed in the configuration's
``assumed``: (1) the config cannot say how the streams begin and end: ``X_0``
is the embedding repeated n times, and the n streams are summed before the
final RMSNorm and the head; (2) ``hc_eps`` sits in Sinkhorn's denominators; (3)
the rotary pairs are ``(j, j + dim/2)`` of the rope columns as they stand:
DeepSeek's code first de-interleaves them, a fixed permutation of columns of
``W_qb`` and ``W_kva`` that no score can see; (4) the multi-token-prediction
module (``num_nextn_predict_layers``) is a draft head beside the model and is
not part of this forward pass.

Size: at the published widths one layer's float32 copies are 3 GB and a whole
8192 x 8192 x 32 score tensor 8.6 GB, beside the 9.6 GB of bfloat16 weights
that the run keeps. So each layer is one jitted call (the residual streams
donated), attention runs in query blocks, the experts one after another inside
a scan (one expert's float32 copy alive), the head in blocks of the vocabulary.

``lowp`` selects the control, as in ``reference/gpt2.py``: the same
mathematics with every weight matrix and every activation that a bfloat16
program keeps in bfloat16 rounded to a precision below the one the
configuration states (``"fp8"``, ``"int8"``; ``"bf16"`` below the float32 of
the tests' toy cells). The router and the hyper-connection maps, float32 in
the configuration, stay float32.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

LowP = Optional[str]  # None, "bf16", "int8" or "fp8"


def _lowered(x: jax.Array, lowp: str, axis: int) -> jax.Array:
    if lowp == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if lowp == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if lowp == "fp8":
        return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown control precision {lowp!r}")


def rounded(x: jax.Array, lowp: LowP) -> jax.Array:
    """An activation as the control keeps it (int8: a scale per token)."""
    return _lowered(x, lowp, axis=-1) if lowp else x


def dense(x: jax.Array, kernel: jax.Array, lowp: LowP) -> jax.Array:
    kernel = kernel.astype(jnp.float32)
    if lowp:
        kernel = _lowered(kernel, lowp, axis=-2)  # int8: a scale per output channel
    return rounded(jnp.matmul(rounded(x, lowp), kernel, precision=HIGHEST), lowp)


def rms_norm(x: jax.Array, scale: Optional[jax.Array], eps: float) -> jax.Array:
    normed = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return normed if scale is None else normed * scale.astype(jnp.float32)


def swiglu(x: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array, lowp: LowP) -> jax.Array:
    hidden = rounded(jax.nn.silu(dense(x, gate, lowp)) * dense(x, up, lowp), lowp)
    return dense(hidden, down, lowp)


def yarn_inverse_frequencies(rope: Dict[str, Any]) -> np.ndarray:
    dim, theta, factor = rope["dim"], rope["theta"], rope["factor"]
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return plain

    def correction_dim(rotations: float) -> float:
        return dim * math.log(rope["original_max_position"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rotate(x: jax.Array, positions: jax.Array, rope: Dict[str, Any]) -> jax.Array:
    """Rotary positions on the last axis of ``x`` (seq, ..., dim)."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inverse_frequencies(rope), jnp.float32)
    angles = jnp.concatenate([angles, angles], axis=-1)
    scale = yarn_mscale(rope["factor"], rope["mscale"]) / yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def attention(h: jax.Array, p: Dict[str, Any], sizes: Dict[str, Any], lowp: LowP) -> jax.Array:
    """Expanded latent attention over one sequence ``h`` (seq, d), causal."""
    seq = h.shape[0]
    heads, nope, rope_dim, vdim = sizes["heads"], sizes["nope"], sizes["rope"]["dim"], sizes["vdim"]
    rank, eps = sizes["kv_rank"], sizes["eps"]
    positions = jnp.arange(seq)
    c_q = rounded(rms_norm(dense(h, p["q_a"], lowp), p["q_a_norm"], eps), lowp)
    q = dense(c_q, p["q_b"], lowp).reshape(seq, heads, nope + rope_dim)
    q_nope, q_rope = q[..., :nope], rounded(rotate(q[..., nope:], positions, sizes["rope"]), lowp)
    kv = dense(h, p["kv_a"], lowp)
    c = rounded(rms_norm(kv[:, :rank], p["kv_a_norm"], eps), lowp)
    k_rope = rounded(rotate(kv[:, rank:], positions, sizes["rope"]), lowp)
    expanded = dense(c, p["kv_b"], lowp).reshape(seq, heads, nope + vdim)
    k_nope, values = expanded[..., :nope], expanded[..., nope:]
    scale = (nope + rope_dim) ** -0.5 * yarn_mscale(sizes["rope"]["factor"], sizes["rope"]["mscale_all_dim"]) ** 2
    block = min(sizes["query_block"], seq)
    if seq % block:
        raise ValueError(f"sequence of {seq} is no multiple of the query block {block}")

    def one_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block, axis=0)
        scores = (
            jnp.einsum("qhn,khn->hqk", qn, k_nope, precision=HIGHEST)
            + jnp.einsum("qhr,kr->hqk", qr, k_rope, precision=HIGHEST)
        ) * scale
        visible = (start + jnp.arange(block))[:, None] >= positions[None, :]
        probs = rounded(jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1), lowp)
        return jnp.einsum("hqk,khv->qhv", probs, values, precision=HIGHEST)

    context = jax.lax.map(one_block, jnp.arange(0, seq, block)).reshape(seq, heads * vdim)
    return dense(rounded(context, lowp), p["o"], lowp)


def routed_experts(x: jax.Array, p: Dict[str, Any], sizes: Dict[str, Any], lowp: LowP):
    """``(output, (chosen, margin))``: every expert over every token, a mask
    choosing; ``margin`` (tokens,) is how far the last expert chosen lies above
    the first one left out, in the biased scores the choice is made on."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(jnp.float32), precision=HIGHEST))
    ranked, chosen = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), sizes["top_k"] + 1)
    margin, chosen = ranked[:, -2] - ranked[:, -1], chosen[:, :-1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * sizes["routed_scaling_factor"]
    experts = p["router"].shape[-1]
    # (tokens, experts): an expert's weight for a token, nought where not chosen
    weights = jnp.sum(jax.nn.one_hot(chosen, experts, dtype=jnp.float32) * picked[..., None], axis=1)

    def one_expert(total, expert):
        gate, up, down, weight = expert
        return total + weight[:, None] * swiglu(x, gate, up, down, lowp), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], weights.T))
    shared = p["shared"]
    return rounded(total, lowp) + swiglu(x, shared["gate"], shared["up"], shared["down"], lowp), (chosen, margin)


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    matrix = jnp.exp(logits)
    for _ in range(iters):
        matrix = matrix / (jnp.sum(matrix, axis=-1, keepdims=True) + eps)
        matrix = matrix / (jnp.sum(matrix, axis=-2, keepdims=True) + eps)
    return matrix


def hyper_maps(streams: jax.Array, p: Dict[str, Any], sizes: Dict[str, Any]):
    """``(H_pre (seq, n), H_post (seq, n), H_res (seq, n, n))`` of ``streams`` (seq, n, d)."""
    seq, n, d = streams.shape
    flat = rms_norm(streams.reshape(seq, n * d), None, sizes["eps"])
    mapped = jnp.matmul(flat, p["phi"].astype(jnp.float32), precision=HIGHEST)
    alpha, bias = p["alpha"].astype(jnp.float32), p["bias"].astype(jnp.float32)
    pre = alpha[0] * mapped[:, :n] + bias[:n]
    post = alpha[1] * mapped[:, n : 2 * n] + bias[n : 2 * n]
    res = (alpha[2] * mapped[:, 2 * n :] + bias[2 * n :]).reshape(seq, n, n)
    res = jnp.clip(res, sizes["hc_clamp"][0], sizes["hc_clamp"][1])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), sinkhorn(res, sizes["hc_iters"], sizes["hc_eps"])


def sublayer(streams, maps, norm_scale, fn, sizes, lowp):
    pre, post, res = hyper_maps(streams, maps, sizes)
    mixed = jnp.einsum("sn,snd->sd", pre, streams, precision=HIGHEST)
    out = fn(rounded(rms_norm(mixed, norm_scale, sizes["eps"]), lowp))
    extra = None
    if isinstance(out, tuple):
        out, extra = out
    streams = jnp.einsum("smn,snd->smd", res, streams, precision=HIGHEST) + post[:, :, None] * out[:, None, :]
    return streams, extra


@functools.partial(jax.jit, static_argnames=("sizes", "lowp"), donate_argnums=(1,))
def _layer(p: Dict[str, Any], streams: jax.Array, *, sizes, lowp: LowP):
    """One block over one sequence's streams (seq, n, d); also the experts each
    token chose, (seq, top_k), with the choice's margin, (seq,), or ``None`` for
    a dense layer."""
    sizes = json.loads(sizes)  # static arguments are hashable: the sizes travel as their JSON
    streams, _ = sublayer(
        streams, p["attn_hc"], p["attn_norm"], lambda x: attention(x, p["attn"], sizes, lowp), sizes, lowp
    )
    if "moe" in p:
        return sublayer(
            streams, p["mlp_hc"], p["mlp_norm"], lambda x: routed_experts(x, p["moe"], sizes, lowp), sizes, lowp
        )
    mlp = p["mlp"]
    return sublayer(
        streams, p["mlp_hc"], p["mlp_norm"],
        lambda x: swiglu(x, mlp["gate"], mlp["up"], mlp["down"], lowp), sizes, lowp,
    )


@functools.partial(jax.jit, static_argnames=("eps", "lowp", "vocab_block"))
def _head(final_norm, lm_head, streams, *, eps: float, lowp: LowP, vocab_block: int):
    hidden = rounded(rms_norm(jnp.sum(streams, axis=1), final_norm, eps), lowp)
    vocab = lm_head.shape[1]
    block = vocab_block if vocab % vocab_block == 0 else vocab

    def columns(start):
        # the control rounds the head and its input, never the logits
        kernel = jax.lax.dynamic_slice_in_dim(lm_head, start, block, axis=1).astype(jnp.float32)
        if lowp:
            kernel = _lowered(kernel, lowp, axis=-2)
        return jnp.matmul(hidden, kernel, precision=HIGHEST)

    blocks = jax.lax.map(columns, jnp.arange(0, vocab, block))  # (blocks, rows, block)
    return jnp.moveaxis(blocks, 0, 1).reshape(hidden.shape[0], vocab)


def forward(params: Dict[str, Any], ids: jax.Array, rows: jax.Array, lowp: LowP = None, **sizes: Any):
    """``(logits at rows, chosen, margins)``: the logits (len(rows), vocab) of
    one sequence ``ids`` (1, seq) at the positions ``rows``, and per expert
    layer the experts each of those positions chose, (len(rows), top_k), and
    the margin of that choice, (len(rows),)."""
    sizes = {name: value for name, value in sizes.items() if name != "tie_margin"}
    frozen = json.dumps(sizes, sort_keys=True)
    embedded = jnp.take(params["embed"], ids[0], axis=0).astype(jnp.float32)
    streams = jnp.repeat(rounded(embedded, lowp)[:, None, :], sizes["streams"], axis=1)
    chosen, margins = [], []
    for i in range(sum(1 for name in params if name.startswith("layer_"))):
        streams, routed = _layer(params[f"layer_{i}"], streams, sizes=frozen, lowp=lowp)
        if routed is not None:
            chosen.append(routed[0][rows])
            margins.append(routed[1][rows])
    logits = _head(
        params["final_norm"], params["lm_head"], streams[rows], eps=sizes["eps"], lowp=lowp,
        vocab_block=sizes["vocab_block"],
    )
    return logits, chosen, margins


def logits_at(
    params: Dict[str, Any], ids: jax.Array, rows: jax.Array, lowp: LowP = None, tie_margin: float = 0.0,
    **sizes: Any,
) -> jax.Array:
    """Logits (len(rows), vocab) of one padded sequence ``ids`` (1, seq) at the
    positions ``rows``. Causal attention makes right padding harmless.

    ``tie_margin``: a top-k choice is a step, not a rounding. Where the float32
    router has the last expert chosen and the first one left out closer than
    this, in any expert layer, a program in any lower precision may rightly
    choose the other, and with experts that differ its logits are then another
    token's. Such positions say nothing of the program, and their float32
    logits come back flat (all nought), so that no token there lies below the
    best: the comparison holds the program to the positions whose every choice
    is wider than ``tie_margin``, and there a wrong expert shows in full. A
    control (``lowp``) is never flattened: it answers at every position, and is
    judged where float32 was decisive.
    """
    logits, _, margins = forward(params, ids, rows, lowp=lowp, **sizes)
    if lowp is None and tie_margin > 0.0 and margins:
        decisive = jnp.min(jnp.stack(margins), axis=0) >= tie_margin
        logits = jnp.where(decisive[:, None], logits, 0.0)
    return logits
