"""Plain Phi-4-mini-flash (SambaY with differential attention) in ``jax.numpy``:
float32, ``highest`` matmul precision, no kernel, no cache, no chunking of the
recurrence. Imports nothing of the program.

It follows ``microsoft/Phi-4-mini-flash-reasoning`` ``config.json`` and the
papers the configuration's ``assumed`` names (arXiv:2507.06607 for the layout
and the gated memory unit, arXiv:2312.00752 for Mamba-1, arXiv:2410.05258 for
differential attention), layer by layer. Every layer ``i`` of ``L``:

    h = x + Mixer_i(LN(x));  out = h + W2 (SiLU(g) * u),  [g ; u] = LN'(h) W1

then a final LayerNorm and the tied embedding as head. The mixers:

- **Mamba-1** (even ``i <= L/2``): ``[u ; z] = x W_in``; ``u' = SiLU(conv1d(u))``
  (depthwise, causal, ``d_conv`` taps, bias); ``[r ; B ; C] = u' W_x``; ``Delta =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(Delta_t A) * S_{t-1}
  + (Delta_t u'_t) B_t^T``; ``y_t = S_t C_t + D * u'_t``; output ``(y * SiLU(z))
  W_out``. The recurrence is a ``lax.scan`` over tokens. Layer ``L/2`` also
  gives the memory ``m = y``, before the gate.
- **Differential attention** (odd ``i < L/2`` over the last ``window`` keys, key
  ``j`` visible to query ``t`` iff ``t - window < j <= t``; layer ``L/2 + 1``
  over all keys). Query heads ``2h, 2h+1`` are the pair ``q1_h, q2_h``; key
  heads ``2g, 2g+1`` are ``k1_g, k2_g`` and value heads ``2g, 2g+1`` joined are
  ``V_g``; pair ``h`` uses group ``g = h // 2``. ``A1 = softmax(q1 k1^T /
  sqrt(d)) V``, ``A2`` alike; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's
  index; ``O_h = (1 - lambda_init) RMSNorm(A1 - lambda A2)``; ``concat_h(O_h)
  W_o``. Attention by masks, every position.
- **Gated memory unit** (even ``i > L/2 + 1``): ``(m * SiLU(LN(x) W_a)) W_b``.
- **Differential cross attention** (odd ``i > L/2 + 1``): its own ``W_q``,
  ``W_o``, lambdas and sub-norm; keys and values are layer ``L/2 + 1``'s.

No positional encoding. Every position runs through every layer (the program's
prefill runs the layers after ``L/2 + 1`` for the positions it reads only).

Size: each layer is one jitted call (the hidden state donated), attention
runs in query blocks and the head in blocks of the vocabulary, so that at the
published widths it fits beside the program's bfloat16 weights.

``lowp`` selects the control, as in the other references: the same
mathematics with every weight matrix and every activation that a bfloat16
program keeps in bfloat16 rounded to a precision below the one the
configuration states (``"fp8"``, ``"int8"``; ``"bf16"`` below the float32 of
the tests' toy cells). The recurrent state, ``Delta``, the softmaxes and the
norms, float32 in the configuration, stay float32.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

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


def dense(x: jax.Array, kernel: jax.Array, lowp: LowP, bias: Optional[jax.Array] = None) -> jax.Array:
    kernel = kernel.astype(jnp.float32)
    if lowp:
        kernel = _lowered(kernel, lowp, axis=-2)  # int8: a scale per output channel
    out = jnp.matmul(rounded(x, lowp), kernel, precision=HIGHEST)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return rounded(out, lowp)


def layer_norm(x: jax.Array, p: Dict[str, Any], eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


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


def mamba(x: jax.Array, p: Dict[str, Any], sizes: Dict[str, Any], lowp: LowP):
    """``(output (seq, d), y (seq, d_inner))`` of one sequence ``x`` (seq, d)."""
    d_inner, d_state = p["A_log"].shape
    dt_rank = p["dt_proj"].shape[0]
    taps = p["conv_w"].shape[0]
    uz = dense(x, p["in_proj"], lowp)
    u, z = uz[:, :d_inner], uz[:, d_inner:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d_inner), jnp.float32), u], axis=0)
    conv = sum(
        padded[k : k + u.shape[0]] * p["conv_w"][k].astype(jnp.float32) for k in range(taps)
    ) + p["conv_b"].astype(jnp.float32)
    u = rounded(jax.nn.silu(conv), lowp)
    rbc = dense(u, p["x_proj"], lowp)
    r, b, c = rbc[:, :dt_rank], rbc[:, dt_rank : dt_rank + d_state], rbc[:, dt_rank + d_state :]
    kernel = p["dt_proj"].astype(jnp.float32)
    if lowp:
        kernel = _lowered(kernel, lowp, axis=-2)
    delta = jax.nn.softplus(jnp.matmul(r, kernel, precision=HIGHEST) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))

    def token(state, inputs):
        delta_t, u_t, b_t, c_t = inputs
        state = jnp.exp(delta_t[:, None] * a) * state + (delta_t * u_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((d_inner, d_state), jnp.float32), (delta, u, b, c))
    y = rounded(y + p["D"].astype(jnp.float32) * u, lowp)
    return dense(rounded(y * jax.nn.silu(z), lowp), p["out_proj"], lowp), y


def differential(q, k, v, p, layer: int, window: Optional[int], sizes, lowp: LowP) -> jax.Array:
    """Differential attention of queries ``q`` (seq, heads, dim) over keys ``k``
    and values ``v`` (seq, key heads, dim), causal, the last ``window`` keys
    where given: ``concat_h(O_h)`` (seq, heads / 2 * 2 dim)."""
    seq, heads, dim = q.shape
    groups = k.shape[1] // 2
    per_group = heads // 2 // groups  # pairs that share a key group
    q = q.reshape(seq, groups, per_group, 2, dim)
    k = k.reshape(seq, groups, 2, dim)
    v = v.reshape(seq, groups, 2 * dim)
    f32 = lambda name: p[name].astype(jnp.float32)
    init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) - jnp.exp(
        jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + init
    positions = jnp.arange(seq)
    block = min(sizes["query_block"], seq)
    if seq % block:
        raise ValueError(f"sequence of {seq} is no multiple of the query block {block}")

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("tgpwd,sgwd->gpwts", qb, k, precision=HIGHEST) * dim ** -0.5
        at = (start + jnp.arange(block))[:, None]
        visible = positions[None, :] <= at
        if window is not None:
            visible = visible & (positions[None, :] > at - window)
        probs = rounded(jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1), lowp)
        attended = jnp.einsum("gpwts,sgv->tgpwv", probs, v, precision=HIGHEST)
        diff = attended[:, :, :, 0] - lam * attended[:, :, :, 1]  # (block, groups, pairs, 2 dim)
        normed = diff * jax.lax.rsqrt(jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + sizes["eps"])
        return (1.0 - init) * normed * f32("subln")

    out = jax.lax.map(one_block, jnp.arange(0, seq, block))
    return out.reshape(seq, heads // 2 * 2 * dim)


def attention(x, p, layer: int, window: Optional[int], sizes, lowp: LowP):
    """``(output, keys, values)`` of a layer with keys of its own."""
    heads, key_heads, dim = sizes["heads"], sizes["key_heads"], sizes["head_dim"]
    qkv = dense(x, p["qkv"], lowp, p["qkv_bias"])
    q = qkv[:, : heads * dim].reshape(-1, heads, dim)
    k = qkv[:, heads * dim : (heads + key_heads) * dim].reshape(-1, key_heads, dim)
    v = qkv[:, (heads + key_heads) * dim :].reshape(-1, key_heads, dim)
    out = p["out"]  # lambdas, sub-norm and W_o
    context = rounded(differential(q, k, v, out, layer, window, sizes, lowp), lowp)
    return dense(context, out["o"], lowp, out["o_bias"]), k, v


def cross_attention(x, p, layer: int, k, v, sizes, lowp: LowP) -> jax.Array:
    heads, dim = sizes["heads"], sizes["head_dim"]
    q = dense(x, p["q"], lowp, p["q_bias"]).reshape(-1, heads, dim)
    out = p["out"]
    context = rounded(differential(q, k, v, out, layer, None, sizes, lowp), lowp)
    return dense(context, out["o"], lowp, out["o_bias"])


@functools.partial(jax.jit, static_argnames=("layer", "sizes", "lowp"), donate_argnums=(1,))
def _layer(p: Dict[str, Any], x: jax.Array, shared, *, layer: int, sizes: str, lowp: LowP):
    """One block over one sequence ``x`` (seq, d). ``shared`` is ``(m, k, v)`` as
    far as the layers before have made them; returns ``(x, made)`` with what
    this layer adds to it (``None`` where nothing)."""
    sizes = json.loads(sizes)  # static arguments are hashable: the sizes travel as their JSON
    kind = layer_kind(layer, sizes["layers"])
    normed = rounded(layer_norm(x, p["norm"], sizes["eps"]), lowp)
    mixer, made = p["mixer"], None
    if kind == "mamba":
        out, y = mamba(normed, mixer, sizes, lowp)
        made = y if layer == sizes["layers"] // 2 else None
    elif kind in ("window", "full"):
        out, k, v = attention(normed, mixer, layer, sizes["window"] if kind == "window" else None, sizes, lowp)
        made = (k, v) if kind == "full" else None
    elif kind == "gmu":
        gate = jax.nn.silu(dense(normed, mixer["in_proj"], lowp))
        out = dense(rounded(shared[0] * gate, lowp), mixer["out_proj"], lowp)
    else:
        out = cross_attention(normed, mixer, layer, shared[1], shared[2], sizes, lowp)
    x = x + out
    normed = rounded(layer_norm(x, p["mlp_norm"], sizes["eps"]), lowp)
    gu = dense(normed, p["mlp"]["up"], lowp)
    inner = gu.shape[-1] // 2
    x = x + dense(rounded(jax.nn.silu(gu[:, :inner]) * gu[:, inner:], lowp), p["mlp"]["down"], lowp)
    return x, made


@functools.partial(jax.jit, static_argnames=("eps", "lowp", "vocab_block"))
def _head(final_norm, embedding, x, *, eps: float, lowp: LowP, vocab_block: int):
    hidden = rounded(layer_norm(x, final_norm, eps), lowp)
    vocab = embedding.shape[0]
    block = vocab_block if vocab % vocab_block == 0 else vocab

    def rows(start):
        # the control rounds the head and its input, never the logits
        kernel = jax.lax.dynamic_slice_in_dim(embedding, start, block, axis=0).astype(jnp.float32)
        if lowp:
            kernel = _lowered(kernel, lowp, axis=-1)
        return jnp.matmul(hidden, kernel.T, precision=HIGHEST)

    blocks = jax.lax.map(rows, jnp.arange(0, vocab, block))  # (blocks, rows, block)
    return jnp.moveaxis(blocks, 0, 1).reshape(hidden.shape[0], vocab)


def logits_at(params: Dict[str, Any], ids: jax.Array, rows: jax.Array, lowp: LowP = None, **sizes: Any) -> jax.Array:
    """Logits (len(rows), vocab) of one padded sequence ``ids`` (1, seq) at the
    positions ``rows``. Every mixer is causal, so right padding is harmless."""
    frozen = json.dumps(sizes, sort_keys=True)
    x = rounded(jnp.take(params["embed"]["embedding"], ids[0], axis=0).astype(jnp.float32), lowp)
    shared = [None, None, None]
    for i in range(sizes["layers"]):
        x, made = _layer(params[f"layer_{i}"], x, tuple(shared), layer=i, sizes=frozen, lowp=lowp)
        if isinstance(made, tuple):
            shared[1], shared[2] = made
        elif made is not None:
            shared[0] = made
    return _head(
        params["final_norm"], params["embed"]["embedding"], x[rows], eps=sizes["eps"], lowp=lowp,
        vocab_block=sizes["vocab_block"],
    )
