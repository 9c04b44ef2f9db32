"""Plain GPT-2 in ``jax.numpy``: float32, ``highest`` matmul precision, no
kernels, no cache, no batching tricks. Imports nothing of the program.

It follows the published architecture (Radford et al. 2019; the
``openai-community/gpt2*`` ``config.json`` files): learned token and position
embeddings, pre-LayerNorm blocks of causal multi-head attention and a 4x GELU
(tanh approximation, ``gelu_new``) MLP, a final LayerNorm and a head tied to
the token embedding. One departure, which is the program's too: packed rows
(``segments``) confine attention to a row's own document and restart positions
at each document, which is what training the documents one by one would do.

The parameter tree is the one ``perfbench.weights`` makes: ``wte``, ``wpe``,
``final_norm`` and ``layer_<i>`` with ``attn_norm``, ``qkv``, ``attn_out``,
``mlp_norm``, ``mlp_up``, ``mlp_down``.

``lowp`` selects the control: the same mathematics with every weight matrix and
every activation that a bfloat16 program rounds to bfloat16 (the residual stream,
LayerNorm outputs, queries, keys, values, attention weights, context, the MLP's
hidden layer, the head's input) rounded to a precision below the one the
configuration states. Below bfloat16: ``"int8"`` (symmetric, a scale per token
— last axis — or per output channel) or ``"fp8"`` (``float8_e4m3fn``, no
scale). Below float32 (the toy cells of the tests): ``"bf16"``.
The rounding is straight-through, so the control has gradients too.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


LowP = Optional[str]  # None, "bf16", "int8" or "fp8"


def _lowered(x: jax.Array, lowp: str, axis: int) -> jax.Array:
    if lowp == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        low = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    elif lowp == "bf16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif lowp == "fp8":
        low = jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        raise ValueError(f"unknown control precision {lowp!r}")
    return x + jax.lax.stop_gradient(low - x)


def rounded(x: jax.Array, lowp: LowP) -> jax.Array:
    """An activation as the control keeps it (int8: a scale per token)."""
    return _lowered(x, lowp, axis=-1) if lowp else x


def dense(x: jax.Array, layer: Dict[str, jax.Array], lowp: LowP) -> jax.Array:
    kernel = layer["kernel"].astype(jnp.float32)
    if lowp:
        kernel = _lowered(kernel, lowp, axis=0)
    out = jnp.matmul(rounded(x, lowp), kernel, precision=HIGHEST) + layer["bias"].astype(jnp.float32)
    return rounded(out, lowp)


def layer_norm(x: jax.Array, layer: Dict[str, jax.Array], eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    normed = (x - mean) * jax.lax.rsqrt(var + eps)
    return normed * layer["scale"].astype(jnp.float32) + layer["bias"].astype(jnp.float32)


def gelu_new(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def positions_and_mask(
    seq: int, segments: Optional[jax.Array], batch: int
) -> Tuple[jax.Array, jax.Array]:
    """Positions (batch, seq) and the boolean attention mask (batch, seq, seq)."""
    idx = jnp.arange(seq)
    causal = idx[None, :, None] >= idx[None, None, :]
    if segments is None:
        return jnp.broadcast_to(idx[None, :], (batch, seq)), jnp.broadcast_to(causal, (batch, seq, seq))
    starts = jnp.concatenate(
        [jnp.ones((batch, 1), bool), segments[:, 1:] != segments[:, :-1]], axis=1
    )
    seg_start = jax.lax.cummax(jnp.where(starts, idx[None, :], 0), axis=1)
    same = segments[:, :, None] == segments[:, None, :]
    return idx[None, :] - seg_start, causal & same


def hidden_states(
    params: Dict[str, Any], ids: jax.Array, *, num_heads: int, eps: float,
    segments: Optional[jax.Array] = None, lowp: LowP = None,
) -> jax.Array:
    """Final-LayerNorm output (batch, seq, hidden) for whole rows."""
    batch, seq = ids.shape
    positions, mask = positions_and_mask(seq, segments, batch)
    wte = params["wte"]["embedding"].astype(jnp.float32)
    wpe = params["wpe"]["embedding"].astype(jnp.float32)
    hidden = rounded(wte[ids] + wpe[positions], lowp)
    width = hidden.shape[-1]
    head_dim = width // num_heads
    n_layers = sum(1 for name in params if name.startswith("layer_"))

    def block(hidden, layer):
        normed = rounded(layer_norm(hidden, layer["attn_norm"], eps), lowp)
        qkv = dense(normed, layer["qkv"], lowp)
        q, k, v = (
            part.reshape(batch, seq, num_heads, head_dim).transpose(0, 2, 1, 3)
            for part in jnp.split(qkv, 3, axis=-1)
        )
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(head_dim)
        )
        scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
        probs = rounded(jax.nn.softmax(scores, axis=-1), lowp)
        context = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
        context = context.transpose(0, 2, 1, 3).reshape(batch, seq, width)
        hidden = rounded(hidden + dense(context, layer["attn_out"], lowp), lowp)
        normed = rounded(layer_norm(hidden, layer["mlp_norm"], eps), lowp)
        up = rounded(gelu_new(dense(normed, layer["mlp_up"], lowp)), lowp)
        return rounded(hidden + dense(up, layer["mlp_down"], lowp), lowp), None

    # the blocks one after another, as a scan over their stacked weights: the
    # same arithmetic as a loop, one block to compile instead of n_layers
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *(params[f"layer_{i}"] for i in range(n_layers)))
    hidden, _ = jax.lax.scan(block, hidden, stacked)
    return rounded(layer_norm(hidden, params["final_norm"], eps), lowp)


def head(params: Dict[str, Any], hidden: jax.Array, lowp: LowP = None) -> jax.Array:
    wte = params["wte"]["embedding"].astype(jnp.float32)
    if lowp:
        wte = _lowered(wte, lowp, axis=-1)
    return jnp.matmul(rounded(hidden, lowp), wte.T, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("num_heads", "eps", "lowp"))
def logits_at(
    params: Dict[str, Any], ids: jax.Array, rows: jax.Array, *, num_heads: int, eps: float,
    lowp: LowP = None,
) -> jax.Array:
    """Logits (len(rows), vocab) of one padded sequence ``ids`` (1, seq) at the
    positions ``rows``. Causal attention makes right padding harmless."""
    hidden = hidden_states(params, ids, num_heads=num_heads, eps=eps, lowp=lowp)
    return head(params, hidden[0][rows], lowp)


def packed_loss_sum(
    params: Dict[str, Any], ids: jax.Array, segments: jax.Array, *, num_heads: int, eps: float,
    lowp: LowP = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sum of next-token cross-entropies over the rows' counted targets, and
    their count: a target counts when it continues its own document."""
    hidden = hidden_states(params, ids, num_heads=num_heads, eps=eps, segments=segments, lowp=lowp)
    logits = head(params, hidden[:, :-1], lowp)
    targets = ids[:, 1:]
    counted = (segments[:, 1:] == segments[:, :-1]) & (segments[:, 1:] > 0)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    weights = counted.astype(jnp.float32)
    return jnp.sum((log_z - picked) * weights), jnp.sum(weights)


@functools.partial(jax.jit, static_argnames=("num_heads", "eps", "lowp"))
def _block_loss_and_grad(params, ids, segments, *, num_heads, eps, lowp):
    def loss(p):
        total, count = packed_loss_sum(p, ids, segments, num_heads=num_heads, eps=eps, lowp=lowp)
        return total, count

    (total, count), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return total, count, grads


def loss_and_grads(
    params: Dict[str, Any], ids: jax.Array, segments: jax.Array, *, num_heads: int, eps: float,
    lowp: LowP = None, block_rows: int = 2,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Mean loss over the batch's counted targets and its gradient, in blocks of
    rows so that the float32 logits of a block, not of the batch, are alive."""
    total = count = grads = None
    for start in range(0, ids.shape[0], block_rows):
        t, c, g = _block_loss_and_grad(
            params, ids[start : start + block_rows], segments[start : start + block_rows],
            num_heads=num_heads, eps=eps, lowp=lowp,
        )
        total = t if total is None else total + t
        count = c if count is None else count + c
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = jnp.maximum(count, 1e-8)
    return total / count, jax.tree.map(lambda g: g / count, grads)


@jax.jit
def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "weight_decay", "max_grad_norm"))
def adamw_step(
    params, mu, nu, grads, step, learning_rate, *, b1=0.9, b2=0.999, eps=1e-8,
    weight_decay=0.01, max_grad_norm=1.0,
):
    """Global-norm clipping then AdamW (Loshchilov & Hutter 2019), ``step`` from
    1. Returns the new ``(params, mu, nu)`` and the clipped gradient."""
    norm = global_norm(grads)
    factor = jnp.where(norm < max_grad_norm, 1.0, max_grad_norm / norm)
    grads = jax.tree.map(lambda g: g * factor, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * jnp.square(g), nu, grads)
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)

    def update(p, m, n):
        return p - learning_rate * ((m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p)

    return jax.tree.map(update, params, mu, nu), mu, nu, grads
