"""State-space (Mamba-1) operations with carried state: the selective scan of
a prefill, its one-token form for decode, and the causal depthwise convolution
in front of both.

The recurrence, per channel ``c`` of ``d_inner`` and state index ``n`` of
``d_state``::

    S_t[n, c] = exp(Delta_t[c] * A[n, c]) * S_{t-1}[n, c] + Delta_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n S_t[n, c] * C_t[n] + D[c] * u_t[c]

**Layout.** The state is ``(rows, d_state, d_inner)`` float32: channels on the
lanes (5120 is 40 whole vregs' worth), the 16 state indices on the sublanes, so
that ``B_t`` and ``C_t`` broadcast along lanes and ``Delta_t``, ``u_t`` along
sublanes, and the sum over ``n`` is a sublane reduction. ``A`` is handed over in
the same layout, ``(d_state, d_inner)``, already ``-exp(A_log)``.

**Carried state.** Every function takes the state the previous call left and
returns the one it leaves: a chunked prefill runs :func:`selective_scan` chunk
by chunk, a decode step :func:`selective_step` on the same leaf. ``valid``
(rows,) is the count of real tokens of a row in this call; what follows them is
padding and moves neither the state (its ``Delta`` is nought: the decay is 1 and
the input 0) nor the convolution's tail (the last ``taps - 1`` real inputs).

**Implementations.** :func:`selective_step` is a Mosaic kernel on a TPU (the
state is read and written once, in place: a step is memory-bound on it) under
the scope ``ssm_step``, which a device trace names the call by; off a TPU, and
under ``impl="xla"``, the same arithmetic in ``jax.numpy``. :func:`selective_scan`
is a Mosaic kernel too where the shapes divide into its tiles (``ssm_scan``: a
grid step takes 64 tokens of one row and 512 channels, the tile's state stays
in VMEM from token to token and from step to step of the time axis, so the
state crosses HBM once a call and not once a token), and elsewhere a
``lax.scan`` over tokens, unrolled, under the same scope.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unionml_tpu.ops.attention import on_tpu

__all__ = ["causal_conv1d", "selective_scan", "selective_step"]

#: slots a grid step of the decode kernel takes: four states of 16 x 5120 float32
#: are 1.3 MB in and as much out, double-buffered 5 MB of VMEM, and a step's
#: fixed cost is then small beside its 3 us of HBM time
_STEP_ROWS = 4
_SCAN_UNROLL = 8
#: tokens and channels a grid step of the prefill kernel takes: the state of a
#: channel tile, 16 x 512 float32, is 8 vregs that stay in VMEM from token to
#: token, and 64 tokens' inputs and outputs are 1 MB a buffer as VMEM pads them
_SCAN_TOKENS = 64
_SCAN_CHANNELS = 512
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def causal_conv1d(
    u: jax.Array, weight: jax.Array, bias: jax.Array, tail: jax.Array,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over time with a carried tail.

    :param u: ``(rows, seq, channels)`` inputs of this call.
    :param weight: ``(taps, channels)``; tap ``taps - 1`` meets the current input.
    :param bias: ``(channels,)``.
    :param tail: ``(rows, taps - 1, channels)``: the inputs before ``u`` (zeros
        at a sequence's start).
    :param valid: ``(rows,)`` real tokens a row; ``None``: all ``seq``.
    :returns: ``(out (rows, seq, channels) float32, new tail)``: the last ``taps
        - 1`` real inputs, what the next call of the row starts from.
    """
    rows, seq, channels = u.shape
    taps = weight.shape[0]
    window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)  # input t is window[t + taps - 1]
    w = weight.astype(jnp.float32)
    out = sum(window[:, k : k + seq].astype(jnp.float32) * w[k] for k in range(taps))
    out = out + bias.astype(jnp.float32)
    if valid is None:
        new_tail = window[:, seq:]
    else:
        # real inputs valid - (taps - 1) .. valid - 1 are window[valid .. valid + taps - 2]
        at = valid.astype(jnp.int32)[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
    return out, new_tail.astype(tail.dtype)


def _step_math(state, delta, u, a, b, c, d):
    """One token: ``state`` (rows, n, ch), ``delta`` and ``u`` (rows, 1, ch),
    ``a`` (n, ch), ``b`` and ``c`` (rows, n, 1), ``d`` (1, ch); all float32."""
    state = jnp.exp(delta * a) * state + (delta * u) * b
    y = jnp.sum(state * c, axis=1, keepdims=True) + d * u
    return state, y


def _scan_kernel(state_ref, delta_ref, u_ref, a_ref, b_ref, c_ref, d_ref, y_ref, out_ref, carry_ref, *, tokens):
    """One row, one tile of channels, ``tokens`` tokens: the state stays in
    ``carry_ref`` from one grid step of the time axis to the next."""
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _start():
        carry_ref[...] = state_ref[0]

    a, d = a_ref[...], d_ref[...]

    def token(t, state):
        u = u_ref[0, t].astype(jnp.float32)  # (1, channels)
        state, y = _step_math(state[None], delta_ref[0, t][None], u[None], a, b_ref[0, t][None], c_ref[0, t][None], d)
        y_ref[0, t] = y[0]
        return state[0]

    # unrolled by hand: Mosaic's loop takes an unroll of 1 or of all its steps
    unroll = _SCAN_UNROLL if tokens % _SCAN_UNROLL == 0 else 1

    def group(g, state):
        for j in range(unroll):
            state = token(g * unroll + j, state)
        return state

    state = jax.lax.fori_loop(0, tokens // unroll, group, carry_ref[...])
    carry_ref[...] = state

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _finish():
        out_ref[0] = state


def _scan_tiles(seq: int, channels: int) -> Optional[Tuple[int, int]]:
    """``(tokens, channels)`` of a grid step of the prefill kernel, or ``None``
    where the shapes do not divide into them (the XLA loop then runs)."""
    tokens = seq if seq <= _SCAN_TOKENS else _SCAN_TOKENS
    tile = _SCAN_CHANNELS if channels % _SCAN_CHANNELS == 0 else channels
    if seq % tokens or tile % 128:
        return None
    return tokens, tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_pallas(state, delta, u, a, b, c, d, *, interpret: bool):
    rows, seq, channels = u.shape
    n = a.shape[0]
    tokens, tile = _scan_tiles(seq, channels)
    # time on a leading axis of every per-token operand: a token is then an
    # index on an untiled axis, and B and C come as columns that broadcast over lanes
    per_token = lambda x: x[:, :, None, :]
    by_time = lambda r, ch, t: (r, t, 0, ch)
    columns = lambda r, ch, t: (r, t, 0, 0)
    by_row = lambda r, ch, t: (r, 0, ch)
    call = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=tokens),
        grid=(rows, channels // tile, seq // tokens),
        in_specs=[
            pl.BlockSpec((1, n, tile), by_row),
            pl.BlockSpec((1, tokens, 1, tile), by_time),
            pl.BlockSpec((1, tokens, 1, tile), by_time),
            pl.BlockSpec((n, tile), lambda r, ch, t: (0, ch)),
            pl.BlockSpec((1, tokens, n, 1), columns),
            pl.BlockSpec((1, tokens, n, 1), columns),
            pl.BlockSpec((1, tile), lambda r, ch, t: (0, ch)),
        ],
        out_specs=[pl.BlockSpec((1, tokens, 1, tile), by_time), pl.BlockSpec((1, n, tile), by_row)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, seq, 1, channels), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * rows * seq * n * channels, transcendentals=rows * seq * n * channels,
            bytes_accessed=rows * seq * (channels * 10 + 2 * n * 4) + 2 * rows * n * channels * 4,
        ),
        interpret=interpret,
        name="ssm_scan",
    )
    with jax.named_scope("ssm_scan"):
        y, state = call(state, per_token(delta), per_token(u), a, b[..., None], c[..., None], d)
    return y[:, :, 0, :], state


def selective_scan(
    u: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array,
    state: jax.Array, valid: Optional[jax.Array] = None, impl: str = "auto", interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over ``seq`` tokens, from ``state`` on.

    :param u: ``(rows, seq, channels)`` inputs (after the convolution and SiLU).
    :param delta: ``(rows, seq, channels)`` float32 step sizes (after softplus).
    :param a: ``(d_state, channels)`` float32, negative.
    :param b / c: ``(rows, seq, d_state)``.
    :param d: ``(channels,)``.
    :param state: ``(rows, d_state, channels)`` float32 to start from.
    :param valid: ``(rows,)`` real tokens a row: those after them leave the
        state as it is (their outputs are read by nothing).
    :param impl: ``"auto"`` (on a TPU the kernel, where the shapes divide into
        its tiles; XLA's loop elsewhere), ``"pallas"`` or ``"xla"``; ``interpret``
        as in :func:`selective_step`.
    :returns: ``(y (rows, seq, channels) float32, state after the last real token)``.
    """
    rows, seq, channels = u.shape
    f32 = jnp.float32
    delta = delta.astype(f32)
    if valid is not None:
        real = jnp.arange(seq, dtype=jnp.int32)[None, :] < valid.astype(jnp.int32)[:, None]
        delta = jnp.where(real[:, :, None], delta, 0.0)
    d = d.astype(f32).reshape(1, channels)
    a = a.astype(f32)
    if impl == "auto":
        impl = "pallas" if on_tpu() and _scan_tiles(seq, channels) else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"Unknown ssm scan impl {impl!r}; expected 'auto', 'pallas', or 'xla'")
    if impl == "pallas" and _scan_tiles(seq, channels) is not None:
        if not interpret and not on_tpu():
            raise RuntimeError(
                f"selective_scan(impl='pallas') needs a TPU backend, found {jax.default_backend()!r}; "
                "use impl='auto'/'xla', or interpret=True in tests"
            )
        return _scan_pallas(state.astype(f32), delta, u, a, b.astype(f32), c.astype(f32), d, interpret=interpret)

    def token(state, inputs):
        delta_t, u_t, b_t, c_t = inputs  # (rows, ch), (rows, ch), (rows, n), (rows, n)
        state, y = _step_math(
            state, delta_t[:, None, :], u_t.astype(f32)[:, None, :], a,
            b_t.astype(f32)[:, :, None], c_t.astype(f32)[:, :, None], d,
        )
        return state, y[:, 0, :]

    time_major = lambda x: jnp.swapaxes(x, 0, 1)
    with jax.named_scope("ssm_scan"):
        state, y = jax.lax.scan(
            token, state.astype(f32), (time_major(delta), time_major(u), time_major(b), time_major(c)),
            unroll=min(_SCAN_UNROLL, seq),
        )
    return time_major(y), state


def _step_kernel(state_ref, delta_ref, u_ref, a_ref, b_ref, c_ref, d_ref, live_ref, out_ref, y_ref):
    state = state_ref[...]
    new, y = _step_math(
        state, delta_ref[...], u_ref[...].astype(jnp.float32), a_ref[...], b_ref[...], c_ref[...], d_ref[...]
    )
    out_ref[...] = jnp.where(live_ref[...] > 0, new, state)
    y_ref[...] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(state, delta, u, a, b, c, d, live, *, interpret: bool):
    rows, n, channels = state.shape
    block = next(r for r in (_STEP_ROWS, 2, 1) if rows % r == 0)
    by_row = lambda i: (i, 0, 0)
    whole = lambda i: (0, 0)
    call = pl.pallas_call(
        _step_kernel,
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, n, channels), by_row),
            pl.BlockSpec((block, 1, channels), by_row),
            pl.BlockSpec((block, 1, channels), by_row),
            pl.BlockSpec((n, channels), whole),
            pl.BlockSpec((block, n, 1), by_row),
            pl.BlockSpec((block, n, 1), by_row),
            pl.BlockSpec((1, channels), whole),
            pl.BlockSpec((block, 1, 1), by_row),
        ],
        out_specs=[pl.BlockSpec((block, n, channels), by_row), pl.BlockSpec((block, 1, channels), by_row)],
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
            jax.ShapeDtypeStruct((rows, 1, channels), jnp.float32),
        ],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=6 * rows * n * channels, transcendentals=rows * n * channels,
            bytes_accessed=2 * rows * n * channels * 4 + 3 * rows * channels * 4,
        ),
        interpret=interpret,
        name="ssm_step",
    )
    # the scope, innermost: XLA names the Mosaic call after it, so a device
    # trace shows the state update as ``ssm_step`` whatever jit wraps it
    with jax.named_scope("ssm_step"):
        return call(state, delta, u, a, b, c, d, live)


def selective_step(
    u: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array,
    state: jax.Array, live: Optional[jax.Array] = None, impl: str = "auto", interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`selective_scan` for one token a row, the state updated in place.

    :param u / delta: ``(rows, channels)``; ``b / c``: ``(rows, d_state)``.
    :param state: ``(rows, d_state, channels)`` float32; a caller that donates
        it gets the update without a copy.
    :param live: ``(rows,)`` bool; a row that is not live keeps its state (its
        output is read by nothing): a decode step's retired and reserved slots.
    :param impl: ``"auto"`` (the kernel on a TPU, XLA elsewhere), ``"pallas"`` or
        ``"xla"``. ``interpret`` runs the kernel under the Pallas interpreter
        (tests); without it the kernel off a TPU is an error.
    :returns: ``(y (rows, channels) float32, new state)``.
    """
    rows, channels = u.shape
    f32 = jnp.float32
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"Unknown ssm step impl {impl!r}; expected 'auto', 'pallas', or 'xla'")
    if live is None:
        live = jnp.ones((rows,), jnp.bool_)
    operands = (
        delta.astype(f32)[:, None, :], u[:, None, :], a.astype(f32), b.astype(f32)[:, :, None],
        c.astype(f32)[:, :, None], d.astype(f32).reshape(1, channels),
    )
    if impl == "xla":
        with jax.named_scope("ssm_step"):
            new, y = _step_math(state, operands[0], operands[1].astype(f32), *operands[2:])
            return y[:, 0, :], jnp.where(live[:, None, None], new, state)
    if not interpret and not on_tpu():
        raise RuntimeError(
            f"selective_step(impl='pallas') needs a TPU backend, found {jax.default_backend()!r}; "
            "use impl='auto'/'xla', or interpret=True in tests"
        )
    new, y = _step_pallas(
        state, *operands, live.astype(jnp.int32)[:, None, None], interpret=interpret
    )
    return y[:, 0, :], new
