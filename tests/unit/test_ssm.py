"""The state-space operations against a plain loop: the selective scan with
carried state, its one-token form (both arms, the kernel under the Pallas
interpreter), and the causal convolution's carried tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.ops.ssm import causal_conv1d, selective_scan, selective_step

ROWS, SEQ, CH, N = 3, 11, 16, 4


def _inputs(seed=0, rows=ROWS, seq=SEQ):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        u=f(rows, seq, CH), delta=jax.nn.softplus(f(rows, seq, CH) - 1.0), a=-jnp.exp(f(N, CH)),
        b=f(rows, seq, N), c=f(rows, seq, N), d=f(CH), state=f(rows, N, CH),
    )


def _loop(u, delta, a, b, c, d, state, valid=None):
    """Token by token, row by row, element by element: numpy, no broadcasting tricks."""
    u, delta, a, b, c, d = (np.asarray(x, np.float64) for x in (u, delta, a, b, c, d))
    state = np.array(state, np.float64)
    rows, seq, ch = u.shape
    y = np.zeros((rows, seq, ch))
    for r in range(rows):
        for t in range(seq if valid is None else int(valid[r])):
            for ci in range(ch):
                for n in range(a.shape[0]):
                    state[r, n, ci] = np.exp(delta[r, t, ci] * a[n, ci]) * state[r, n, ci] \
                        + delta[r, t, ci] * u[r, t, ci] * b[r, t, n]
                y[r, t, ci] = (state[r, :, ci] * c[r, t]).sum() + d[ci] * u[r, t, ci]
    return y, state


def test_scan_is_the_plain_loop():
    x = _inputs()
    y, state = selective_scan(**x)
    want_y, want_state = _loop(**x)
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


def test_scan_in_chunks_carries_its_state():
    """Two calls, the second from the state the first left, are one call."""
    x = _inputs(1)
    whole_y, whole_state = selective_scan(**x)
    cut = 4  # inside the sequence, no multiple of the unroll
    part = lambda lo, hi: {k: (v[:, lo:hi] if k in ("u", "delta", "b", "c") else v) for k, v in x.items()}
    y0, state = selective_scan(**part(0, cut))
    y1, state = selective_scan(**{**part(cut, SEQ), "state": state})
    np.testing.assert_allclose(jnp.concatenate([y0, y1], axis=1), whole_y, atol=1e-5)
    np.testing.assert_allclose(state, whole_state, atol=1e-5)


def test_padding_does_not_enter_the_state():
    x = _inputs(2)
    valid = jnp.asarray([SEQ, 5, 0])
    y, state = selective_scan(**x, valid=valid)
    want_y, want_state = _loop(**x, valid=np.asarray(valid))
    np.testing.assert_allclose(state, want_state, atol=1e-5)
    np.testing.assert_array_equal(state[2], x["state"][2])  # a row of padding alone: untouched
    for r, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(y[r, :n], want_y[r, :n], atol=1e-5)


@pytest.mark.parametrize("impl,interpret", [("xla", False), ("pallas", True)])
def test_one_token_form_is_a_scan_of_one(impl, interpret):
    x = _inputs(3, rows=4, seq=1)
    want_y, want_state = selective_scan(**x)
    live = jnp.asarray([True, False, True, True])
    y, state = selective_step(
        x["u"][:, 0], x["delta"][:, 0], x["a"], x["b"][:, 0], x["c"][:, 0], x["d"], x["state"],
        live=live, impl=impl, interpret=interpret,
    )
    np.testing.assert_allclose(y[live], want_y[:, 0][live], atol=1e-5)
    np.testing.assert_allclose(state[live], want_state[live], atol=1e-5)
    np.testing.assert_array_equal(state[1], x["state"][1])  # a row that is not live keeps its state


def test_the_kernel_off_a_tpu_is_an_error_not_a_slow_path():
    x = _inputs(4, seq=1)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        selective_step(x["u"][:, 0], x["delta"][:, 0], x["a"], x["b"][:, 0], x["c"][:, 0], x["d"], x["state"],
                       impl="pallas")
    with pytest.raises(ValueError, match="Unknown ssm step impl"):
        selective_step(x["u"][:, 0], x["delta"][:, 0], x["a"], x["b"][:, 0], x["c"][:, 0], x["d"], x["state"],
                       impl="mosaic")


def _conv_loop(u, weight, bias):
    u, weight, bias = (np.asarray(x, np.float64) for x in (u, weight, bias))
    rows, seq, ch = u.shape
    taps = weight.shape[0]
    out = np.zeros_like(u)
    for t in range(seq):
        for k in range(taps):
            if t - (taps - 1) + k >= 0:
                out[:, t] += weight[k] * u[:, t - (taps - 1) + k]
    return out + bias


@pytest.mark.parametrize("cut", [1, 2, 3, 5])
def test_convolution_carries_its_tail_over_a_cut_inside_its_taps(cut):
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(2, 9, CH)), jnp.float32)
    weight, bias = jnp.asarray(rng.normal(size=(4, CH)), jnp.float32), jnp.asarray(rng.normal(size=(CH,)), jnp.float32)
    want = _conv_loop(u, weight, bias)
    empty = jnp.zeros((2, 3, CH), jnp.float32)
    first, tail = causal_conv1d(u[:, :cut], weight, bias, empty)
    second, tail = causal_conv1d(u[:, cut:], weight, bias, tail)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), want, atol=1e-5)
    np.testing.assert_array_equal(tail, u[:, -3:])


def test_convolutions_tail_is_the_last_real_inputs():
    """A padded row's tail holds its last three real inputs: those of the call,
    and before them the tail it came with."""
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.normal(size=(3, 6, CH)), jnp.float32)
    weight, bias = jnp.ones((4, CH)), jnp.zeros((CH,))
    before = jnp.asarray(rng.normal(size=(3, 3, CH)), jnp.float32)
    _, tail = causal_conv1d(u, weight, bias, before, valid=jnp.asarray([6, 2, 0]))
    np.testing.assert_array_equal(tail[0], u[0, 3:6])
    np.testing.assert_array_equal(tail[1], jnp.concatenate([before[1, 2:], u[1, :2]]))
    np.testing.assert_array_equal(tail[2], before[2])


@pytest.mark.parametrize("seq,channels", [(16, 128), (128, 1024)])
def test_the_scan_kernel_is_the_xla_loop(seq, channels):
    """Under the Pallas interpreter: one time chunk and one channel tile, and
    two of each (the state carried in VMEM over the chunk's edge), padding and
    a state to start from."""
    rng = np.random.default_rng(8)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x = dict(
        u=f(2, seq, channels), delta=jax.nn.softplus(f(2, seq, channels) - 1.0), a=-jnp.exp(f(N, channels)),
        b=f(2, seq, N), c=f(2, seq, N), d=f(channels), state=f(2, N, channels),
    )
    valid = jnp.asarray([seq, seq - 5])
    want_y, want_state = selective_scan(**x, valid=valid, impl="xla")
    y, state = selective_scan(**x, valid=valid, impl="pallas", interpret=True)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    np.testing.assert_allclose(y[0], want_y[0], atol=2e-5)
    np.testing.assert_allclose(y[1, : seq - 5], want_y[1, : seq - 5], atol=2e-5)


def test_shapes_the_kernel_cannot_tile_take_the_xla_loop():
    from unionml_tpu.ops.ssm import _scan_tiles

    assert _scan_tiles(1024, 5120) == (64, 512) and _scan_tiles(32, 128) == (32, 128)
    assert _scan_tiles(100, 5120) is None and _scan_tiles(64, 64) is None
    x = _inputs(9)  # 11 tokens of 16 channels: no tile; "pallas" falls through to the loop, no error
    y, _ = selective_scan(**x, impl="pallas")
    np.testing.assert_allclose(y, selective_scan(**x, impl="xla")[0], atol=1e-6)
