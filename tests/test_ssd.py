"""``ops/ssd.py``: the chunked scan (the Mosaic kernel interpreted, and its
``jax.numpy`` fallback) and the one-token step against Mamba-2's recurrence
written token by token; the step's Mosaic call on a stack of layers
(interpreted) against the step as it is written."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ssd import ssd_fwd, ssd_step, ssd_step_stacked

B, H, P, N, CHUNK = 2, 4, 8, 16, 16


def _inputs(key, length, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, length, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, length, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(ks[3], (B, length, N), jnp.float32)
    c = jax.random.normal(ks[4], (B, length, N), jnp.float32)
    state = jax.random.normal(ks[5], (B, H, P, N), jnp.float32)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), state)


def recurrence(x, dt, a, b, c, state):
    """Token by token: ``S = exp(dt A) S + dt x B^T``, ``y = S C``."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        return ssd_step(x_t, dt_t, a, b_t, c_t, state)[::-1]

    state, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


@pytest.fixture(scope="module")
def programs():
    """Each path compiled once for the module."""
    return {
        use_kernel: jax.jit(lambda x, dt, a, b, c, s, k=use_kernel: ssd_fwd(
            x, dt, a, b, c, initial_state=s, chunk=CHUNK, use_kernel=k))
        for use_kernel in (True, False)}


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "fallback"])
@pytest.mark.parametrize("length", [48, 40], ids=["chunks", "ragged"])
def test_chunked_scan_is_the_recurrence(programs, use_kernel, length):
    """Outputs and final state, from an initial state, at a whole number of
    chunks and at a length that is no multiple of the chunk."""
    args = _inputs(jax.random.PRNGKey(length), length)
    y, state = programs[use_kernel](*args)
    want_y, want_state = recurrence(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "fallback"])
def test_a_padded_position_leaves_the_state(programs, use_kernel):
    """``dt = 0`` past a sequence's length: the final state is the state at
    its last real position, and the real positions' outputs are unmoved."""
    x, dt, a, b, c, state = _inputs(jax.random.PRNGKey(7), 48)
    lengths = jnp.array([29, 48])
    real = jnp.arange(48)[None] < lengths[:, None]
    y, final = programs[use_kernel](x, dt * real[..., None], a, b, c, state)
    for row, n in enumerate(map(int, lengths)):
        cut = tuple(v[row:row + 1, :n] for v in (x, dt, b, c))
        want_y, want_state = recurrence(cut[0], cut[1], a, cut[2], cut[3],
                                        state[row:row + 1])
        np.testing.assert_allclose(y[row:row + 1, :n], want_y, rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(final[row:row + 1], want_state,
                                   rtol=2e-4, atol=2e-4)


def test_no_initial_state_is_zeros(programs):
    x, dt, a, b, c, _ = _inputs(jax.random.PRNGKey(3), 48)
    zeros = jnp.zeros((B, H, P, N), jnp.float32)
    y, state = jax.jit(lambda *v: ssd_fwd(*v, chunk=CHUNK, use_kernel=False))(
        x, dt, a, b, c)
    want_y, want_state = programs[False](x, dt, a, b, c, zeros)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(state, want_state)


def test_the_kernel_is_the_fallback_in_bfloat16(programs):
    """The arrays' own dtype on the MXU, float32 sums and state: both paths
    round at the same places."""
    args = _inputs(jax.random.PRNGKey(11), 48, jnp.bfloat16)
    y, state = programs[True](*args)
    want_y, want_state = programs[False](*args)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    np.testing.assert_allclose(y.astype(jnp.float32),
                               want_y.astype(jnp.float32), rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(state, want_state, rtol=1e-3, atol=1e-3)


def test_the_step_continues_the_scan(programs):
    """``ssd_step`` from the fallback's final state is the recurrence's own
    next token (it *is* the recurrence: float32 rounding of two compilations
    apart), and the chunked scan over one more token agrees with it."""
    x, dt, a, b, c, state = _inputs(jax.random.PRNGKey(5), 49)
    head = tuple(v[:, :48] for v in (x, dt, b, c))
    _, carried = programs[False](head[0], head[1], a, head[2], head[3], state)
    y_next, after = ssd_step(x[:, 48], dt[:, 48], a, b[:, 48], c[:, 48],
                             carried)
    tail = tuple(v[:, 48:] for v in (x, dt, b, c))
    want_y, want_state = recurrence(tail[0], tail[1], a, tail[2], tail[3],
                                    carried)
    np.testing.assert_allclose(y_next, want_y[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(after, want_state, rtol=1e-5, atol=1e-6)
    y_all, state_all = jax.jit(lambda *v: ssd_fwd(
        *v[:5], initial_state=v[5], chunk=CHUNK, use_kernel=False))(
            x, dt, a, b, c, state)
    np.testing.assert_allclose(y_all[:, 48], y_next, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state_all, after, rtol=2e-4, atol=2e-4)


# -- the step as one call on the stacked state ---------------------------------------

LAYERS = 3
# (slots, heads, P, N): the generating cell's widths, and a small shape whose
# heads do not fill a row of lanes in pairs
STACKS = {"cell": (2, 64, 64, 128), "small": (3, 4, 8, 16)}


def _stack_inputs(shape, dtype, seed=0):
    S, heads, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (S, heads, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (S, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    b = jax.random.normal(ks[3], (S, N), jnp.float32).astype(dtype)
    c = jax.random.normal(ks[4], (S, N), jnp.float32).astype(dtype)
    states = jax.random.normal(ks[5], (LAYERS, S, heads, P, N), jnp.float32)
    return x, dt, a, b, c, states


@pytest.fixture(scope="module")
def stacked():
    return jax.jit(ssd_step_stacked)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(STACKS))
def test_the_stacked_step_is_the_step_on_one_layer(stacked, shape, dtype):
    """``y`` and the stepped layer to float32 rounding (the interpreter on a
    CPU may fuse a multiply and an add that the step rounds apart); every
    other layer of the stack to the bit; a slot that steps by 0 keeps its
    state's bits."""
    x, dt, a, b, c, states = _stack_inputs(STACKS[shape], dtype)
    dt = dt.at[0].set(0.0)
    y, out = stacked(x, dt, a, b, c, states, 1)
    want_y, want = ssd_step(x, dt, a, b, c, states[1])
    assert y.dtype == out.dtype == jnp.float32 and y.shape == x.shape
    np.testing.assert_allclose(out[1], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-5)
    for other in (0, 2):
        np.testing.assert_array_equal(out[other], states[other])
    np.testing.assert_array_equal(out[1, 0], states[1, 0])
    assert not np.array_equal(out[1, 1], states[1, 1])


def test_the_stacked_step_takes_a_traced_layer(stacked):
    """The layer's index as a loop's counter, the stack as its carry (what
    ``decode_step`` builds), against a Python loop over the layers (two
    compilations: float32 rounding apart)."""
    x, dt, a, b, c, states = _stack_inputs(STACKS["small"], jnp.float32, 1)

    @jax.jit
    def looped(states):
        def layer(l, carry):
            states, ys = carry
            y, states = ssd_step_stacked(x, dt, a, b, c, states, l)
            return states, ys.at[l].set(y)
        return jax.lax.fori_loop(
            0, LAYERS, layer,
            (states, jnp.zeros((LAYERS,) + x.shape, jnp.float32)))

    got, got_ys = looped(states)
    want = states
    for l in range(LAYERS):
        y, want = stacked(x, dt, a, b, c, want, l)
        np.testing.assert_allclose(got_ys[l], y, rtol=1e-5, atol=2e-5)
        assert not np.array_equal(want[l], states[l])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
