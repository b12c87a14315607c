"""Mamba-2's selective state-space scan (state-space duality), in chunks.

The recurrence, a head at a time, with a decay the *input* sets (``dt_t`` is
a tensor, after its softplus; ``A`` < 0 a head)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      # [P, N], float32
    y_t = S_t C_t

``x`` is ``H`` heads of ``P`` values; ``B`` and ``C`` are ``N`` wide and every
head shares them (one group). A position with ``dt_t = 0`` leaves the state as
it was: that is how a right-padded prompt hands over the state of its last
real position.

Over a chunk of ``Q`` tokens, with ``a_t = dt_t A`` and ``cum_i = sum_{k<=i}
a_k`` inside the chunk (``S`` the state before it)::

    y  = ((C B^T) * M) (dt x) + exp(cum_i) C S^T,   M[i, j] = exp(cum_i - cum_j) for j <= i, else 0
    S' = exp(cum_Q) S + sum_j exp(cum_Q - cum_j) (dt x)_j B_j^T

``M`` is built from the differences (never ``exp(cum_i)`` times ``exp(-cum_j)``:
a head that forgets quickly would overflow). ``C B^T`` and ``M``'s product with
``dt x`` go to the MXU in the arrays' dtype, as the flash kernel's ``p`` does;
the cumulative sums, the state and every product that reads or writes it are
float32.

Three entry points. ``ssd_fwd`` with ``use_kernel=True`` is one Mosaic call:
the grid runs over (batch, groups of heads, chunks), the state of a group of
heads lives in VMEM scratch across the chunk axis, starts from an optional
initial state and leaves as the final one; ``C B^T`` is made once a grid step
for all its heads. ``use_kernel=False`` is the same arithmetic in
``jax.numpy`` under a ``lax.scan`` over chunks: what the CPU tests take and
what the interpreted kernel is compared with. ``ssd_step`` is the recurrence
itself for one token over a batch of slots, plain ``jax.numpy``: it is bound
by the state's bytes. Forward only. It sits beside
``ops/linear_attention.py`` (the same chunk, the same float32 state across the
chunk axis) and does not replace it: there the decay is a constant a head and
a head's value is as wide as its state.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _NN, _backend_is_cpu, _dot

CHUNK = 256            # tokens a chunk: M is [CHUNK, CHUNK]
HEADS_PER_STEP = 8     # heads a grid step walks: they share one C B^T
KERNEL_NAME = "ssd_fwd"


def _chunk_sums(dt: jax.Array, a: jax.Array, chunk: int) -> jax.Array:
    """``cum_i`` [B, L, H] float32: the running sum of ``dt_t A`` from the
    start of each position's chunk (L a multiple of ``chunk``)."""
    B, L, H = dt.shape
    steps = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return jnp.cumsum(steps.reshape(B, L // chunk, chunk, H),
                      axis=2).reshape(B, L, H)


def _chunked(xdt, cum, b, c, state, chunk: int):
    """The fallback. ``xdt`` [B, H, L, P] (``dt x`` in the arrays' dtype),
    ``cum`` [B, H, L], ``b``, ``c`` [B, L, N], ``state`` [B, H, P, N]."""
    B, H, L, P = xdt.shape
    n = L // chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def heads(x):       # [B, H, L, ...] -> [n, B, H, Q, ...]
        return jnp.moveaxis(x.reshape(B, H, n, chunk, *x.shape[3:]), 2, 0)

    def shared(x):      # [B, L, N] -> [n, B, Q, N]
        return jnp.moveaxis(x.reshape(B, n, chunk, -1), 1, 0)

    def step(state, at):
        xc, cc, bc, qc = at
        g = jnp.einsum("bin,bjn->bij", qc, bc,
                       preferred_element_type=jnp.float32)
        ahead = cc[..., :, None] - cc[..., None, :]             # [B, H, Q, Q]
        m = jnp.where(lower, jnp.exp(jnp.minimum(ahead, 0.0)), 0.0)
        intra = jnp.einsum("bhij,bhjp->bhip",
                           (g[:, None] * m).astype(xc.dtype), xc,
                           preferred_element_type=jnp.float32)
        inter = jnp.exp(cc)[..., None] * jnp.einsum(
            "bin,bhpn->bhip", qc.astype(jnp.float32), state)
        last = cc[..., -1:]                                     # [B, H, 1]
        new = jnp.exp(last)[..., None] * state + jnp.einsum(
            "bhjp,bjn->bhpn",
            xc.astype(jnp.float32) * jnp.exp(last - cc)[..., None],
            bc.astype(jnp.float32))
        return new, (intra + inter).astype(xc.dtype)

    state, y = jax.lax.scan(step, state,
                            (heads(xdt), heads(cum), shared(b), shared(c)))
    return jnp.moveaxis(y, 0, 2).reshape(B, H, L, P), state


def _kernel(xdt_ref, bt_ref, c_ref, col_ref, row_ref, s0_ref, y_ref, st_ref,
            state_scr, *, heads: int, chunk: int):
    """One chunk of ``heads`` heads. The state is kept transposed, [N, P] a
    head, so that both products that touch it read it as it lies."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_scr[...] = s0_ref[0]

    first_head = pl.program_id(1) * heads
    c, bt = c_ref[0], bt_ref[0]                                 # [Q, N], [N, Q]
    g = _dot(c, bt, _NN)                                        # C B^T [Q, Q]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cols = col_ref[0]                                           # [Q, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    c32, bt32 = c.astype(jnp.float32), bt.astype(jnp.float32)

    def one(h, carry):
        # the head's sums as a column (picked out of the lanes: a sum of
        # zeros and the one) and as a row
        col = jnp.sum(jnp.where(lane == first_head + h, cols, 0.0), axis=1,
                      keepdims=True)                            # [Q, 1]
        row = row_ref[0, pl.ds(h, 1), :]                        # [1, Q]
        m = jnp.where(i >= j, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
        xdt = xdt_ref[0, h]                                     # [Q, P]
        intra = _dot((g * m).astype(xdt.dtype), xdt, _NN)
        state = state_scr[h]                                    # [N, P]
        inter = jnp.exp(col) * _dot(c32, state, _NN)
        y_ref[0, h] = (intra + inter).astype(y_ref.dtype)
        last = row[:, chunk - 1:chunk]                          # [1, 1]
        state_scr[h] = jnp.exp(last) * state + _dot(
            bt32 * jnp.exp(last - row), xdt.astype(jnp.float32), _NN)
        return carry

    jax.lax.fori_loop(0, heads, one, None)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _last():
        st_ref[0] = state_scr[...]


def _ssd_call(xdt, cum, b, c, state, chunk: int, heads: int, interpret: bool):
    """``xdt`` [B, H, L, P], ``cum`` [B, L, H], ``b``, ``c`` [B, L, N],
    ``state`` [B, H, P, N]; L a multiple of ``chunk``, H of ``heads``."""
    B, H, L, P = xdt.shape
    N = b.shape[-1]
    by_head = pl.BlockSpec((1, heads, chunk, P), lambda i, g, j: (i, g, j, 0))
    whole = pl.BlockSpec((1, heads, N, P), lambda i, g, j: (i, g, 0, 0))
    y, final = pl.pallas_call(
        functools.partial(_kernel, heads=heads, chunk=chunk),
        grid=(B, H // heads, L // chunk),
        in_specs=[by_head,
                  pl.BlockSpec((1, N, chunk), lambda i, g, j: (i, 0, j)),
                  pl.BlockSpec((1, chunk, N), lambda i, g, j: (i, j, 0)),
                  pl.BlockSpec((1, chunk, H), lambda i, g, j: (i, j, 0)),
                  pl.BlockSpec((1, heads, chunk), lambda i, g, j: (i, g, j)),
                  whole],
        out_specs=[by_head, whole],
        out_shape=[jax.ShapeDtypeStruct((B, H, L, P), xdt.dtype),
                   jax.ShapeDtypeStruct((B, H, N, P), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,     # the XLA Ops line of a device trace carries it
    )(xdt, jnp.swapaxes(b, 1, 2), c, cum, jnp.swapaxes(cum, 1, 2),
      jnp.swapaxes(state, 2, 3))
    return y, jnp.swapaxes(final, 2, 3)


def ssd_fwd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
            c: jax.Array, initial_state: Optional[jax.Array] = None,
            chunk: int = CHUNK, use_kernel: bool = True,
            interpret: Optional[bool] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """``x`` [batch, seqlen, heads, P]; ``dt`` [batch, seqlen, heads], each
    position's step (after its softplus; 0 where the position is padding);
    ``a`` [heads], negative; ``b``, ``c`` [batch, seqlen, N];
    ``initial_state`` [batch, heads, P, N] float32 or None for zeros. Returns
    ``y`` [batch, seqlen, heads, P] in ``x``'s dtype (without the ``D x``
    skip, which is the caller's) and the state after the last position
    [batch, heads, P, N] float32. Causal by construction."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    padded = -(-L // chunk) * chunk
    dt = dt.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    pad = ((0, 0), (0, padded - L), (0, 0))
    # a padded position steps by 0: its ``dt x`` is 0 and the sums stand still
    xdt = jnp.pad(xdt, (*pad, (0, 0))).transpose(0, 2, 1, 3)    # [B, H, L, P]
    cum = _chunk_sums(jnp.pad(dt, pad), a, chunk)               # [B, L, H]
    b, c = jnp.pad(b, pad), jnp.pad(c, pad)
    state = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))
    if use_kernel:
        if interpret is None:
            interpret = _backend_is_cpu()
        heads = HEADS_PER_STEP if H % HEADS_PER_STEP == 0 else H
        y, state = _ssd_call(xdt, cum, b, c, state, chunk, heads, interpret)
    else:
        y, state = _chunked(xdt, jnp.swapaxes(cum, 1, 2), b, c, state, chunk)
    return y[:, :, :L].transpose(0, 2, 1, 3), state


def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token for each of ``S`` sequences: ``x`` [S, heads, P], ``dt`` [S,
    heads], ``a`` [heads], ``b``, ``c`` [S, N], ``state`` [S, heads, P, N]
    float32. Returns ``y`` [S, heads, P] float32 (without ``D x``) and the new
    state: the recurrence as it is written, every term float32 (sums, not
    products on the MXU, which would round the state to bfloat16). On the TPU
    the update and the sum over the new state are two fusions, three passes
    over the state where two would do; taken from the state before the step,
    ``exp(dt A) (S C) + dt x (B . C)``, the sum was a third pass all the same
    (PERF.md section 6, PR 52)."""
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32))                 # [S, H]
    push = (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * b.astype(jnp.float32)[:, None, None, :]
    state = decay[..., None, None] * state + push
    y = jnp.sum(state * c.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y, state
