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

Four entry points. ``ssd_fwd`` with ``use_kernel=True`` is one Mosaic call:
the grid runs over (batch, groups of heads, chunks), the state of a group of
heads lives in VMEM scratch across the chunk axis, starts from an optional
initial state and leaves as the final one; ``C B^T`` is made once a grid step
for all its heads. ``use_kernel=False`` is the same arithmetic in
``jax.numpy`` under a ``lax.scan`` over chunks: what the CPU tests take and
what the interpreted kernel is compared with. ``ssd_step`` is the recurrence
itself for one token over a batch of slots, plain ``jax.numpy``: what the
CPU tests and a decode step without the kernels take, three passes over the
state on the TPU. ``ssd_step_stacked`` is the same step as one Mosaic call
(``ssd_step``) on one layer of a state stacked over layers, in place: a
slot's state comes into VMEM once, is stepped, summed against ``C`` and
written back to where it came from, two passes, and every other layer of
the stack keeps its bits. Both are bound by the state's bytes. Forward only.
It sits beside
``ops/linear_attention.py`` (the same chunk, the same float32 state across the
chunk axis) and does not replace it: there the decay is a constant a head and
a head's value is as wide as its state.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _NN, _backend_is_cpu, _dot

CHUNK = 256            # tokens a chunk: M is [CHUNK, CHUNK]
HEADS_PER_STEP = 8     # heads a grid step walks: they share one C B^T
KERNEL_NAME = "ssd_fwd"
STEP_KERNEL_NAME = "ssd_step"
LANES = 128            # of a vector register: the step packs heads into rows of them


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
    over the state (the compiler fuses no reduction over the array an
    in-place update writes: PERF.md section 6, PR 52); a decode step with the
    kernels takes ``ssd_step_stacked``, which makes two. This one is what that
    is compared with, and what a state that is not stacked takes."""
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32))                 # [S, H]
    push = (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * b.astype(jnp.float32)[:, None, None, :]
    state = decay[..., None, None] * state + push
    y = jnp.sum(state * c.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y, state


def _step_kernel(layer_ref, xdt_ref, decay_ref, b_ref, c_ref, s_ref, out_ref,
                 y_ref, *, heads: int, pack: int):
    """One slot of the layer the index maps picked, ``heads`` heads a loop
    step. ``xdt_ref`` and ``y_ref`` [S, H / pack, pack x P] hold ``pack``
    heads a row, ``P`` on the lanes; against the state [P, N] a head ``P``
    lies on the sublanes. A row becomes columns, and columns a row again, by
    a transpose of the whole tile: ``dt x`` laid ``N`` times over the
    sublanes and transposed is the row's value on every lane of its own
    sublane, and ``S' * C`` transposed sums over sublanes (adds of whole
    registers) into a row of ``y``. A head's decay is a scalar, from SMEM."""
    del layer_ref
    P, N = s_ref.shape[3:]
    slot = pl.program_id(0)
    b = b_ref[pl.ds(slot, 1), :]                                # [1, N]
    c = c_ref[pl.ds(slot, 1), :]
    rows = heads // pack

    def walk(g, carry):
        for i in range(rows):
            r = g * rows + i
            xdt = xdt_ref[slot, pl.ds(r, 1), :]                 # [1, pack P]
            push = jnp.broadcast_to(xdt, (N, pack * P)).T * b   # [pack P, N]
            summed = []
            for k in range(pack):
                h = r * pack + k
                new = decay_ref[slot, h] * s_ref[0, 0, h] \
                    + push[k * P:(k + 1) * P]
                out_ref[0, 0, h] = new
                summed.append(new * c)
            y_ref[slot, pl.ds(r, 1), :] = jnp.sum(
                jnp.concatenate(summed).T, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, s_ref.shape[2] // heads, walk, None)


def ssd_step_stacked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                     c: jax.Array, states: jax.Array, layer
                     ) -> Tuple[jax.Array, jax.Array]:
    """``ssd_step`` on layer ``layer`` (an int32 scalar, traced or not) of
    ``states`` [n, S, heads, P, N] float32, as one Mosaic call on the stack
    itself: ``x``, ``dt``, ``a``, ``b``, ``c`` as ``ssd_step`` takes them.
    Returns ``y`` [S, heads, P] float32 and the stack with that layer stepped.
    The stack is the call's own output (``input_output_aliases``) and the
    layer's index a scalar-prefetch operand that the state's index map reads,
    so only that layer's bytes move, each once in and once out, and a donated
    stack is updated in place; a slice of the stack handed to a custom call
    would be copied out and in. A grid step is one slot: its heads' state
    ``[heads, P, N]`` (2 MB at Granite's sizes, double-buffered both ways)
    while everything else (``dt x``, the decays, ``b``, ``c``, ``y``: under
    2.2 MB for 64 slots) stays in VMEM for the whole call, fetched and written
    once, because a small copy a grid step is waited for and a large one is
    not. Every term is float32 and no product goes to the MXU. Interpreted on
    a CPU backend."""
    n, S, H, P, N = states.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    pack = math.gcd(H, max(1, LANES // P))      # heads a row of lanes
    heads = HEADS_PER_STEP if H % HEADS_PER_STEP == 0 \
        and HEADS_PER_STEP % pack == 0 else H
    xdt = (dt[..., None] * x.astype(f32)).reshape(S, H // pack, pack * P)

    def resident(*shape):
        return pl.BlockSpec(shape, lambda s, layer: (0,) * len(shape))

    a_slot = pl.BlockSpec((1, 1, H, P, N),
                          lambda s, layer: (layer[0], s, 0, 0, 0))
    # bytes: a slot's state in and out, and dt x, y, b and c; two buffers
    # each, and room for the loop's tiles
    vmem = 2 * 4 * (2 * H * P * N + 2 * xdt.size + 2 * S * N) + 4 * 2 ** 20
    # an index past the stack is held to it, as a dynamic slice holds it
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, n - 1)
    states, y = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[resident(*xdt.shape),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      resident(S, N), resident(S, N), a_slot],
            out_specs=[a_slot, resident(*xdt.shape)]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                   jax.ShapeDtypeStruct(xdt.shape, f32)],
        input_output_aliases={5: 0},    # the stack, counted from ``layer``
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),     # ``y`` stays across it
            vmem_limit_bytes=vmem),
        interpret=_backend_is_cpu(),
        name=STEP_KERNEL_NAME,    # the XLA Ops line of a device trace carries it
    )(layer.reshape(1), xdt, jnp.exp(dt * a.astype(f32)), b.astype(f32),
      c.astype(f32), states)
    return y.reshape(S, H, P), states
