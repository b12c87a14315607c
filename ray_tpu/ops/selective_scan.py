"""Mamba-1's selective scan: a decay a channel and a state.

For channels ``c < C`` and states ``n < N``, with ``A`` [N, C] negative, a
step ``dt_t`` [C] and an input ``x_t`` [C] a channel, ``B_t`` and ``C_t`` [N]
shared by the channels::

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

``ops/ssd.py`` (Mamba-2) has one scalar decay a head and turns a chunk into
matmuls over it; here the decay of a chunk is a matrix a channel and a state
and does not factor out of the sum, so the scan is the recurrence itself. The
state lies ``[N, C]``, the channels on the lanes (N = 16 fills two float32
tiles' sublanes; ``[C, N]`` would leave seven of eight lanes empty).

``selective_scan`` walks a sequence in time order; a position with ``dt = 0``
passes the state through unchanged, which is how a right-padded prompt leaves
the state of its last real position. With ``use_kernel`` it is one Mosaic call
(``KERNEL_NAME``): the grid walks blocks of ``_TIME`` positions, and inside
each the channels in blocks of ``_LANES``; a channel block's state ``[N,
_LANES]`` lives in VMEM scratch for the whole walk and in registers through a
block's positions, eight at a time, so the sequence costs its inputs read once
and ``y`` written once (in XLA the same loop is two small fusions a position,
each paid its launch: 2-4 us a position and layer on a v5e). ``B_t`` and
``C_t`` are needed down the sublanes, a value a state on every lane: they are
handed to the call laid so, ``[.., N, 128]`` (what a ``[.., N, 1]`` array
occupies anyway), and a block of them is fetched once a time block, whatever
the number of channel blocks. Without the kernel (and for a length that has no
block of whole tiles) ``lax.scan`` with ``chunk`` positions unrolled a trip.

``selective_scan_step`` is one token for every slot of a decode step, plain
``jax.numpy``: at Phi-4-mini-flash's sizes nine layers' steps are 1.3 ms of a
22 ms decode step, and a kernel for them moved no reply in or out of a window
(PERF.md section 6, PR 61). Float32 throughout; forward only. The kernel is
interpreted on a CPU backend.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _backend_is_cpu

KERNEL_NAME = "mamba1_scan"
# positions a trip of the plain loop
CHUNK = 16
# the kernel's blocks: positions a grid step, channels a block of state, and
# the lanes ``B`` and ``C`` are laid over
_TIME, _LANES, _WIDE = 256, 512, 128


def _advance(state, dt, dtx, a, b, c):
    """One position: ``state`` [B, N, C], ``dt`` and ``dtx = dt * x`` [B, C],
    ``b`` and ``c`` [B, N] -> the new state and ``y`` [B, C]."""
    state = jnp.exp(dt[:, None, :] * a) * state \
        + dtx[:, None, :] * b[:, :, None]
    return state, jnp.sum(state * c[:, :, None], axis=1)


def _block(size: int, most: int, tile: int) -> Optional[int]:
    """The largest divisor of ``size`` up to ``most`` that is a multiple of
    ``tile``; ``size`` itself where it is smaller than a tile's multiple can
    be (a block may span a whole axis); else None."""
    fits = [n for n in range(tile, min(size, most) + 1, tile)
            if size % n == 0]
    return fits[-1] if fits else (size if size <= most else None)


def _over_lanes(column, width: int):
    """``column`` [N, _WIDE] (a value a row on every lane) over ``width``
    lanes."""
    if width <= column.shape[1]:
        return column[:, :width]
    return jnp.concatenate([column] * (width // column.shape[1]), axis=1)


def _eight(s, a, dt8, dtx8, b8, c8):
    """Eight positions from the state ``s`` [N, w]:
    ``dt8``, ``dtx8`` [8, w] and ``b8``, ``c8`` [8, N, _WIDE]. Returns the
    state and ``y`` [8, w]."""
    w = s.shape[1]
    ys = []
    for i in range(dt8.shape[0]):
        s = jnp.exp(dt8[i:i + 1] * a) * s \
            + dtx8[i:i + 1] * _over_lanes(b8[i], w)
        ys.append(jnp.sum(s * _over_lanes(c8[i], w), axis=0, keepdims=True))
    return s, jnp.concatenate(ys, axis=0)


def _scan_kernel(dt_ref, dtx_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref,
                 held, *, rows: int):
    """Grid ``(batch, time block, channel block)``, the channel blocks
    innermost: ``held`` [channel blocks, N, lanes] keeps every block's state
    from one time block to the next."""
    ti, ci = pl.program_id(1), pl.program_id(2)

    @pl.when(ti == 0)
    def _first():
        held[ci] = s0_ref[0]

    a = a_ref[...]

    def eight(j, s):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        s, y = _eight(s, a, dt_ref[0, at, :], dtx_ref[0, at, :],
                      b_ref[0, at], c_ref[0, at])
        y_ref[0, at, :] = y
        return s

    s = jax.lax.fori_loop(0, dt_ref.shape[1] // rows, eight, held[ci])
    held[ci] = s

    @pl.when(ti == pl.num_programs(1) - 1)
    def _last():
        s_ref[0] = s


def _wide(v):
    """``v`` [..., N] with each value on ``_WIDE`` lanes: [..., N, _WIDE]."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                            (*v.shape, _WIDE))


def _scan_call(dt, dtx, a, b, c, state):
    B, L, C = dt.shape
    N = a.shape[0]
    bt, bc = _block(L, _TIME, 8), _block(C, _LANES, _WIDE)
    rows = 8 if bt % 8 == 0 else bt
    f32 = jnp.float32

    def moving(*tail):      # a block a (time block, channel block)
        return pl.BlockSpec((1, bt, *tail), {
            1: lambda i, t, j: (i, t, j),
            2: lambda i, t, j: (i, t, 0, 0)}[len(tail)])

    per_block = pl.BlockSpec((1, N, bc), lambda i, t, j: (i, 0, j))
    vmem = 4 * (2 * 3 * bt * bc + 2 * 2 * bt * N * _WIDE + 5 * N * bc
                + N * C) + 8 * 2 ** 20
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows),
        grid=(B, L // bt, C // bc),
        in_specs=[moving(bc), moving(bc), moving(N, _WIDE), moving(N, _WIDE),
                  pl.BlockSpec((N, bc), lambda i, t, j: (0, j)), per_block],
        out_specs=[moving(bc), per_block],
        out_shape=[jax.ShapeDtypeStruct((B, L, C), f32),
                   jax.ShapeDtypeStruct((B, N, C), f32)],
        scratch_shapes=[pltpu.VMEM((C // bc, N, bc), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_backend_is_cpu(),
        name=KERNEL_NAME,     # the XLA Ops line of a device trace carries it
    )(dt, dtx, _wide(b), _wide(c), a, state)
    return y, state


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, state: Optional[jax.Array] = None,
                   chunk: int = CHUNK, use_kernel: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """``x``, ``dt`` [B, L, C]; ``a`` [N, C]; ``b``, ``c`` [B, L, N];
    ``state`` [B, N, C] (zeros if None). Returns ``y`` [B, L, C] and the state
    after the last position [B, N, C], both float32. With ``use_kernel`` one
    Mosaic call (the module's docstring) where the length and the channels
    have blocks (``_block``); else ``lax.scan``, ``chunk`` positions a
    trip."""
    B, L, C = x.shape
    N = a.shape[0]
    f32 = jnp.float32
    dt = dt.astype(f32)
    if state is None:
        state = jnp.zeros((B, N, C), f32)
    if (use_kernel and _block(L, _TIME, 8) is not None
            and _block(C, _LANES, _WIDE) is not None):
        return _scan_call(dt, dt * x.astype(f32), a.astype(f32), b, c,
                          state.astype(f32))

    def position(state, at):
        dt_t, dtx_t, b_t, c_t = at
        return _advance(state, dt_t, dtx_t, a.astype(f32), b_t, c_t)

    time_major = [t.swapaxes(0, 1) for t in
                  (dt, dt * x.astype(f32), b.astype(f32), c.astype(f32))]
    state, y = jax.lax.scan(position, state.astype(f32), time_major,
                            unroll=max(1, min(chunk, L)))
    return y.swapaxes(0, 1), state


def selective_scan_step(x: jax.Array, dt: jax.Array, a: jax.Array,
                        b: jax.Array, c: jax.Array, state: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """One token a slot: ``x``, ``dt`` [S, C]; ``a`` [N, C]; ``b``, ``c`` [S,
    N]; ``state`` [S, N, C] float32. Returns ``y`` [S, C] float32 and the new
    state."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    state, y = _advance(state.astype(f32), dt, dt * x.astype(f32),
                        a.astype(f32), b.astype(f32), c.astype(f32))
    return y, state
