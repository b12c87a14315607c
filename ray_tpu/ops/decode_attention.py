"""A decode step's attention over the rows each slot holds.

For one token a slot, ``spread`` [S, H, C] (a slot's query heads, each laid
over all ``C = kv_heads x head_dim`` lanes of a cache row with zeros outside
its own group's), one layer of the stacked caches ``k``, ``v`` [n, S, T, C]
and ``newest`` [S], the last row each slot has reached::

    softmax(spread[s] k[layer, s, :newest[s] + 1]^T * scale)
        v[layer, s, :newest[s] + 1]                           # [S, H, C]

A masked product over all ``T`` rows reads every allocated row of every slot,
and a decode step is bound by bytes: at 48 slots of 13,312 and 4,096 rows,
53% of them live, the two caches were 5.03 of the 12.2 GB a step read and
41% of its time (PERF.md section 6, PR 59). Here a slot's query meets the
tiles of ``tile_rows(T)`` rows that slot has reached and no other.

``decode_attention`` is one Mosaic call a layer. The stacks stay where they
lie (``pl.ANY``; the layer's index and ``newest`` are scalars in SMEM, so no
layer is cut out or copied) and the call walks the slots' live tiles as one
run of copies, two buffers for K and two for V: while a tile is multiplied
the next one is on its way, be it the slot's next or the next slot's first,
so a slot's end costs no wait and a dead tile nothing at all. ``spread`` and
the output are whole blocks in VMEM, fetched and written once. A tile's
scores ``spread[s] K^T`` [H, tile] come off the MXU in float32 (the operands
go in as they are), the softmax runs online in float32 (the running maximum,
the sum and the accumulator [H, C] are the walk's carry), ``p`` is rounded to
the cache's dtype before ``p V`` and the accumulator is divided once, at the
slot's end. Only a slot's last live tile is masked: the scores past
``newest`` to ``NEG_INF`` and V's rows there to zero, so that nothing a dead
row holds (not a NaN either) reaches the output; the tiles before it are
live whole. A ring of a window's rows needs nothing of its own: its live rows
are the first ``length`` until it has filled and all ``T`` after, and the
rows' order does not matter to the softmax. Forward only. Interpreted on a
CPU backend. Without the kernel (``use_kernel=False``, and a cache whose rows
are no multiple of 8, which Mosaic cannot cut into tiles) the same result is
the masked product over all ``T`` rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (NEG_INF, _NN, _NT, _backend_is_cpu,
                                         _dot)

KERNEL_NAME = "decode_attn"
# The most rows a tile takes. At 512 lanes of bfloat16 a tile of K and one of
# V are 0.5 MB each. On a v5e the walk read SmallThinker's 13,312 rows, half of
# them live, in 1,017 / 920 / 947 us a layer call at 256 / 512 / 1,024 and
# Granite's 1,408, a quarter live, in 138 / 114 / 138 / 259 us at 128 / 352 /
# 704 / 1,408 (my chip run, PR 59): under 512 a tile's fixed cost shows, over
# it the half tile a slot reads past its length.
_ROWS = 512


def tile_rows(T: int) -> Optional[int]:
    """The rows of a tile for a cache of ``T`` rows: the largest divisor of
    ``T`` up to ``_ROWS`` that is a multiple of 8 (what Mosaic asks of a
    slice of rows), or None where ``T`` is no multiple of 8. 13,312 and
    4,096 rows take 512, 1,408 take 352."""
    fits = [t for t in range(8, min(T, _ROWS) + 1, 8) if T % t == 0]
    return fits[-1] if fits else None


def read_rows(newest, T: int):
    """The rows of a cache of ``T`` rows that the kernel reads for a slot
    whose last live row is ``newest`` (a number or an array of them): its
    tiles up to that row's, or all ``T`` where the cache has no tile."""
    tile = tile_rows(T) or T
    return (newest // tile + 1) * tile


def _take(q, k, v, carry, live, scale: float):
    """One tile into a slot's online softmax: ``carry`` the running maximum
    [H, 1], sum [H, 1] and accumulator [H, C], float32. ``live`` is the
    tile's last live row where it is the slot's last tile, None where every
    row is live."""
    m, l, acc = carry
    s = _dot(q, k, _NT) * scale                                 # [H, tile]
    if live is not None:
        s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) <= live,
                      s, NEG_INF)
        v = jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0) <= live, v, jnp.zeros_like(v))
    # row 0 of a slot's first tile is live, so m is finite from there on and
    # exp(NEG_INF - m) an exact 0
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
            alpha * acc + _dot(p.astype(v.dtype), v, _NN))


def _empty(H: int, C: int):
    return (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, C), jnp.float32))


def _kernel(layer_ref, newest_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sem, *, tile: int, scale: float):
    """Every slot's live tiles, one after another. ``step`` counts the tiles
    taken so far over all slots: a tile lies in buffer ``step % 2``."""
    S, H, C = q_ref.shape
    layer = layer_ref[0]

    def copies(s, j, b):
        rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
        return (pltpu.make_async_copy(k_hbm.at[layer, s, rows], k_buf.at[b],
                                      sem.at[0, b]),
                pltpu.make_async_copy(v_hbm.at[layer, s, rows], v_buf.at[b],
                                      sem.at[1, b]))

    def start(s, j, b):
        for copy in copies(s, j, b):
            copy.start()

    def arrived(s, j, b):
        for copy in copies(s, j, b):
            copy.wait()
        return k_buf[b], v_buf[b]

    start(0, 0, 0)

    def slot(s, step):
        newest = newest_ref[s]
        last = newest // tile
        q = q_ref[s]

        def whole(j, carry):
            step, *softmax = carry
            b = step % 2
            start(s, j + 1, 1 - b)
            k, v = arrived(s, j, b)
            return (step + 1, *_take(q, k, v, softmax, None, scale))

        step, *softmax = jax.lax.fori_loop(0, last, whole,
                                           (step, *_empty(H, C)))
        b = step % 2

        @pl.when(s + 1 < S)
        def _next_slot():
            start(s + 1, 0, 1 - b)

        k, v = arrived(s, last, b)
        _, l, acc = _take(q, k, v, softmax, newest - last * tile, scale)
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return step + 1

    jax.lax.fori_loop(0, S, slot, 0)


def _masked(spread, k, v, layer, newest, scale: float):
    """The same over every row of the layer's slice, the rows past
    ``newest`` masked out of the softmax."""
    T = k.shape[2]
    s = jnp.einsum("shc,stc->sht", spread,
                   jax.lax.dynamic_index_in_dim(k, layer, 0, False),
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(T)[None] <= newest[:, None]               # [S, T]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("sht,stc->shc", p.astype(spread.dtype),
                      jax.lax.dynamic_index_in_dim(v, layer, 0, False))


def decode_attention(spread: jax.Array, k: jax.Array, v: jax.Array, layer,
                     newest: jax.Array, scale: float,
                     use_kernel: bool = True) -> jax.Array:
    """``softmax(spread[s] K_s^T * scale) V_s`` over rows ``0 .. newest[s]``
    of slot ``s`` in layer ``layer`` (an int32 scalar, traced or not) of the
    stacks ``k``, ``v`` [n, S, T, C]: ``spread`` [S, H, C] in the stacks'
    dtype, ``newest`` [S] int32 in ``[0, T)``. Returns [S, H, C] in that
    dtype. With ``use_kernel`` one Mosaic call that reads ``read_rows(newest,
    T)`` rows of each slot's K and V, once (the module's docstring); without,
    or where ``T`` has no tile, a masked product over all ``T`` rows."""
    n, S, T, C = k.shape
    H = spread.shape[1]
    tile = tile_rows(T)
    if not use_kernel or tile is None:
        return _masked(spread, k, v, layer, newest, scale)
    item = jnp.dtype(k.dtype).itemsize
    rows = 8 * max(1, 4 // item)    # the heads in whole packed sublanes
    padded = -(-H // rows) * rows
    spread = jnp.pad(spread.astype(k.dtype), ((0, 0), (0, padded - H), (0, 0)))
    # an index past the stack is held to it, as a dynamic slice holds it
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, n - 1)
    newest = jnp.clip(newest.astype(jnp.int32), 0, T - 1)
    # bytes: the four tiles, ``spread`` and the output (two buffers each),
    # and room for a tile's float32 scores and the accumulator
    vmem = (4 * tile * C * item + 4 * S * padded * C * item
            + 4 * 4 * padded * (tile + C) + 4 * 2 ** 20)
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, scale=scale),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((S, padded, C), k.dtype),
        scratch_shapes=[pltpu.VMEM((2, tile, C), k.dtype),
                        pltpu.VMEM((2, tile, C), k.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=_backend_is_cpu(),
        name=KERNEL_NAME,     # the XLA Ops line of a device trace carries it
    )(layer.reshape(1), newest, spread, k, v)
    return out[:, :H]
