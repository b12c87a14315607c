"""A routed mixture for a handful of rows: every expert's weights streamed
once, the rows resident.

For ``u`` [T, d], a weight a (row, expert) ``c`` [T, count] and one layer of
the stacked experts (``wi``, ``wg`` [n, count, d, width], ``wo`` [n, count,
width, d])::

    sum_e c[t, e] * ((act(u[t] wi_e) * (u[t] wg_e)) wo_e)     # [T, d], float32

over the experts e with ``c[t, e] != 0``. A decode step brings the mixture a
row a slot, 4.5 rows an expert at 48 slots of 6 picks over 64 experts, and is
bound by the experts' bytes: three grouped products a layer read them at 59%
of the chip's bandwidth (each starts a pipeline of its own, ``hidden`` goes to
HBM and back between them, and the rows are listed, gathered and placed round
them: PERF.md section 6, PR 56). Here *every* row meets *every* expert: a
weight tile that is in the MXU is multiplied by T <= 128 rows for the price of
one, so nothing is listed, and ``c`` selects which products count.

``experts_streamed`` is one Mosaic call. The grid runs over (expert, tile of
``width``); a grid step holds ``wi_e`` and ``wg_e``'s [d, tile] columns and
``wo_e``'s [tile, d] rows, read off the stacked leaves where they lie by the
index maps (the layer is a scalar-prefetch operand: no layer's experts are cut
out or copied), double-buffered by the pipeline so that the next step's
weights arrive under this step's products. ``u`` and ``c`` are whole blocks,
fetched once; the float32 sum [T, d] is one output block that every grid step
revisits and that goes to HBM once, when the grid ends. The operands go to the
MXU as they are (bfloat16) and accumulate in float32; ``act(a) * b`` is made in
float32 and rounded to ``u``'s dtype once, before ``wo``; a step's ``y`` is
weighed and summed in float32. A product that does not count is selected out,
not multiplied by zero. Forward only: ``parallel.expert._held_sum`` takes it
as one way of its forward and keeps its own backward. Interpreted on a CPU
backend.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _LANES, _NN, _backend_is_cpu, _dot

KERNEL_NAME = "experts_stream"
# Columns of an expert's width a grid step takes (a width that is no multiple
# of it goes whole). At SmallThinker's 2560 x 768 a step's three blocks are
# 3.9 MB, 7.9 MB double-buffered; tiles of 384 and 768 columns stream at the
# same 755 GB/s (my chip run, PR 56).
TILE = 256


def _kernel(layer_ref, u_ref, c_ref, wi_ref, wg_ref, wo_ref, out_ref, *,
            activation: str):
    """One tile of one expert's width: the rows' two first products, the gate,
    the product back to ``d``, weighed by the expert's column of ``c``."""
    del layer_ref
    e = pl.program_id(0)

    @pl.when((e == 0) & (pl.program_id(1) == 0))
    def _first():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]
    a = _dot(u, wi_ref[0, 0], _NN)                              # [T, tile]
    b = _dot(u, wg_ref[0, 0], _NN)
    act = jax.nn.silu if activation == "silu" else jax.nn.relu
    hidden = (act(a) * b).astype(u.dtype)
    y = _dot(hidden, wo_ref[0, 0], _NN)                         # [T, d]
    # the expert's weights as a column, picked out of the lanes (a sum of
    # zeros and the one)
    c = c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    col = jnp.sum(jnp.where(lane == e, c, 0.0), axis=1, keepdims=True)
    out_ref[...] += jnp.where(col != 0.0, col * y, 0.0)


def experts_streamed(u: jax.Array, c: jax.Array, experts: Dict[str, jax.Array],
                     layer, activation: str = "silu") -> jax.Array:
    """``sum_e c[t, e] Expert_e(u[t])`` over layer ``layer``'s experts (an
    int32 scalar, traced or not): ``u`` [T, d]; ``c`` [T, count] float32, a
    row's weight on each expert and 0 where it did not choose it; ``experts``
    the stacked leaves ``wi``, ``wg`` [n, count, d, width] and ``wo`` [n,
    count, width, d] in ``u``'s dtype; an expert is ``(act(x wi) * (x wg))
    wo``, ``act`` the ``activation`` (``silu`` | ``relu``). Returns float32
    [T, d]. One Mosaic call that reads the layer's weights once, in expert
    order (the module's docstring); meant for T of one MXU row tile or fewer:
    its work is T x count products whatever ``c`` holds."""
    T, d = u.shape
    n, count, _, width = experts["wi"].shape
    tile = TILE if width % TILE == 0 else width
    # whole sublanes of the dtype's packing: 8 rows of 32 bits, 16 of 16
    rows = 8 * max(1, 4 // jnp.dtype(u.dtype).itemsize)
    padded = -(-T // rows) * rows
    u = jnp.pad(u, ((0, padded - T), (0, 0)))
    c = jnp.pad(c.astype(jnp.float32), ((0, padded - T), (0, 0)))

    def resident(*shape):
        return pl.BlockSpec(shape, lambda e, j, layer: (0,) * len(shape))

    columns = pl.BlockSpec((1, 1, d, tile),
                           lambda e, j, layer: (layer[0], e, 0, j))
    item = jnp.dtype(u.dtype).itemsize
    # bytes: a step's three blocks of weights and the resident blocks, two
    # buffers each, and room for the step's float32 products
    vmem = (2 * (3 * d * tile * item + padded * d * (item + 4)
                 + 4 * padded * max(count, _LANES))
            + 4 * 4 * padded * (d + 2 * tile) + 4 * 2 ** 20)
    # an index past the stack is held to it, as a dynamic slice holds it
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, n - 1)
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(count, width // tile),
            in_specs=[resident(padded, d), resident(padded, count),
                      columns, columns,
                      pl.BlockSpec((1, 1, tile, d),
                                   lambda e, j, layer: (layer[0], e, j, 0))],
            out_specs=resident(padded, d)),
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the sum stays across both axes
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_backend_is_cpu(),
        name=KERNEL_NAME,     # the XLA Ops line of a device trace carries it
    )(layer.reshape(1), u, c, experts["wi"], experts["wg"], experts["wo"])
    return out[:T]
