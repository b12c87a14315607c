"""Lightning linear attention with a per-head decay, computed in chunks.

The recurrence, a head at a time, with ``lam = exp(-rate)`` in (0, 1)::

    S_t = lam * S_{t-1} + k_t^T v_t          # [D, D], float32
    o_t = scale * q_t S_t                    # no normaliser

Over a chunk of ``C`` tokens that is three matmuls and a state carried to the
next chunk (``i``, ``j`` count inside the chunk, ``S`` is the state before
it)::

    o   = scale * [ ((Q K^T) * M) V + (Q * lam^(i+1)) S ],   M[i, j] = lam^(i-j) for j <= i, else 0
    S' = lam^C S + (K * lam^(C-1-j))^T V

``M`` is built from the differences ``i - j`` directly: ``lam^i`` and
``lam^-j`` apart would overflow for a head that forgets quickly. The scores
and the products of ``M`` go to the MXU in the operands' dtype, as the flash
kernel's ``p`` does; the state, the decays and every product that reads or
writes the state are float32.

Two paths. ``linear_attn_fwd`` is one Mosaic call: the grid runs over
(batch x heads, groups of chunks), the state lives in VMEM scratch across a
head's steps, the decays of one chunk come as small float32 arrays a head.
``use_kernel=False`` is the same arithmetic in ``jax.numpy`` as a
``lax.scan`` over chunks: what the CPU tests take and what the interpreted
kernel is compared with. Forward only.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _NN, _backend_is_cpu, _dot

CHUNK = 256            # tokens a chunk: M is [CHUNK, CHUNK]
CHUNKS_PER_STEP = 8    # chunks a grid step walks: 2,048 rows of q, k, v
KERNEL_NAME = "linear_attn_fwd"


def decay_rates(n_heads: int, layer: int, depth: int) -> np.ndarray:
    """The decay rate of each head of layer ``layer`` (0-based) of a stack
    ``depth`` deep, ``lam_h = exp(-rate_h)``: ``rate_h = 2^(-8 (h + 1) /
    n_heads) * (1 - layer / (depth - 1) + 1e-5)``, the slopes of Lightning
    Attention (MiniMax-01's ``_build_slope_tensor``), fading with depth."""
    slopes = 2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)
    return (slopes * (1.0 - layer / max(depth - 1, 1) + 1e-5)
            ).astype(np.float32)


def _decays(rates: jax.Array, chunk: int):
    """What a chunk needs of a head's decay, float32: ``M`` [H, C, C],
    ``lam^(i+1)`` [H, C, 1], ``lam^(C-1-j)`` [H, 1, C], ``lam^C`` [H]."""
    rates = rates.astype(jnp.float32)[:, None, None]
    i = jnp.arange(chunk, dtype=jnp.float32)
    ahead = i[:, None] - i[None, :]
    mask = jnp.where(ahead >= 0, jnp.exp(-rates * jnp.maximum(ahead, 0.0)),
                     0.0)
    to_row = jnp.exp(-rates * (i + 1.0)[None, :, None])
    to_end = jnp.exp(-rates * (chunk - 1.0 - i)[None, None, :])
    return mask, to_row, to_end, jnp.exp(-rates[:, 0, 0] * chunk)


def _chunked(q, k, v, rates, scale: float, chunk: int):
    """The fallback: [B, H, n, C, D] chunks under a ``lax.scan``."""
    B, H, L, D = q.shape
    mask, to_row, to_end, whole = _decays(rates, chunk)
    to_end = jnp.swapaxes(to_end, 1, 2)                       # [H, C, 1]

    def chunks(x):
        return jnp.moveaxis(x.reshape(B, H, L // chunk, chunk, D), 2, 0)

    def step(state, qkv):
        qc, kc, vc = qkv                                      # [B, H, C, D]
        s = jnp.einsum("bhid,bhjd->bhij", qc, kc,
                       preferred_element_type=jnp.float32) * mask
        intra = jnp.einsum("bhij,bhjd->bhid", s.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
        inter = jnp.einsum("bhid,bhde->bhie",
                           qc.astype(jnp.float32) * to_row, state)
        new = state * whole[:, None, None] + jnp.einsum(
            "bhjd,bhje->bhde", kc.astype(jnp.float32) * to_end,
            vc.astype(jnp.float32))
        return new, ((intra + inter) * scale).astype(qc.dtype)

    state = jnp.zeros((B, H, D, D), jnp.float32)
    _, out = jax.lax.scan(step, state, (chunks(q), chunks(k), chunks(v)))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, L, D)


def _kernel(q_ref, kt_ref, v_ref, mask_ref, row_ref, end_ref, whole_ref,
            o_ref, state_scr, *, scale: float, chunk: int, n_chunks: int):
    @pl.when(pl.program_id(1) == 0)
    def _first():
        state_scr[...] = jnp.zeros_like(state_scr)

    mask, to_row, to_end = mask_ref[0], row_ref[0], end_ref[0]
    whole = whole_ref[0, :1, :]                               # [1, D]

    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        q, kt, v = q_ref[0, rows, :], kt_ref[0, :, rows], v_ref[0, rows, :]
        s = _dot(q, kt, _NN) * mask                           # [C, C]
        intra = _dot(s.astype(v.dtype), v, _NN)               # [C, D]
        state = state_scr[...]
        inter = _dot(q.astype(jnp.float32) * to_row, state, _NN)
        o_ref[0, rows, :] = ((intra + inter) * scale).astype(o_ref.dtype)
        state_scr[...] = state * whole + _dot(
            kt.astype(jnp.float32) * to_end, v.astype(jnp.float32), _NN)
        return carry

    jax.lax.fori_loop(0, n_chunks, one, None)


def _linear_fwd(q, k, v, rates, scale, chunk, per_step, interpret):
    """q, k, v: [B * H, L, D] with L a multiple of ``chunk * per_step``;
    rates [H]."""
    BH, L, D = q.shape
    H = rates.shape[0]
    step = chunk * per_step
    mask, to_row, to_end, whole = _decays(rates, chunk)
    # lane-dense float32 operands: a column over D lanes, a row over D
    # sublanes, a scalar over one (8, D) tile
    to_row = jnp.broadcast_to(to_row, (H, chunk, D))
    to_end = jnp.broadcast_to(to_end, (H, D, chunk))
    whole = jnp.broadcast_to(whole[:, None, None], (H, 8, D))

    def head(b, j):
        return (b % H, 0, 0)

    rows = pl.BlockSpec((1, step, D), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, chunk=chunk,
                          n_chunks=per_step),
        grid=(BH, L // step),
        in_specs=[rows,
                  pl.BlockSpec((1, D, step), lambda b, j: (b, 0, j)),
                  rows,
                  pl.BlockSpec((1, chunk, chunk), head),
                  pl.BlockSpec((1, chunk, D), head),
                  pl.BlockSpec((1, D, chunk), head),
                  pl.BlockSpec((1, 8, D), head)],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,     # the XLA Ops line of a device trace carries it
    )(q, jnp.swapaxes(k, 1, 2), v, mask, to_row, to_end, whole)


def linear_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     rates: jax.Array, chunk: int = CHUNK,
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q, k, v: [batch, seqlen, heads, head_dim]; ``rates`` [heads], each
    head's decay rate a token (``decay_rates``). Returns [batch, seqlen,
    heads, head_dim]: ``o_t = scale * q_t S_t`` with ``S_t = exp(-rate) *
    S_{t-1} + k_t^T v_t`` (``scale`` = 1 / sqrt(head_dim)). Causal by
    construction, so positions appended on the right change nothing before
    them."""
    B, L, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    per_step = max(1, min(CHUNKS_PER_STEP, -(-L // chunk)))
    if not use_kernel:
        per_step = 1
    padded = -(-L // (chunk * per_step)) * chunk * per_step

    def heads_first(x):
        x = jnp.pad(x, ((0, 0), (0, padded - L), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3)                        # [B, H, L, D]

    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    rates = jnp.asarray(rates, jnp.float32)
    if use_kernel:
        if interpret is None:
            interpret = _backend_is_cpu()
        out = _linear_fwd(*(x.reshape(B * H, padded, D) for x in (q, k, v)),
                          rates, scale, chunk, per_step, interpret)
        out = out.reshape(B, H, padded, D)
    else:
        out = _chunked(q, k, v, rates, scale, chunk)
    return out[:, :, :L].transpose(0, 2, 1, 3)
