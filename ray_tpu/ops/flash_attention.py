"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The MXU-resident attention kernel used by the model stack. Blocks of Q stay
in VMEM while K/V blocks stream through; softmax is computed online
(running max + normalizer in VMEM scratch) so the O(L²) score matrix never
hits HBM. Causal masking skips fully-masked K blocks at the grid level.
The backward pass recomputes P from the saved log-sum-exp (flash-style
rematerialization) in two kernels: one accumulating dQ over K blocks, one
accumulating dK/dV over Q blocks.

Runs in interpreter mode only where the backend is ``cpu`` (the CPU test
mesh exercises the same code path); on any other backend the Mosaic kernel
compiles or the call fails. A Mosaic kernel cannot be partitioned by XLA:
under a mesh, call it inside a ``shard_map`` (``models.transformer``
does). Role in the stack: the per-shard kernel under
``ray_tpu.parallel.sequence.ring_attention`` and the dense-attention op for
``ray_tpu.models`` (the reference delegates attention to torch; here it is a
first-class TPU kernel).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # stats buffers keep a full lane dim (TPU tiling)


def _backend_is_cpu() -> bool:
    return jax.default_backend() == "cpu"


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int, seq_k: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: K blocks strictly above the diagonal contribute nothing.
    run = (ik * block_k < (iq + 1) * block_q) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # [bq, d]
        k = k_ref[0].astype(jnp.float32)                      # [bk, d]
        v = v_ref[0].astype(jnp.float32)                      # [bk, d]
        # Pad rows of a ragged last K block hold garbage (possibly NaN/Inf);
        # zero them so 0-weighted dot contributions stay 0 (0*NaN = NaN).
        kv_valid = (ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_k  # ragged last K block must not leak pad columns
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]                                 # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)          # [bq, bk]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lse is stored compact [BH, Lq, 1]: same column orientation as the
        # scratch stats, single lane (Mosaic allows full-dim lane blocks).
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)                # [bq, 1]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    nq = pl.cdiv(Lq, block_q)
    nk = pl.cdiv(Lk, block_k)
    grid = (BH, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, seq_k=Lk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",  # the XLA Ops line of a device trace carries it
    )(q, k, v)
    return out, lse[:, :, 0]


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, block_q, block_k, num_k_blocks,
               seq_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (ik * block_k < (iq + 1) * block_q) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                      # [bq, 1]
        delta = delta_ref[0]                                  # [bq, 1]
        kv_valid = (ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_k
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(mask, p * (dp - delta) * scale, 0.0)
        acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k, num_q_blocks, seq_k, seq_q):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (ik * block_k < (iq + 1) * block_q) if causal else (iq >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                      # [bq, 1]
        delta = delta_ref[0]                                  # [bq, 1]
        # Pad *query* rows of a ragged last Q block would contaminate the
        # dk/dv sums (they reduce over q rows); zero the sources and mask p.
        q_valid = (iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < seq_q
        q = jnp.where(q_valid, q, 0.0)
        do = jnp.where(q_valid, do, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.logical_and(cols < seq_k, rows < seq_q)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)             # [bq, bk]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(mask, p * (dp - delta) * scale, 0.0)    # [bq, bk]
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(scale, causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    do = g
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    nq = pl.cdiv(Lq, block_q)
    nk = pl.cdiv(Lk, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # [BH, Lq]
    lse_c = lse[:, :, None]                                    # [BH, Lq, 1]
    delta_c = delta[:, :, None]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          seq_k=Lk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse_c, delta_c)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          seq_k=Lk, seq_q=Lq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse_c, delta_c)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhld(q, k, v, scale, causal, block_q, block_k,
                          interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd_rule(scale, causal, block_q, block_k, interpret, residuals, g):
    return _flash_bwd(scale, causal, block_q, block_k, interpret, residuals, g)


_flash_attention_bhld.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention. q/k/v: [batch, seqlen, heads, head_dim].

    Returns [batch, seqlen, heads, head_dim]. Differentiable (custom VJP).
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = _backend_is_cpu()
    # [B, L, H, D] -> [B*H, L, D]
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    out = _flash_attention_bhld(qb, kb, vb, scale, causal, block_q, block_k,
                                interpret)
    return out.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
