"""Block-sparse attention with a learned selection (InfLLM-V2, as MiniCPM4
and MiniCPM-SALA use it): every query attends the tokens of the ``topk``
key blocks that its own scores against mean-pooled keys rank highest, the
first block and the local window always among them.

For query ``t`` of a K/V head (``G`` query heads share it)::

    Kc_j   = mean(k[stride * j : stride * j + kernel_size])           # pooled keys
    P[t,j] = sum over the G heads of softmax_j(q_{t,h} . Kc_j * scale) # over the j whose
                                                                      # tokens all lie at or before t
    score[t,b] = max of P[t,j] over the pooled j that overlap block b = tokens [block_size * b, +block_size)
    forced[t,b]: b < init_blocks, or block b holds one of the window_size tokens (t - window_size, t]
    selected   : the topk blocks by (forced first, then score) among the blocks that start at or before t;
                 neighbouring blocks share a pooled window, so equal scores are common: ties at the last place are all kept
    o_{t,h} = softmax over the tokens u <= t of the selected blocks (q_{t,h} . k_u * scale) v_u

A block's place in the ranking is a number, so near the ``topk``-th place a
rounding of q or k swaps two blocks: the result is the same function of the
*selection*, and the selection is as good as the scores' precision.

Two Mosaic calls. ``sparse_attn_scores`` makes ``P``: a query tile against
all pooled keys of its K/V head (at 32,768 tokens 2,047 of them, half a
megabyte), softmax, summed over the group's heads in the output block.
The ranking between them is XLA's (a strided max, ``lax.top_k``'s 64th value
as the threshold, the selected blocks packed as bits, 8 to a K/V tile).
``sparse_attn_fwd`` is the flash forward over rows that stack the group's
``G`` heads of one query tile, so that a K/V tile and the tile's selection
mask are read and built once for the 16 heads that share them: a query
tile's mask is [block_q, block_k] from one shift of its bits, and the K/V
tiles a query tile walks are those under the diagonal. Neighbouring queries
select different blocks, so no K/V tile is skipped for being unselected;
the unselected tokens are masked.

``use_kernel=False`` is the same arithmetic in ``jax.numpy`` on whole score
matrices: what the CPU tests take and what the interpreted kernels are
compared with. Forward only.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (_LANES, _NN, _NT, NEG_INF,
                                         _backend_is_cpu, _dot, _lanes, _walk)

SCORES_KERNEL, ATTEND_KERNEL = "sparse_attn_scores", "sparse_attn_fwd"
_ROWS = 1024           # rows of a score tile: a query tile x the group's heads
_TILE_BLOCKS = 8       # key blocks a K/V tile holds (their bits fit one word)
_MAJOR_TILES = 8       # K/V tiles a grid step keeps in VMEM
_SCORES_BLOCK_Q = 256
_VMEM_BYTES = 64 * 2 ** 20


@dataclass(frozen=True)
class SparseConfig:
    """The constants of the selection (MiniCPM4's published
    ``sparse_config``) and the length up to which attention stays dense."""
    kernel_size: int = 32      # tokens a pooled key averages
    kernel_stride: int = 16    # tokens between two pooled keys
    block_size: int = 64       # tokens a selectable block holds
    topk: int = 64             # blocks a query keeps, forced ones included
    init_blocks: int = 1       # leading blocks always kept
    window_size: int = 2048    # trailing tokens whose blocks are always kept
    dense_len: int = 8192      # sequences up to this long attend everything

    def __post_init__(self):
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride):
            raise ValueError(f"{self}: kernel_size and block_size are "
                             "multiples of kernel_stride")


# -- the selection -----------------------------------------------------------


def pooled_keys(k: jax.Array, cfg: SparseConfig) -> jax.Array:
    """k [..., L, D] -> the means of its windows [..., n, D], n = (L -
    kernel_size) / kernel_stride + 1, summed in float32."""
    *lead, L, D = k.shape
    stride, per = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    sums = k.astype(jnp.float32).reshape(*lead, L // stride, stride, D).sum(-2)
    n = L // stride - per + 1
    pooled = sum(sums[..., i:i + n, :] for i in range(per)) / cfg.kernel_size
    return pooled.astype(k.dtype)


def _pooled_probs(q, kc, scale: float, cfg: SparseConfig):
    """The fallback of ``sparse_attn_scores``: q [B, KV, G, L, D], kc [B,
    KV, n, D] -> P [B, KV, L, n] float32."""
    L, n = q.shape[3], kc.shape[2]
    s = jnp.einsum("bkgld,bknd->bkgln", q, kc,
                   preferred_element_type=jnp.float32) * scale
    ends = jnp.arange(n) * cfg.kernel_stride + cfg.kernel_size - 1
    valid = ends[None, :] <= jnp.arange(L)[:, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    return (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(2)


def _scores_kernel(q_ref, kc_ref, p_ref, *, scale: float, block_q: int,
                   stride: int, kernel_size: int):
    iq, g = pl.program_id(1), pl.program_id(2)
    s = _dot(q_ref[0, 0], kc_ref[0], _NT) * scale             # [bq, n]
    t = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = j * stride + (kernel_size - 1) <= t
    s = jnp.where(valid, s, NEG_INF)
    # a row with no whole pooled window yet has m = NEG_INF and exp(0) = 1
    # everywhere: the second select makes it 0
    p = jnp.where(valid, jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)

    @pl.when(g == 0)
    def _first():
        p_ref[0] = p

    @pl.when(g > 0)
    def _rest():
        p_ref[0] += p


def _pooled_probs_kernel(q, kc, scale, cfg: SparseConfig, interpret):
    B, KV, G, L, D = q.shape
    n = kc.shape[2]
    wide = -(-n // _LANES) * _LANES
    kc = jnp.pad(kc, ((0, 0), (0, 0), (0, wide - n), (0, 0)))
    block_q = math.gcd(L, _SCORES_BLOCK_Q)
    p = pl.pallas_call(
        functools.partial(_scores_kernel, scale=scale, block_q=block_q,
                          stride=cfg.kernel_stride,
                          kernel_size=cfg.kernel_size),
        grid=(B * KV, L // block_q, G),
        in_specs=[pl.BlockSpec((1, 1, block_q, D),
                               lambda b, i, g: (b, g, i, 0)),
                  pl.BlockSpec((1, wide, D), lambda b, i, g: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, block_q, wide), lambda b, i, g: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, L, wide), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=SCORES_KERNEL,
    )(q.reshape(B * KV, G, L, D), kc.reshape(B * KV, wide, D))
    return p.reshape(B, KV, L, wide)[..., :n]


def selected_blocks(probs: jax.Array, cfg: SparseConfig) -> jax.Array:
    """P [..., L, n] -> which blocks each query keeps, bool [..., L, L /
    block_size]."""
    *lead, L, n = probs.shape
    size, stride = cfg.block_size, cfg.kernel_stride
    blocks = L // size
    # block b meets the pooled windows per * b - before .. per * b + per - 1
    per, before = size // stride, cfg.kernel_size // stride - 1
    pad = [(0, 0)] * len(lead) + [(0, 0),
                                  (before, per * blocks + before - n)]
    padded = jnp.pad(probs, pad)
    score = functools.reduce(jnp.maximum, (
        padded[..., i:i + per * blocks:per] for i in range(per + before)))
    t = jnp.arange(L)[:, None]
    first = jnp.arange(blocks)[None, :] * size
    causal = first <= t
    forced = causal & ((first < cfg.init_blocks * size)
                       | (first + size - 1 > t - cfg.window_size))
    value = jnp.where(forced, jnp.inf, jnp.where(causal, score, -jnp.inf))
    if blocks <= cfg.topk:
        return jnp.broadcast_to(causal, value.shape)
    kth = jax.lax.top_k(value, cfg.topk)[0][..., -1:]
    return (value >= kth) & causal


# -- attention over the selected blocks ---------------------------------------


def _attend(q, k, v, picked, scale: float, cfg: SparseConfig):
    """The fallback of ``sparse_attn_fwd``: q [B, KV, G, L, D], k and v [B,
    KV, L, D], picked [B, KV, L, blocks] -> [B, KV, G, L, D]."""
    L = q.shape[3]
    s = jnp.einsum("bkgld,bkud->bkglu", q, k,
                   preferred_element_type=jnp.float32) * scale
    keep = (jnp.repeat(picked, cfg.block_size, axis=-1)
            & jnp.tril(jnp.ones((L, L), bool)))[:, :, None]
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkglu,bkud->bkgld", p, v)


def _attend_kernel(bits_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, group: int, block_q: int,
                   block_k: int, tiles: int, block_size: int):
    """The flash forward on rows ``g * block_q + i`` (head g of the group,
    query i of the tile), with the tile's selection under the causal mask."""
    iq, jk = pl.program_id(1), pl.program_id(2)
    D = q_ref.shape[-1]

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row0 = iq * block_q
    lo = jk * tiles
    hi = jnp.minimum(lo + tiles, (row0 + block_q + block_k - 1) // block_k)
    query = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    block = key // block_size

    def tile(t):
        col0 = t * block_k
        rows = pl.ds(pl.multiple_of((t - lo) * block_k, block_k), block_k)
        q = q_ref[0, 0]                                       # [G * bq, d]
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]           # [bk, d]
        s = _dot(q, k, _NT) * scale                           # [G * bq, bk]
        picked = jnp.right_shift(bits_ref[0, t - lo], block) & 1
        keep = jnp.logical_and(picked == 1, key - query <= row0 - col0)
        bias = jnp.where(keep, 0.0, NEG_INF)                  # [bq, bk]
        s = s + jnp.concatenate([bias] * group, axis=0)
        # The first tile holds block 0, which every query keeps, so m is
        # finite from the first tile on and exp(NEG_INF - m) is an exact 0.
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, block_k))
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = (acc_scr[...] * _lanes(alpha, D)
                        + _dot(p.astype(v.dtype), v, _NN))

    _walk(lo, hi, tile)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] * _lanes(1.0 / l, D)).astype(o_ref.dtype)


def _attend_by_kernel(q, k, v, picked, scale, cfg: SparseConfig, interpret):
    B, KV, G, L, D = q.shape
    block_k = cfg.block_size * _TILE_BLOCKS
    block_q = math.gcd(L, max(8, _ROWS // G))
    if L % block_k or cfg.init_blocks < 1:
        raise ValueError(
            f"sparse_attn_fwd: {L} tokens in K/V tiles of {block_k}, "
            f"init_blocks {cfg.init_blocks}: whole tiles, and a first block "
            "that every query keeps")
    n_tiles = L // block_k
    tiles = max(t for t in range(1, _MAJOR_TILES + 1) if n_tiles % t == 0)
    major = tiles * block_k
    nq, nk = L // block_q, n_tiles // tiles
    # the selected blocks of a K/V tile as the bits of one word a query
    bits = jnp.sum(
        picked.reshape(B * KV, L, n_tiles, _TILE_BLOCKS).astype(jnp.int32)
        << jnp.arange(_TILE_BLOCKS, dtype=jnp.int32), axis=-1)
    bits = jnp.swapaxes(bits, 1, 2)[..., None]        # [B * KV, tiles, L, 1]
    # rows g * block_q + i of query tile n: [B * KV, nq, G * block_q, D]
    stacked = q.reshape(B * KV, G, nq, block_q, D).transpose(0, 2, 1, 3, 4)
    stacked = stacked.reshape(B * KV, nq, G * block_q, D)

    def last(i):       # the last major K/V block under query tile i
        return jnp.minimum(((i + 1) * block_q - 1) // major, nk - 1)

    # a step past the diagonal names the block already held: no copy
    q_spec = pl.BlockSpec((1, 1, G * block_q, D), lambda b, i, j: (b, i, 0, 0))
    kv_spec = pl.BlockSpec((1, major, D),
                           lambda b, i, j: (b, jnp.minimum(j, last(i)), 0))
    out = pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, group=G,
                          block_q=block_q, block_k=block_k, tiles=tiles,
                          block_size=cfg.block_size),
        grid=(B * KV, nq, nk),
        in_specs=[pl.BlockSpec((1, tiles, block_q, 1), lambda b, i, j: (
            b, jnp.minimum(j, last(i)), i, 0)), q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(stacked.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((G * block_q, _LANES), jnp.float32),
                        pltpu.VMEM((G * block_q, _LANES), jnp.float32),
                        pltpu.VMEM((G * block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=ATTEND_KERNEL,
    )(bits, stacked, k.reshape(B * KV, L, D), v.reshape(B * KV, L, D))
    out = out.reshape(B * KV, nq, G, block_q, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, KV, G, L, D)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cfg: SparseConfig = SparseConfig(),
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: [batch, seqlen, heads, head_dim]; k and v: [batch, seqlen,
    kv_heads, head_dim], ``heads`` a multiple of ``kv_heads``. Returns
    [batch, seqlen, heads, head_dim]: the selection, then attention over
    the selected blocks. ``seqlen`` is a multiple of ``block_size`` (with
    the kernels, of 8 blocks). Causal throughout, so positions appended on
    the right change nothing before them (and are never selected)."""
    B, L, H, D = q.shape
    KV = k.shape[2]
    if H % KV or v.shape != k.shape or L % cfg.block_size:
        raise ValueError(
            f"sparse_attention: q {q.shape}, K {k.shape}, V {v.shape}, "
            f"blocks of {cfg.block_size}")
    scale = 1.0 / math.sqrt(D)
    q = q.transpose(0, 2, 1, 3).reshape(B, KV, H // KV, L, D)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    kc = pooled_keys(k, cfg)
    if use_kernel:
        if interpret is None:
            interpret = _backend_is_cpu()
        probs = _pooled_probs_kernel(q, kc, scale, cfg, interpret)
        out = _attend_by_kernel(q, k, v, selected_blocks(probs, cfg), scale,
                                cfg, interpret)
    else:
        probs = _pooled_probs(q, kc, scale, cfg)
        out = _attend(q, k, v, selected_blocks(probs, cfg), scale, cfg)
    return out.reshape(B, H, L, D).transpose(0, 2, 1, 3)
