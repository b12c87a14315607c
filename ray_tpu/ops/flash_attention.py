"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The dense-attention op of ``ray_tpu.models``: three Mosaic calls,
``flash_fwd``, ``flash_dq`` and ``flash_dkv``. (Ring attention,
``ray_tpu.parallel.sequence``, is a separate einsum path and never calls
this.) Softmax is computed online, so the O(L^2) score matrix never reaches
HBM; the backward recomputes P from the saved log-sum-exp.

How the problem is tiled. Every matmul works on a ``block_q x block_k``
score tile. One grid step keeps one tile of the *stationary* side in VMEM
(a query tile in the forward and dq, a K/V tile in dk/dv) together with
``block_major`` rows of the *streamed* side, and walks those rows in tiles
with a ``lax.fori_loop`` whose bounds stop at the causal diagonal and at the
sequence's end. With ``block_q`` / ``block_k`` / ``block_major`` left at
``None`` the sizes come from the shape (``_tiles``): sequence lengths,
head_dim and itemsize, held to a VMEM budget that also sets the call's
``vmem_limit_bytes``. A grid step that the causal mask skips names the
block the step before it held, so the pipeline issues no copy for it. Every
tile that is walked applies the mask (one compare and one select against
iotas that do not depend on the tile): measured on a v5e at head_dim 128
that costs the kernels 0.3-0.6%, the VPU having slack under the MXU, where
a second, unmasked body for interior tiles cost every compiled shape 0.16 s
more of tracing, and a ``lax.cond`` round the mask 50-70% of kernel time.

A window. ``flash_attention(..., window=W)`` (causal only) lets position
``t`` attend the keys ``t - W < j <= t``: ``_masked`` drops what lies before,
``_kv_walk`` starts a query tile's walk at the first tile its first row's
window reaches, as it stops it at the diagonal, and a grid step before that
names the first block the tile needs, so nothing is copied for it. Forward
only (serving's prefill): the call is a ``custom_vjp`` of its own whose
gradient raises (the backward walks would take the same bounds).

What is float32. Operands go to the MXU in the arrays' own dtype (bfloat16
in, bfloat16 products accumulated in float32; float32 in, float32 dots).
Scores, the running max and normaliser, lse, delta and the three
accumulators are float32; ``p`` and ``ds`` are cast to the operand dtype at
the dot that consumes them, which is where the plain einsum path of
``models.transformer._attention`` rounds too.

GQA. K and V keep their own head count: query head ``h`` reads K/V head
``h // (H // KVH)`` through the index map, and dk/dv runs its grid over the
K/V heads, accumulating the group's query heads in scratch before one write.

Head widths. q and k share one width and v (and with it o) may have another
(latent attention: q and k of 192, v of 128), forward and backward: v is
never padded to q's width in HBM. The forward's accumulator and output tile
are v's width; in the backward q, k, dq and dk (and their scratch) are q's
width and v, o, dO and dv v's, so the two products against v run at v's
width. The tiles and the VMEM asked for are reckoned at q's width, the wider
(``_tiles``): with equal widths nothing differs from a call of one width.

What a checkpoint may keep. The backward needs q, k, v, the output and
the log-sum-exp. Under a plain ``jax.checkpoint`` all five are made again:
the block is recomputed, ``flash_fwd`` with it, so the forward kernel runs
twice a layer. The forward rule names the last two (``KEPT``: ``flash_out``
and ``flash_lse``, by ``jax.ad_checkpoint.checkpoint_name``) and hands the
*named* output on both as the result and among the residuals, so a
checkpoint whose policy is
``jax.checkpoint_policies.save_only_these_names(*KEPT)`` keeps those two
arrays (o in the operands' dtype and a float32 row a head: at 32 heads of
128 over 8,192 tokens 67.1 MB + 1.0 MB a layer), the recomputed block's
second ``flash_fwd`` has no reader and partial evaluation drops it, and
``flash_dq`` / ``flash_dkv`` read the arrays the first call made: the same
bits, one call fewer. q, k and v are still recomputed with the projections
that make them. ``models.transformer._parts_states`` is the one checkpoint
with that policy; without one the names are identities and lower to
nothing, and every other differentiated program compiles to what it
compiled to.

Runs in interpreter mode only where the backend is ``cpu`` (the CPU test
mesh exercises the same code path); on any other backend the Mosaic kernel
compiles or the call fails. A Mosaic kernel cannot be partitioned by XLA:
under a mesh, call it inside a ``shard_map`` (``models.transformer`` does).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# The names of the forward's output and log-sum-exp (``checkpoint_name``):
# what a ``jax.checkpoint`` policy may keep so that its backward does not run
# ``flash_fwd`` again (the module's docstring, "What a checkpoint may keep").
KEPT = ("flash_out", "flash_lse")
_LANES = 128  # row statistics are kept replicated over a full lane dim

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b

# Tile choice (see ``_tiles``): the score tile the sweep on a v5e settled on,
# the VMEM the streamed side's double buffers may take, and the scoped VMEM a
# Mosaic call gets without asking.
_TILE = 512
_STREAM_BYTES = 8 * 2 ** 20
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _dot(a, b, dims):
    """``a`` and ``b`` to the MXU as they are, accumulated in float32. Below
    32 bits a product is exact in float32, so it is one pass whatever
    precision the caller's context asks for (Mosaic refuses any other)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype.itemsize < 4 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _backend_is_cpu() -> bool:
    return jax.default_backend() == "cpu"


class _Blocks(NamedTuple):
    """``flash_attention``'s tile arguments; ``None`` = from the shape."""
    q: Optional[int]
    k: Optional[int]
    major: Optional[int]


def _fit(tile: int, length: int) -> int:
    """A tile of at most ``tile`` rows for a side of ``length`` rows: a
    multiple of 128 where the side has that many, else the side whole (a
    block may always span a full dimension)."""
    if length <= _LANES:
        return length
    return min(tile, length // _LANES * _LANES)


def _tiles(blocks: _Blocks, Lq: int, Lk: int, D: int, itemsize: int,
           stream_q: bool) -> Tuple[int, int, int, int]:
    """(block_q, block_k, tiles a grid step walks, VMEM bytes) for one call.

    ``stream_q``: dk/dv streams q and dO past a K/V tile; the forward and dq
    stream K and V past a query tile. The streamed side is held
    ``tiles x block`` rows at a time, as many as ``_STREAM_BYTES`` allows for
    its two arrays and two pipeline buffers each, or as ``block_major`` asks.
    """
    bq = _fit(blocks.q or _TILE, Lq)
    bk = _fit(blocks.k or _TILE, Lk)
    (tile, length), still = ((bq, Lq), bk) if stream_q else ((bk, Lk), bq)
    rows = blocks.major or _STREAM_BYTES // (4 * D * itemsize)
    n = max(1, min(rows, length) // tile)
    # What the call holds in VMEM. Streamed side: two arrays, two pipeline
    # buffers each. Stationary side: at most four operand and result tiles,
    # two buffers each, and three float32 scratch tiles a full lane wide.
    # Row statistics, two arrays and two buffers: [bq, 1] blocks pad to 128
    # lanes, [tiles, 1, bq] blocks to 8 sublanes. Some five float32
    # temporaries the size of the score tile.
    vmem = (4 * n * tile * D * itemsize
            + 8 * still * D * itemsize + 3 * still * max(D, _LANES) * 4
            + 4 * 4 * (n * tile * 8 if stream_q else bq * _LANES)
            + 5 * bq * bk * 4)
    return bq, bk, n, vmem


def _compiler_params(vmem: int, grid_rank: int):
    """The first grid axis (batch x heads) is independent; ``vmem`` is
    ``_tiles``'s estimate, asked for with half as much again where it comes
    near what a call gets unasked."""
    limit = None
    if vmem > _DEFAULT_SCOPED_VMEM * 3 // 4:
        limit = vmem * 3 // 2
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (grid_rank - 1),
        vmem_limit_bytes=limit)


def _masked(s, q_axis: int, row0, col0, causal: bool,
            seq_q: Optional[int], seq_k: Optional[int],
            window: Optional[int] = None):
    """Scores ``s`` with NEG_INF where they are not attended. ``s``'s
    ``q_axis`` runs over query rows from ``row0``, the other axis over key
    columns from ``col0``; ``seq_q`` / ``seq_k`` are given only where that
    side has a ragged end; with ``window`` a row ``t`` attends the columns
    ``t - window < j`` alone. A compare and a select for each condition,
    against iotas that do not depend on the tile; nothing where the call
    has neither a diagonal nor a ragged end."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = []
    if causal:
        keep.append(rows - cols >= col0 - row0)
    if window is not None:
        keep.append(rows - cols < window + col0 - row0)
    if seq_k is not None:
        keep.append(cols < seq_k - col0)
    if seq_q is not None:
        keep.append(rows < seq_q - row0)
    if not keep:
        return s
    return jnp.where(functools.reduce(jnp.logical_and, keep), s, NEG_INF)


def _zero_pad_rows(x, start, seq: Optional[int]):
    """Rows of a ragged last tile past ``seq`` hold whatever was in VMEM
    (possibly NaN or Inf); zero them so their 0-weighted products stay 0."""
    if seq is None:
        return x
    valid = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) < seq - start
    return jnp.where(valid, x, jnp.zeros_like(x))


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic as ``[rows, n]``."""
    reps, rem = divmod(n, _LANES)
    if rem:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _walk(lo, hi, tile):
    """Run ``tile(t)`` for t in [lo, hi)."""

    def step(t, carry):
        tile(t)
        return carry

    jax.lax.fori_loop(lo, hi, step, None)


def _ragged(length: int, block: int) -> Optional[int]:
    """``length`` where its last tile of ``block`` rows is ragged."""
    return length if length % block else None


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _window_start(iq, block_q, window):
    """The first key a query tile's first row sees through ``window``."""
    return jnp.maximum(iq * block_q - window + 1, 0)


def _kv_walk(iq, jk, *, causal, block_q, block_k, tiles, seq_k, window=None):
    """The K/V tiles a query tile needs out of major block ``jk``, as global
    tile numbers [lo, hi), and the block's first tile (a tile's rows in the
    block held count from there)."""
    first = lo = jk * tiles
    hi = jnp.minimum(lo + tiles, pl.cdiv(seq_k, block_k))
    if causal:  # tiles wholly above the diagonal contribute nothing
        hi = jnp.minimum(hi, ((iq + 1) * block_q + block_k - 1) // block_k)
    if window is not None:  # nor those wholly before the window's reach
        lo = jnp.maximum(lo, _window_start(iq, block_q, window) // block_k)
    return first, lo, hi


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                tiles: int, seq_k: int, window: Optional[int] = None):
    iq, jk = pl.program_id(1), pl.program_id(2)
    Dv = v_ref.shape[-1]           # the accumulator's and the output's width

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row0 = iq * block_q
    ragged_k = _ragged(seq_k, block_k)
    first, lo, hi = _kv_walk(iq, jk, causal=causal, block_q=block_q,
                             block_k=block_k, tiles=tiles, seq_k=seq_k,
                             window=window)

    def tile(t):
        col0 = t * block_k
        rows = pl.ds(pl.multiple_of((t - first) * block_k, block_k), block_k)
        q = q_ref[0]                                          # [bq, d]
        k = k_ref[0, rows, :]                                 # [bk, d]
        v = _zero_pad_rows(v_ref[0, rows, :], col0, ragged_k)  # [bk, dv]
        s = _dot(q, k, _NT) * scale                           # [bq, bk]
        s = _masked(s, 0, row0, col0, causal, None, ragged_k, window)
        # Every row meets an attended column in the first tile it walks
        # (column 0 under the causal mask), so m is finite from there on and
        # exp(NEG_INF - m) is an exact 0: p needs no second mask. (Under a
        # window a tile's later rows may see nothing of the first tiles
        # walked: m stays NEG_INF there, p is exp(0) and what it adds is
        # multiplied by alpha = exp(NEG_INF - m) = 0 at the first tile that
        # holds a column the row attends, its own diagonal at the latest.)
        m_prev, l_prev = m_scr[...], l_scr[...]               # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, block_k))               # [bq, bk]
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = (acc_scr[...] * _lanes(alpha, Dv)
                        + _dot(p.astype(v.dtype), v, _NN))

    _walk(lo, hi, tile)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] * _lanes(1.0 / l, Dv)).astype(o_ref.dtype)
        # lse is stored compact [BH, Lq, 1]: same column orientation as the
        # scratch stats, single lane (Mosaic allows full-dim lane blocks).
        lse_ref[0] = m_scr[:, :1] + jnp.log(l[:, :1])         # [bq, 1]


def _query_stationary(blocks: _Blocks, q, k, causal: bool,
                      window: Optional[int] = None):
    """The layout the forward and dq share: a query tile stays, ``major``
    rows of K and V stream past it. Returns the kernels' tile arguments, the
    VMEM estimate, the grid and the makers of a q-shaped and a K/V operand's
    spec at a head width (q's and k's, or v's and o's). With ``window`` (the
    forward alone) the major blocks before a query tile's reach are not
    copied either."""
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    group = BH // k.shape[0]
    block_q, block_k, tiles, vmem = _tiles(blocks, Lq, Lk, D,
                                           q.dtype.itemsize, stream_q=False)
    major = tiles * block_k
    nq, nk = pl.cdiv(Lq, block_q), pl.cdiv(Lk, major)

    def last(i):  # the last major K/V block query tile i needs
        if not causal:
            return nk - 1
        return jnp.minimum(((i + 1) * block_q - 1) // major, nk - 1)

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))

    # Query head b reads K/V head b // group; a step past the diagonal
    # names the block already held, so the pipeline copies nothing for it.
    def held(i, j):     # the major block step (i, j) holds
        j = jnp.minimum(j, last(i))
        if window is None:
            return j
        return jnp.maximum(j, _window_start(i, block_q, window) // major)

    def kv_spec(width):
        return pl.BlockSpec(
            (1, major, width), lambda b, i, j: (b // group, held(i, j), 0))

    args = dict(causal=causal, block_q=block_q, block_k=block_k, tiles=tiles,
                seq_k=Lk)
    return args, vmem, (BH, nq, nk), q_spec, kv_spec


def _flash_fwd(q, k, v, scale, causal, blocks, interpret, window=None):
    BH, Lq, D = q.shape
    Dv = v.shape[-1]
    args, vmem, grid, q_spec, kv_spec = _query_stationary(blocks, q, k,
                                                          causal, window)
    if window is not None:
        args["window"] = window
    block_q = args["block_q"]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, **args),
        grid=grid,
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv)],
        out_specs=[
            q_spec(Dv),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem, 3),
        interpret=interpret,
        name="flash_fwd",  # the XLA Ops line of a device trace carries it
    )(q, k, v)
    return out, lse[:, :, 0]


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, block_q, block_k, tiles, seq_k):
    iq, jk = pl.program_id(1), pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row0 = iq * block_q
    ragged_k = _ragged(seq_k, block_k)
    first, lo, hi = _kv_walk(iq, jk, causal=causal, block_q=block_q,
                             block_k=block_k, tiles=tiles, seq_k=seq_k)

    def tile(t):
        col0 = t * block_k
        rows = pl.ds(pl.multiple_of((t - first) * block_k, block_k), block_k)
        q, do = q_ref[0], do_ref[0]                           # [bq, d]
        k = _zero_pad_rows(k_ref[0, rows, :], col0, ragged_k)  # [bk, d]
        v = _zero_pad_rows(v_ref[0, rows, :], col0, ragged_k)
        s = _dot(q, k, _NT) * scale
        s = _masked(s, 0, row0, col0, causal, None, ragged_k)
        p = jnp.exp(s - lse_ref[0])                           # [bq, bk]
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta_ref[0])       # times scale, once, at the end
        acc_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    _walk(lo, hi, tile)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k, tiles, seq_k, seq_q):
    """Scores are built transposed, ``[bk, bq]``: query rows run along the
    lanes, so lse and delta come as lane-dense rows and dv and dk are plain
    ``[bk, bq] @ [bq, d]`` products."""
    jk, g, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    col0 = jk * block_k
    lo = iq * tiles
    hi = jnp.minimum(lo + tiles, pl.cdiv(seq_q, block_q))
    if causal:  # query tiles wholly above the diagonal contribute nothing
        lo = jnp.maximum(lo, col0 // block_q)
    ragged_q, ragged_k = _ragged(seq_q, block_q), _ragged(seq_k, block_k)

    def tile(t):
        row0 = t * block_q
        local = t - iq * tiles
        rows = pl.ds(pl.multiple_of(local * block_q, block_q), block_q)
        k, v = k_ref[0], v_ref[0]                             # [bk, d]
        # Pad *query* rows of a ragged last tile would contaminate the
        # dk/dv sums (they reduce over q rows): zero the sources.
        q = _zero_pad_rows(q_ref[0, rows, :], row0, ragged_q)  # [bq, d]
        do = _zero_pad_rows(do_ref[0, rows, :], row0, ragged_q)
        st = _dot(k, q, _NT) * scale                          # [bk, bq]
        st = _masked(st, 1, row0, col0, causal, ragged_q, ragged_k)
        pt = jnp.exp(st - lse_ref[0, local])
        dv_scr[...] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        dst = pt * (dpt - delta_ref[0, local])  # times scale at the end
        dk_scr[...] += _dot(dst.astype(q.dtype), q, _NN)

    _walk(lo, hi, tile)

    @pl.when(jnp.logical_and(g == pl.num_programs(2) - 1,
                             iq == pl.num_programs(3) - 1))
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(scale, causal, blocks, interpret, residuals, g):
    q, k, v, out, lse = residuals
    do = g
    BH, Lq, D = q.shape
    Dv = v.shape[-1]               # v's, o's, dO's and dv's width
    BKV, Lk, _ = k.shape
    group = BH // BKV
    itemsize = q.dtype.itemsize
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # [BH, Lq]

    args, vmem, grid, q_spec, kv_spec = _query_stationary(blocks, q, k, causal)
    row_spec = pl.BlockSpec((1, args["block_q"], 1),
                            lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, **args),
        grid=grid,
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv), q_spec(Dv), row_spec,
                  row_spec],
        out_specs=q_spec(D),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((args["block_q"], D), jnp.float32)],
        compiler_params=_compiler_params(vmem, 3),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse[:, :, None], delta[:, :, None])

    block_q, block_k, tiles, vmem = _tiles(blocks, Lq, Lk, D, itemsize,
                                           stream_q=True)
    major = tiles * block_q
    nq, nk = pl.cdiv(Lq, major), pl.cdiv(Lk, block_k)

    def first(j):  # the first major q block K/V tile j needs
        return jnp.minimum(j * block_k // major, nq - 1) if causal else 0

    # One lane-dense row of block_q statistics for each query tile.
    def rows(x):
        x = jnp.pad(x, ((0, 0), (0, nq * major - Lq)))
        return x.reshape(BH, nq * tiles, 1, block_q)

    def q_spec(width):
        return pl.BlockSpec(
            (1, major, width),
            lambda b, j, g, i: (b * group + g, jnp.maximum(i, first(j)), 0))

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, j, g, i: (b, j, 0))

    row_spec = pl.BlockSpec(
        (1, tiles, 1, block_q),
        lambda b, j, g, i: (b * group + g, jnp.maximum(i, first(j)), 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, tiles=tiles,
                          seq_k=Lk, seq_q=Lq),
        grid=(BKV, nk, group, nq),
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv), q_spec(Dv), row_spec,
                  row_spec],
        out_specs=[kv_spec(D), kv_spec(Dv)],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, Lk, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem, 4),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, rows(lse), rows(delta))
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_bhld(q, k, v, scale, causal, blocks, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, blocks, interpret)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_window_bhld(q, k, v, scale, window, blocks, interpret):
    """The causal forward through a window. Its backward walk would take the
    same bounds (``_kv_walk``'s for dq, their mirror for dk/dv); nothing
    trains a window yet, so differentiating it is refused."""
    out, _ = _flash_fwd(q, k, v, scale, True, blocks, interpret, window)
    return out


def _no_window_backward(*_):
    raise NotImplementedError(
        "flash_attention: a window has no backward pass (flash_dq and "
        "flash_dkv walk the causal triangle whole); train without the "
        "kernel (use_flash=False) or give the backward walk the window's "
        "bounds")


_flash_window_bhld.defvjp(_no_window_backward, _no_window_backward)


def _fwd_rule(q, k, v, scale, causal, blocks, interpret):
    # the named arrays are both the primal and the residuals: once a policy
    # keeps them, nothing reads a recomputed call's results
    out, lse = map(checkpoint_name,
                   _flash_fwd(q, k, v, scale, causal, blocks, interpret), KEPT)
    return out, (q, k, v, out, lse)


_flash_attention_bhld.defvjp(_fwd_rule, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_major: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention. q: [batch, seqlen, heads, head_dim]; k:
    [batch, seqlen_k, kv_heads, head_dim] and v: [batch, seqlen_k, kv_heads,
    v_dim] with ``heads`` a multiple of ``kv_heads`` (query head h attends
    K/V head ``h // (heads // kv_heads)``).

    Returns [batch, seqlen, heads, v_dim]. Differentiable (custom VJP),
    whatever ``v_dim`` is. ``window`` (causal only): position ``t`` attends
    the keys ``t - window < j <= t``; the walk starts at the first tile the
    window reaches, as it stops at the diagonal. Forward only: its gradient
    raises.
    ``block_q x block_k`` is the score tile; ``block_major`` is how many rows
    of the streamed side (K/V in the forward and dq, q/dO in dk/dv) a grid
    step holds in VMEM. ``None`` = chosen from the shape.
    """
    B, Lq, H, D = q.shape
    Lk, KVH = k.shape[1], k.shape[2]
    if H % KVH or k.shape[-1] != D or v.shape[:3] != k.shape[:3]:
        raise ValueError(
            f"flash_attention: q {q.shape} over K {k.shape} / V {v.shape}: K "
            "has q's head width, V has K's batch, length and heads (its head "
            "width is its own) and their heads divide the query's")
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"flash_attention: window={window} needs causal "
                         "attention and at least one key a row")
    Dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = _backend_is_cpu()
    # [B, L, H, D] -> [B*H, L, D]
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B * KVH, Lk, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B * KVH, Lk, Dv)
    blocks = _Blocks(block_q, block_k, block_major)
    if window is None:
        out = _flash_attention_bhld(qb, kb, vb, scale, causal, blocks,
                                    interpret)
    else:
        out = _flash_window_bhld(qb, kb, vb, scale, int(window), blocks,
                                 interpret)
    return out.reshape(B, H, Lq, Dv).transpose(0, 2, 1, 3)
