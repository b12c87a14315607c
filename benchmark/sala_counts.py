"""Operations and bytes of MiniCPM-SALA's two attention operations from
shapes alone, and the readers of its cell's per-layer metrics.

Both counts are what the *algorithm* needs, not what the kernels do. Linear
attention is counted as its recurrence (``S_t = lam S_{t-1} + k_t^T v_t``,
``o_t = q_t S_t``: two products of ``head_dim x head_dim`` a token and head);
the chunked kernel does more (the ``C x C`` products inside a chunk), so its
share of the roofline reads low rather than high. Sparse attention is
counted over the tokens a query may select at most, ``min(t + 1, topk *
block_size)``, plus its scores against the pooled keys whose windows lie at
or before it; the kernel walks every K/V tile under the diagonal and masks
the unselected tokens, so its share reads what a kernel that gathered would
gain.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no call of that name (the
parent commit's program, another architecture's cell), or dims without
``mixer_types``. A call's sizes are read off its own result's shape in the
operation's HLO text, so the counts follow the lengths the window's calls
really had.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark import peaks, program_spans
from benchmark.flops import MATMUL
from benchmark.reducers import Context
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, NS, parse_op

LINEAR_CALL = "linear_attn_fwd"
SPARSE_SCORES, SPARSE_ATTEND = "sparse_attn_scores", "sparse_attn_fwd"
RESULT = re.compile(r" = \(?[a-z]+\d*\[([\d,]*)\]")

# The accepted reader under this module's name: ``proxy_self_ms.longdoc``
# names it here (the accepted suite counts the files that name
# ``program_spans``).
span_self_ms = program_spans.span_self_ms


# -- counts ------------------------------------------------------------------


def linear_attn_flops(batch: int, seq: int, heads: int, head_dim: int
                      ) -> float:
    """The recurrence: ``k_t^T v_t`` into the state and ``q_t S_t`` out of
    it, each ``head_dim x head_dim`` multiply-adds a token and head."""
    return 2 * MATMUL * batch * seq * heads * head_dim * head_dim


def linear_attn_bytes(batch: int, seq: int, heads: int, head_dim: int,
                      itemsize: int = 2) -> float:
    """q, k, v in and o out, once each; the state stays on the chip."""
    return 4 * batch * seq * heads * head_dim * itemsize


def selectable_tokens(seq: int, sparse: Dict[str, int]) -> int:
    """Sum over the queries t of a sequence of ``min(t + 1, topk *
    block_size)``: the tokens a query attends at most."""
    most = min(seq, sparse["topk"] * sparse["block_size"])
    return most * (most + 1) // 2 + (seq - most) * most


def pooled_windows(seq: int, sparse: Dict[str, int]) -> int:
    """Sum over the queries t of the pooled windows that lie at or before
    t: ``(t - kernel_size + 1) // kernel_stride + 1`` where positive."""
    size, stride = sparse["kernel_size"], sparse["kernel_stride"]
    return int((np.arange(max(0, seq - size + 1)) // stride + 1).sum())


def sparse_attn_flops(batch: int, seq: int, heads: int, head_dim: int,
                      sparse: Dict[str, int]) -> float:
    """QK^T and PV over the selectable tokens, and the scores against the
    pooled keys, for every query head."""
    each = MATMUL * batch * heads * head_dim
    return each * (2 * selectable_tokens(seq, sparse)
                   + pooled_windows(seq, sparse))


def sparse_attn_bytes(batch: int, seq: int, heads: int, kv_heads: int,
                      head_dim: int, itemsize: int = 2) -> float:
    """q in and o out at the query heads, k and v in at the K/V heads, once
    each; the pooled keys and the selection are made of them on the chip."""
    return 2 * batch * seq * (heads + kv_heads) * head_dim * itemsize


def min_seconds(flops: float, nbytes: float, device_kind: str
                ) -> Tuple[float, str]:
    """The least time the chip could take and which bound applies."""
    peak = peaks.peak(device_kind)
    by_flops = flops / peak.bf16_flops_per_s
    by_bytes = nbytes / peak.hbm_bytes_per_s
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


# -- the window's calls --------------------------------------------------------


@functools.lru_cache(maxsize=4)
def _device_ops(path: str) -> Tuple[Tuple[str, str, Tuple[int, ...], int,
                                          int], ...]:
    """The first device's operations as (name, category, result's shape,
    start, end): the reduced trace keeps no shape."""
    from jax.profiler import ProfileData
    planes = {int(m.group(1)): plane
              for plane in ProfileData.from_file(path).planes
              if (m := DEVICE_PLANE.match(plane.name))}
    out = []
    if planes:
        for line in planes[min(planes)].lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    name, category = parse_op(ev.name)
                    shape = RESULT.search(ev.name)
                    dims = tuple(int(n) for n in shape.group(1).split(",")
                                 if n) if shape else ()
                    start = int(ev.start_ns)
                    out.append((name, category, dims, start,
                                start + int(ev.duration_ns)))
    return tuple(out)


def _named(name: str) -> re.Pattern:
    return re.compile(rf"(^|_){re.escape(name)}(_|\.|$)")


def window_calls(ops, window, names: List[str], ranking: bool = False):
    """The Mosaic calls named ``names`` that lie inside the window, as
    {name: [(shape, seconds)]}; with ``ranking`` also the sorts (the
    selection's ``top_k``) under ``"sort"``."""
    found: Dict[str, List[Tuple[Tuple[int, ...], float]]] = {}
    wanted = [(want, _named(want)) for want in names]
    for name, category, shape, start, end in ops:
        if start < window[0] or end > window[1]:
            continue
        if program_spans.KERNEL_CATEGORY in category:
            for want, pattern in wanted:
                if pattern.search(name):
                    found.setdefault(want, []).append((shape,
                                                       (end - start) * NS))
        elif ranking and category == "sort":
            found.setdefault("sort", []).append((shape, (end - start) * NS))
    return found


def _calls(ctx: Context, names: List[str], ranking: bool = False):
    """``window_calls`` of this run, and the sizes of its model; ``None``
    where the trace has no device plane or the cell no such layers."""
    dims = ctx.counters.get("dims", {})
    if (ctx.trace is None or not ctx.trace.devices
            or "mixer_types" not in dims):
        return None
    path = program_spans.find_trace(tuple(ctx.trace.window))
    if path is None:
        return None
    found = window_calls(_device_ops(path), ctx.trace.window, names, ranking)
    return (found, dims) if any(n in found for n in names) else None


def _share_pct(ctx: Context, found) -> Optional[float]:
    busy = ctx.trace.first.busy_ns(ctx.trace.window) * NS
    mine = sum(s for calls in found.values() for _, s in calls)
    return 100.0 * mine / busy if busy else None


# -- readers -------------------------------------------------------------------


def linear_attn_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the ``linear_attn_fwd`` calls over the window's busy
    time."""
    got = _calls(ctx, [LINEAR_CALL])
    return _share_pct(ctx, got[0]) if got else None


def sparse_attn_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of ``sparse_attn_scores``, the ranking's sorts and
    ``sparse_attn_fwd`` over the window's busy time."""
    got = _calls(ctx, [SPARSE_SCORES, SPARSE_ATTEND], ranking=True)
    return _share_pct(ctx, got[0]) if got else None


def linear_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                             ) -> Optional[float]:
    """The least time for the window's ``linear_attn_fwd`` calls (each
    result is [batch x heads, seq, head_dim]) over their device time."""
    got = _calls(ctx, [LINEAR_CALL])
    if not got:
        return None
    found, dims = got
    least, bounds = 0.0, set()
    for (rows, seq, head_dim), _ in found[LINEAR_CALL]:
        batch = rows // dims["n_heads"]
        s, bound = min_seconds(
            linear_attn_flops(batch, seq, dims["n_heads"], head_dim),
            linear_attn_bytes(batch, seq, dims["n_heads"], head_dim),
            ctx.device_kind)
        least += s
        bounds.add(bound)
    spent = sum(s for _, s in found[LINEAR_CALL])
    ctx.notes.append(
        f"linear attention roofline: {len(found[LINEAR_CALL])} calls, least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f}; bound by "
        f"{sorted(bounds)}")
    return 100.0 * least / spent


def sparse_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                             ) -> Optional[float]:
    """The least time for the window's sparse-attention operations (one a
    ``sparse_attn_fwd`` call, whose result is [batch x kv_heads, query
    tiles, group x tile, head_dim]) over the device time of their calls:
    the scores, the ranking's sorts and the attention."""
    got = _calls(ctx, [SPARSE_SCORES, SPARSE_ATTEND], ranking=True)
    if not got or SPARSE_ATTEND not in got[0]:
        return None
    found, dims = got
    heads, kv = dims["n_heads"], dims["n_kv_heads"]
    least, bounds = 0.0, set()
    for (rows, tiles, stacked, head_dim), _ in found[SPARSE_ATTEND]:
        batch, seq = rows // kv, tiles * stacked // (heads // kv)
        s, bound = min_seconds(
            sparse_attn_flops(batch, seq, heads, head_dim,
                              dims["sparse_config"]),
            sparse_attn_bytes(batch, seq, heads, kv, head_dim),
            ctx.device_kind)
        least += s
        bounds.add(bound)
    spent = {name: sum(s for _, s in calls) for name, calls in found.items()}
    ctx.notes.append(
        f"sparse attention roofline: {len(found[SPARSE_ATTEND])} operations, "
        f"least {least * 1e3:.3f} ms; device ms "
        + ", ".join(f"{n} {s * 1e3:.3f}" for n, s in sorted(spent.items()))
        + f"; bound by {sorted(bounds)}")
    return 100.0 * least / sum(spent.values())
