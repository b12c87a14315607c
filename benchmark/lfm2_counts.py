"""Operations and bytes of LFM2's layers from shapes alone, and the readers of
its cell's per-layer metrics.

The counts are what the *algorithm* needs. A forward's model FLOPs are its
matmuls at the padded tokens it ran (a mixer's projections, the dense FFN or
the router and ``top_k`` experts a token: each routed pair once, whatever
expert it went to), the convolution's taps, its causal attention at 64-wide
heads, and the tied head at the last positions. The grouped product is
counted at the pairs the program's router really sent (its ``moe.route``
spans carry the counters' increments), against the weights of the experts
that hold rows in a step of the dropless loop, read once a step: what a
step must read, not what this loop reads.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no call of that name, a
span without ``steps`` (a program from before this cell), or dims without
``layer_types``. A forward's shape is read off its own ``flash_fwd`` calls'
results, so the counts follow the lengths the window's calls really had.
The trace helpers are ``sala_counts``', ``longcat_counts``' and
``device_scopes``', imported, not copied.

The dropless loop makes this cell's window some 3.5 million device
operations (25,000 steps of 130), twenty times the prefill cell's: every
reader here works on ``device_scopes``' one decoding of the trace and on one
merge of the operations' intervals (``_merged``), and ``execution_busy_ms``
is the accepted reader's arithmetic in one pass (the accepted one clips and
merges every operation once for each execution: 140 x 3.5 million).
"""

from __future__ import annotations

import bisect
import functools
import itertools
import statistics
from typing import Any, Dict, FrozenSet, List, Optional, Tuple

from benchmark import device_scopes, peaks, program_spans
from benchmark.flops import MATMUL
from benchmark.longcat_counts import (FLASH_CALL, RAGGED_DOT, ROUTE_SPAN,
                                      causal_pairs)
from benchmark.reducers import Context
from benchmark.sala_counts import RESULT, _named, min_seconds
from benchmark.trace_reduce import NS, Interval, merge

ATTENTION = "full_attention"        # ``layer_types``' value
EXPERTS_SCOPE = "experts"           # the dropless loop (parallel/expert.py)


# -- counts ------------------------------------------------------------------


def attention_params(dims: Dict[str, Any]) -> int:
    """The attention mixer's matmul weights: q and o at ``n_heads``, k and v
    at ``n_kv_heads``."""
    d, hd = dims["d_model"], dims["head_dim"]
    return 2 * d * hd * (dims["n_heads"] + dims["n_kv_heads"])


def shortconv_params(dims: Dict[str, Any]) -> int:
    """The short-convolution mixer's matmul weights: ``W_in`` d -> 3d and
    ``W_out`` d -> d."""
    return 4 * dims["d_model"] ** 2


def expert_params(dims: Dict[str, Any]) -> int:
    """One expert's weights (SwiGLU: gate, up, down)."""
    return 3 * dims["d_model"] * dims["expert_width"]


def is_moe(i: int, dims: Dict[str, Any]) -> bool:
    return dims["layer_ids"][i] >= dims["num_dense_layers"]


def attention_layers(dims: Dict[str, Any]) -> int:
    return sum(t == ATTENTION for t in dims["layer_types"])


def layer_flops_per_token(i: int, dims: Dict[str, Any]) -> float:
    """Layer ``i``'s FLOPs a token outside attention's scores: the mixer's
    projections (and the convolution's taps, a multiply-add a tap and
    channel), then the dense FFN, or the router and ``top_k`` experts."""
    d = dims["d_model"]
    if dims["layer_types"][i] == ATTENTION:
        mixer = MATMUL * attention_params(dims)
    else:
        mixer = MATMUL * (shortconv_params(dims) + dims["conv_width"] * d)
    if is_moe(i, dims):
        return mixer + MATMUL * (d * dims["n_routed"]
                                 + dims["top_k"] * expert_params(dims))
    return mixer + MATMUL * 3 * d * dims["d_ff"]


def attn_flops(batch: int, seq: int, dims: Dict[str, Any]) -> float:
    """One attention call: QK^T and PV at ``head_dim`` over the causal pairs
    of every query head."""
    return (MATMUL * 2 * dims["head_dim"] * causal_pairs(seq)
            * dims["n_heads"] * batch)


def attn_bytes(batch: int, seq: int, dims: Dict[str, Any],
               itemsize: int = 2) -> float:
    """q in and o out at the query heads, k and v in at the K/V heads, once
    each."""
    return (2 * batch * seq * (dims["n_heads"] + dims["n_kv_heads"])
            * dims["head_dim"] * itemsize)


def forward_flops(batch: int, seq: int, dims: Dict[str, Any]) -> float:
    """Model FLOPs of one served forward of ``batch`` padded prompts of
    ``seq`` tokens: every layer at every padded token, an attention call in
    each attention layer, the head at the ``batch`` last positions."""
    layers = sum(layer_flops_per_token(i, dims)
                 for i in range(dims["n_layers"]))
    return (batch * seq * layers
            + attention_layers(dims) * attn_flops(batch, seq, dims)
            + MATMUL * batch * dims["d_model"] * dims["vocab_size"])


def expert_matmul_flops(pairs: float, dims: Dict[str, Any]) -> float:
    return MATMUL * pairs * expert_params(dims)


def expert_matmul_bytes(pairs: float, expert_reads: float,
                        dims: Dict[str, Any], itemsize: int = 2) -> float:
    """``expert_reads`` experts' weights, and each routed pair's token in
    and its result out."""
    return itemsize * (expert_reads * expert_params(dims)
                       + pairs * 2 * dims["d_model"])


def expert_reads(layer_calls: float, steps: float, experts: float) -> float:
    """Experts whose weights the dropless steps of ``layer_calls`` layer
    calls must read, each once a step it holds rows in: the pairs are
    listed by expert, so a layer call reads each of its experts once and
    once more for each of its ``steps - 1`` step boundaries, which falls
    inside one expert's rows (every expert taken to hold rows: at 64
    experts and 2,048 tokens or more none is empty)."""
    return layer_calls * experts + (steps - layer_calls)


# -- the window's operations ---------------------------------------------------


def _dims(ctx: Context) -> Optional[Dict[str, Any]]:
    dims = ctx.counters.get("dims", {})
    if ctx.trace is None or not ctx.trace.devices or "layer_types" not in dims:
        return None
    return dims


_MERGED: Dict[Tuple[int, Interval], Tuple[List[int], ...]] = {}


def _merged(ctx: Context) -> Tuple[List[int], List[int], List[int]]:
    """The first device's operations inside the window merged into disjoint
    busy intervals, once a run: their starts, their ends and the running
    sum of their lengths before each."""
    dev, (lo, hi) = ctx.trace.first, ctx.trace.window
    key = (id(dev), (lo, hi))
    if key not in _MERGED:
        busy = merge((max(e.start, lo), min(e.end, hi)) for e in dev.ops
                     if e.end > lo and e.start < hi)
        _MERGED.clear()                 # one run's trace at a time
        _MERGED[key] = ([s for s, _ in busy], [e for _, e in busy],
                        [0, *itertools.accumulate(e - s for s, e in busy)])
    return _MERGED[key]


def _busy_inside(ctx: Context, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` in which the device is busy."""
    starts, ends, before = _merged(ctx)
    first = bisect.bisect_right(ends, lo)       # ends after lo
    last = bisect.bisect_left(starts, hi)       # starts before hi
    if first >= last:
        return 0
    whole = before[last] - before[first]
    return whole - max(0, lo - starts[first]) - max(0, ends[last - 1] - hi)


def _busy_s(ctx: Context) -> float:
    return _busy_inside(ctx, *ctx.trace.window) * NS


@functools.lru_cache(maxsize=2)
def _flash_calls_in(window: Interval) -> Tuple[Tuple[Tuple[int, ...], float],
                                               ...]:
    wanted = _named(FLASH_CALL)
    calls = []
    for op in device_scopes._run_leaves(window) or ():
        if program_spans.KERNEL_CATEGORY not in op.record.name:
            continue
        name = op.record.name.split(" = ")[0].lstrip("%")
        shape = RESULT.search(op.record.name)
        if wanted.search(name) and shape:
            calls.append((tuple(int(n) for n in shape.group(1).split(",")
                                if n), op.seconds))
    return tuple(calls)


def _flash_calls(ctx: Context) -> Tuple[Tuple[Tuple[int, ...], float], ...]:
    """The window's ``flash_fwd`` calls as (result's shape [batch x heads,
    seq, head_dim], seconds), told by their own name among the kernels and
    sized by the result in their HLO text; walked once a run."""
    return _flash_calls_in(tuple(ctx.trace.window))


def _route_spans(ctx: Context) -> List[Dict[str, float]]:
    """The attributes of the program's ``moe.route`` spans inside the
    window that carry the loop's ``steps``: one a forward."""
    spans = program_spans.inside(
        program_spans.named(program_spans.program_spans(ctx), ROUTE_SPAN),
        tuple(ctx.trace.window))
    keys = ("held", "layers", "experts", "steps")
    return [{k: float(s.attrs[k]) for k in keys} for s in spans
            if all(k in s.attrs for k in keys)]


def program_scopes() -> FrozenSet[str]:
    """The scopes the program says it enters: ``DEVICE_SCOPES`` and those
    declared after the accepted reader pinned that set
    (``LATER_DEVICE_SCOPES``; none on a program from before them)."""
    from ray_tpu.observability import metric_names
    return device_scopes.program_scopes() | frozenset(
        getattr(metric_names, "LATER_DEVICE_SCOPES", ()))


# -- readers -------------------------------------------------------------------


def execution_busy_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """``reducers.execution_busy_ms``: device busy time inside one
    execution of the program ``p["program"]``, the median over the window's
    executions or with ``"stat": "mean"`` the mean."""
    if _dims(ctx) is None:
        return None
    runs = ctx.trace.first.executions(p["program"], ctx.trace.window)
    if not runs:
        return None
    busy = [_busy_inside(ctx, r.start, r.end) * NS * 1e3 for r in runs]
    return (statistics.fmean(busy) if p.get("stat") == "mean"
            else statistics.median(busy))


def scope_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """``device_scopes.scope_share_pct`` over ``program_scopes()``: the
    device time of the leaf operations whose ``tf_op`` names ``p["scope"]``
    first (``"unscoped"``: none) over the device time of all the window's
    leaves. The first call of a run leaves the whole table as a note."""
    if _dims(ctx) is None:
        return None
    window, scopes = tuple(ctx.trace.window), program_scopes()
    rows = device_scopes._run_rows(window, scopes)
    heads = {key: row for key, row in rows.items() if len(key) == 1}
    if not any(key != (device_scopes.UNSCOPED,) for key in heads):
        return None
    if not any(n.startswith(device_scopes.NOTE_HEAD) for n in ctx.notes):
        ctx.notes.append(device_scopes.table(
            device_scopes._run_leaves(window), scopes))
    total = sum(row.seconds for row in heads.values())
    mine = heads.get((p["scope"],), device_scopes.Row()).seconds
    return 100.0 * mine / total if total else None


def fwd_mfu_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of the forwards the window ran over its device busy time
    x the chip's bf16 peak. A forward of [batch, seq] shows as one
    ``flash_fwd`` call an attention layer, whose result is [batch x heads,
    seq, head_dim]."""
    dims = _dims(ctx)
    calls = _flash_calls(ctx) if dims else []
    busy = _busy_s(ctx) if calls else 0.0
    if not busy:
        return None
    per_forward = attention_layers(dims)
    shapes = [(rows // dims["n_heads"], seq) for (rows, seq, _), _ in calls]
    flops = sum(forward_flops(b, seq, dims) for b, seq in shapes) / per_forward
    padded = sum(b * seq for b, seq in shapes) / per_forward
    # the prompts' own tokens of the replies the window counted
    real = (ctx.counters.get("serve_tokens_per_s", 0.0)
            * ctx.counters.get("window_s", 0.0))
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"forward mfu: {len(calls) / per_forward:.1f} forwards of "
        f"{padded:.0f} padded tokens ({real:.0f} real tokens answered: pad "
        f"positions are routed and multiplied like tokens), "
        f"{flops / 1e12:.3f} model TFLOP in {busy:.3f} s busy")
    return 100.0 * flops / (busy * peak)


def gqa64_attn_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the ``flash_fwd`` calls over the window's busy time."""
    calls = _flash_calls(ctx) if _dims(ctx) else []
    busy = _busy_s(ctx) if calls else 0.0
    return 100.0 * sum(s for _, s in calls) / busy if busy else None


def gqa64_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                            ) -> Optional[float]:
    """The least time for the window's ``flash_fwd`` calls (causal FLOPs at
    64-wide heads against the bytes of q, k, v and o) over their device
    time."""
    dims = _dims(ctx)
    calls = _flash_calls(ctx) if dims else []
    if not calls:
        return None
    least, bounds = 0.0, set()
    for (rows, seq, _), _ in calls:
        batch = rows // dims["n_heads"]
        s, bound = min_seconds(attn_flops(batch, seq, dims),
                               attn_bytes(batch, seq, dims), ctx.device_kind)
        least += s
        bounds.add(bound)
    spent = sum(s for _, s in calls)
    ctx.notes.append(
        f"attention roofline: {len(calls)} calls, least {least * 1e3:.3f} ms "
        f"of {spent * 1e3:.3f}; bound by {sorted(bounds)}")
    return 100.0 * least / spent


def _named_ragged(op: device_scopes.Op) -> bool:
    """Whether the operation's own name (its HLO text up to `` = ``) is a
    grouped product's or its helper's."""
    return RAGGED_DOT in op.record.name.split(" = ")[0]


def _is_product(op: device_scopes.Op) -> bool:
    return _named_ragged(op) and "metadata" not in op.record.name.split(
        " = ")[0]


@functools.lru_cache(maxsize=2)
def _expert_seconds(window: Interval, scopes: FrozenSet[str]
                    ) -> Tuple[float, float, float]:
    """Seconds of the window's leaf operations: all of them, the dropless
    loop's (under the ``experts`` scope by their ``tf_op``: the rows' tokens,
    gather, weighing, scatter-add; or a grouped product or its helper by
    name, which the compiler strips of their scope), and of those the
    grouped products alone. Walked once a run."""
    total = loop = products = 0.0
    for op in device_scopes._run_leaves(window) or ():
        total += op.seconds
        ragged = _named_ragged(op)
        if ragged or EXPERTS_SCOPE in device_scopes.scope_path(
                op.record.tf_op, scopes):
            loop += op.seconds
            if ragged and _is_product(op):
                products += op.seconds
    return total, loop, products


def expert_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the dropless loop's operations (``_expert_seconds``)
    over the device time of all the window's leaves."""
    if _dims(ctx) is None:
        return None
    total, spent, products = _expert_seconds(tuple(ctx.trace.window),
                                             program_scopes())
    if not spent or not total:
        return None
    ctx.notes.append(
        f"dropless loop: {spent * 1e3:.3f} ms of {total * 1e3:.3f}, of "
        f"which the grouped products {products * 1e3:.3f}")
    return 100.0 * spent / total


def expert_matmul_roofline_pct(ctx: Context, p: Dict[str, Any]
                               ) -> Optional[float]:
    """The least time for the window's grouped products, at the pairs the
    router sent and the experts' weights read once a step
    (``expert_reads``), over the device time of the ``ragged-dot`` calls."""
    dims = _dims(ctx)
    if dims is None:
        return None
    spent = _expert_seconds(tuple(ctx.trace.window), program_scopes())[2]
    routed = _route_spans(ctx)
    if not spent or not routed:
        return None
    pairs = sum(r["held"] for r in routed)
    layer_calls = sum(r["layers"] for r in routed)
    steps = sum(r["steps"] for r in routed)
    reads = sum(expert_reads(r["layers"], r["steps"], r["experts"])
                for r in routed)
    least, bound = min_seconds(expert_matmul_flops(pairs, dims),
                               expert_matmul_bytes(pairs, reads, dims),
                               ctx.device_kind)
    ctx.notes.append(
        f"grouped product roofline: {pairs:.0f} pairs in {steps:.0f} steps "
        f"of {layer_calls:.0f} layer calls ({pairs / steps:.0f} rows a "
        f"step, {reads / steps:.2f} experts read a step), least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f}; bound by {bound}")
    return 100.0 * least / spent
