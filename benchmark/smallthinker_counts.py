"""Operations and bytes of SmallThinker's layers from shapes alone, and the
readers of its generating cell's per-layer metrics.

The counts are what the *algorithm* needs, whatever implements it. A token's
model FLOPs are its matmuls (the attention's four projections, the router,
``top_k`` experts' three products, the head where a position is read) and its
attention's scores over the keys it attends: in prefill the causal triangle
of a global layer and the *band* of a window layer (``band_pairs``), in
decode the rows the slot holds (``live_rows`` of the step's span: a window
layer ``min(length, window)``, a global layer ``length``). A decode step's
bytes are every weight it must read once (the layers and the head; of the
embedding it reads a row a slot) and, of the two caches, **the rows the slots
hold**, not the rows the masked product reads: the share reads low while the
program reads every allocated row, and no later kernel can push it past 100.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no execution of that name,
no ``swa`` scope, no ``live_rows`` on a step's span (a program from before
this configuration), or dims without ``window``. The trace helpers are
``lfm2_counts``', ``granite_counts``', ``sala_counts``' and
``device_scopes``', imported, not copied.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Any, Dict, List, Optional

from benchmark import (device_scopes, granite_counts, lfm2_counts,
                       longcat_counts, peaks)
from benchmark.flops import MATMUL
from benchmark.longcat_counts import FLASH_CALL, causal_pairs
from benchmark.reducers import Context
from benchmark.sala_counts import RESULT, min_seconds
from benchmark.trace_reduce import NS

WINDOW, GLOBAL = "window", "global"          # ``layer_types``' values
SWA_SCOPE = "swa"                            # inside ``attn``
DECODE = granite_counts.DECODE
STEP_SPAN = granite_counts.STEP_SPAN
STREAM_CALL = "experts_stream"               # ``ops.expert_stream``'s kernel
ACT_BYTES = 2                                # bfloat16 weights and caches


# -- counts ------------------------------------------------------------------


def attention_params(dims: Dict[str, Any]) -> int:
    """q and o at ``n_heads``, k and v at ``n_kv_heads``, heads of
    ``head_dim`` (not ``d_model / n_heads``)."""
    return 2 * dims["d_model"] * dims["head_dim"] * (
        dims["n_heads"] + dims["n_kv_heads"])


def expert_params(dims: Dict[str, Any]) -> int:
    """One expert's weights (ReGLU: gate, up, down)."""
    return 3 * dims["d_model"] * dims["expert_width"]


def layer_params(dims: Dict[str, Any]) -> int:
    """A layer of either kind: attention, router, every expert, two norms."""
    d = dims["d_model"]
    return (attention_params(dims) + d * dims["n_experts"]
            + dims["n_experts"] * expert_params(dims) + 2 * d)


def layers(dims: Dict[str, Any]):
    """``(window layers, global layers)``."""
    types = dims["layer_types"]
    return types.count(WINDOW), types.count(GLOBAL)


def param_count(dims: Dict[str, Any]) -> int:
    """Every parameter: the layers, the embedding, the untied head, the
    final norm."""
    d = dims["d_model"]
    return (dims["n_layers"] * layer_params(dims)
            + 2 * dims["vocab_size"] * d + d)


def row_bytes(dims: Dict[str, Any]) -> int:
    """One position of one layer in a cache: k and v at the K/V heads."""
    return 2 * dims["n_kv_heads"] * dims["head_dim"] * ACT_BYTES


def slot_rows(cache_len: int, dims: Dict[str, Any]) -> int:
    """The rows one slot is allocated over the layers: a window layer a ring
    of ``window`` rows, a global layer ``cache_len``."""
    n_window, n_global = layers(dims)
    return n_window * min(dims["window"], cache_len) + n_global * cache_len


def slot_bytes(cache_len: int, dims: Dict[str, Any]) -> int:
    return slot_rows(cache_len, dims) * row_bytes(dims)


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs of ``length`` positions through a window that
    counts the token itself: position ``t`` attends ``min(t + 1, window)``
    keys."""
    full = min(length, window)
    return full * (full + 1) // 2 + max(0, length - window) * window


def attn_flops(pairs: float, dims: Dict[str, Any]) -> float:
    """QK^T and PV at ``head_dim`` over ``pairs`` (query, key) pairs of every
    query head."""
    return MATMUL * 2 * dims["head_dim"] * pairs * dims["n_heads"]


def attn_bytes(batch: int, length: int, dims: Dict[str, Any]) -> float:
    """q in and o out at the query heads, k and v in at the K/V heads, once
    each."""
    return (2 * batch * length * (dims["n_heads"] + dims["n_kv_heads"])
            * dims["head_dim"] * ACT_BYTES)


def token_flops(dims: Dict[str, Any]) -> float:
    """A token's FLOPs in one layer outside its attention's scores: the four
    projections, the router, ``top_k`` experts."""
    return MATMUL * (attention_params(dims)
                     + dims["d_model"] * dims["n_experts"]
                     + dims["top_k"] * expert_params(dims))


def head_flops(positions: int, dims: Dict[str, Any]) -> float:
    return MATMUL * positions * dims["d_model"] * dims["vocab_size"]


def prefill_flops(batch: int, length: int, dims: Dict[str, Any]) -> float:
    """One prefill of ``batch`` padded prompts of ``length``: every layer at
    every padded position, the band of a window layer and the triangle of a
    global one, the head at the ``batch`` last positions."""
    n_window, n_global = layers(dims)
    pairs = (n_window * band_pairs(length, dims["window"])
             + n_global * causal_pairs(length))
    return (batch * length * dims["n_layers"] * token_flops(dims)
            + batch * attn_flops(pairs, dims) + head_flops(batch, dims))


def decode_step_flops(slots: int, live_rows: float, dims: Dict[str, Any]
                      ) -> float:
    """One decode step over ``slots`` slots, empty ones too (the program has
    one shape), and the scores over the ``live_rows`` rows the occupied
    slots hold."""
    return (slots * dims["n_layers"] * token_flops(dims)
            + attn_flops(live_rows, dims) + head_flops(slots, dims))


def step_weight_bytes(dims: Dict[str, Any]) -> int:
    """The weights a decode step must read: every layer and the head with
    the final norm (of the embedding it reads one row a slot)."""
    d = dims["d_model"]
    return ACT_BYTES * (dims["n_layers"] * layer_params(dims)
                        + dims["vocab_size"] * d + d)


def decode_step_bytes(live_rows: float, dims: Dict[str, Any]) -> float:
    """What a step must move whatever implements it: every weight once and
    the K and V rows the slots hold."""
    return step_weight_bytes(dims) + live_rows * row_bytes(dims)


# -- the window's operations ---------------------------------------------------


def _dims(ctx: Context) -> Optional[Dict[str, Any]]:
    dims = ctx.counters.get("dims", {})
    if (ctx.trace is None or not ctx.trace.devices
            or "window" not in dims or "layer_types" not in dims):
        return None
    return dims


def _runs(ctx: Context, program: str):
    return ctx.trace.first.executions(program, ctx.trace.window)


def _busy_ms(ctx: Context, runs) -> List[float]:
    return [lfm2_counts._busy_inside(ctx, r.start, r.end) * NS * 1e3
            for r in runs]


def _live_rows(ctx: Context) -> List[float]:
    """``live_rows`` of the window's ``serve.generate.step`` spans."""
    return [float(s.attrs["live_rows"])
            for s in granite_counts._spans(ctx, STEP_SPAN)
            if "live_rows" in s.attrs]


def _inside(op, starts: List[int], runs) -> bool:
    """Whether the operation ran inside one of ``runs`` (sorted by start)."""
    i = bisect.bisect_right(starts, op.start) - 1
    return i >= 0 and op.end <= runs[i].end


# -- readers -------------------------------------------------------------------


def decode_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device busy time inside the window's ``jit_decode_step`` executions
    over the window's device busy time."""
    if _dims(ctx) is None:
        return None
    runs = _runs(ctx, DECODE)
    busy = lfm2_counts._busy_s(ctx) if runs else 0.0
    if not busy:
        return None
    return 100.0 * sum(_busy_ms(ctx, runs)) / 1e3 / busy


def window_mfu_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of every token the window ran (each prefill at its padded
    length, read off its ``flash_fwd`` calls, one a layer; each decode step
    at every slot, empty ones too, its scores over the rows its span says
    the slots hold) over the window's device busy time x the chip's bf16
    peak."""
    dims = _dims(ctx)
    if dims is None:
        return None
    busy = lfm2_counts._busy_s(ctx)
    calls = lfm2_counts._flash_calls(ctx)
    steps, live = len(_runs(ctx, DECODE)), _live_rows(ctx)
    slots = int(ctx.counters.get("slots", 0))
    if not busy or not (calls or steps) or not live:
        return None
    per_prefill = dims["n_layers"]
    shapes = [(rows // dims["n_heads"], seq) for (rows, seq, _), _ in calls]
    prefill = sum(prefill_flops(b, seq, dims)
                  for b, seq in shapes) / per_prefill
    decode = steps * decode_step_flops(slots, statistics.fmean(live), dims)
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"window mfu: {len(calls) / per_prefill:.1f} prefills of "
        f"{sum(b * seq for b, seq in shapes) / per_prefill:.0f} padded "
        f"tokens ({prefill / 1e12:.3f} model TFLOP) and {steps} steps of "
        f"{slots} slots over {statistics.fmean(live):.0f} live rows "
        f"({decode / 1e12:.3f}) in {busy:.3f} s busy; "
        f"{ctx.counters.get('prompt_tokens')} prompt and "
        f"{ctx.counters.get('new_tokens')} generated tokens were answered")
    return 100.0 * (prefill + decode) / (busy * peak)


def decode_hbm_roofline_pct(ctx: Context, p: Dict[str, Any]
                            ) -> Optional[float]:
    """The bytes a decode step must move (``decode_step_bytes`` at the mean
    ``live_rows`` of the window's steps) over the chip's bandwidth, over a
    step's device time (the mean over the window's ``jit_decode_step``
    executions)."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    live = _live_rows(ctx) if runs else []
    if not live:
        return None
    spent = statistics.fmean(_busy_ms(ctx, runs)) / 1e3
    rows = statistics.fmean(live)
    nbytes = decode_step_bytes(rows, dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    ctx.notes.append(
        f"decode step roofline: {nbytes / 1e9:.3f} GB a step must move "
        f"({step_weight_bytes(dims) / 1e9:.3f} of weights, "
        f"{rows * row_bytes(dims) / 1e9:.3f} of the {rows:.0f} rows the "
        f"slots hold), least {least * 1e3:.3f} ms of {spent * 1e3:.3f} over "
        f"{len(runs)} steps")
    return 100.0 * least / spent if spent else None


def cache_live_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The mean ``live_rows`` of the window's steps over the rows allocated
    (``slots x slot_rows``)."""
    dims = ctx.counters.get("dims", {})
    live = (_live_rows(ctx)
            if ctx.trace is not None and "window" in dims else [])
    slots, cache = ctx.counters.get("slots"), ctx.counters.get("cache_len")
    if not live or not slots or not cache:
        return None
    return 100.0 * statistics.fmean(live) / (
        int(slots) * slot_rows(int(cache), dims))


def swa_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                          ) -> Optional[float]:
    """The least time for the window's ``flash_fwd`` calls under ``swa`` (the
    band's FLOPs alone against the bytes of q, k, v and o, at the lengths the
    calls had) over their device time."""
    dims = _dims(ctx)
    if dims is None:
        return None
    scopes = lfm2_counts.program_scopes()
    least = spent = 0.0
    count, bounds = 0, set()
    for op in device_scopes._run_leaves(tuple(ctx.trace.window)) or ():
        shape = RESULT.search(op.record.name)
        if (FLASH_CALL not in op.record.name.split(" = ")[0] or not shape
                or SWA_SCOPE not in device_scopes.scope_path(
                    op.record.tf_op, scopes)):
            continue
        rows, seq, _ = (int(n) for n in shape.group(1).split(",") if n)
        batch = rows // dims["n_heads"]
        s, bound = min_seconds(
            batch * attn_flops(band_pairs(seq, dims["window"]), dims),
            attn_bytes(batch, seq, dims), ctx.device_kind)
        least, spent, count = least + s, spent + op.seconds, count + 1
        bounds.add(bound)
    if not spent:
        return None
    ctx.notes.append(
        f"window attention roofline: {count} flash_fwd calls under swa, "
        f"least {least * 1e3:.3f} ms of {spent * 1e3:.3f}; bound by "
        f"{sorted(bounds)}")
    return 100.0 * least / spent


def _is_mixture_product(op) -> bool:
    """Whether the operation is one of the mixture's products, whatever
    implements them: a grouped product (``ragged-dot``) or the call that
    streams every expert past the rows (``STREAM_CALL``), by its own
    name (its HLO text up to `` = ``)."""
    return (lfm2_counts._is_product(op)
            or STREAM_CALL in op.record.name.split(" = ")[0])


def expert_matmul_roofline_pct(ctx: Context, p: Dict[str, Any]
                               ) -> Optional[float]:
    """The decode steps' mixture: the least time for the pairs the steps
    routed (their ``moe.route`` spans: a step's pairs are ``slots x top_k``
    a layer) against every expert's weights read once a layer call, over
    the device time of the mixture's products (``_is_mixture_product``: the
    ``ragged-dot`` calls of a program that lists its pairs, the
    ``experts_stream`` calls of one that streams its experts) inside the
    window's ``jit_decode_step`` executions. The count is the work's and
    does not ask which of the two ran."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    slots = ctx.counters.get("slots")
    if not runs or not slots:
        return None
    starts = [r.start for r in runs]
    spent = sum(op.seconds for op in
                device_scopes._run_leaves(tuple(ctx.trace.window)) or ()
                if _is_mixture_product(op) and _inside(op, starts, runs))
    a_step = int(slots) * dims["top_k"] * dims["n_layers"]
    routed = [r for r in longcat_counts._route_spans(ctx)
              if r["held"] == a_step]
    if not spent or not routed:
        return None
    pairs = sum(r["held"] for r in routed)
    reads = sum(r["layers"] * r["experts"] for r in routed)
    least, bound = min_seconds(
        lfm2_counts.expert_matmul_flops(pairs, dims),
        lfm2_counts.expert_matmul_bytes(pairs, reads, dims), ctx.device_kind)
    ctx.notes.append(
        f"decode mixture roofline: {pairs:.0f} pairs of {len(routed)} steps "
        f"({pairs / reads:.2f} rows an expert read), least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f} inside {len(runs)} "
        f"steps; bound by {bound}")
    return 100.0 * least / spent


def nested_scope_share_pct(ctx: Context, p: Dict[str, Any]
                           ) -> Optional[float]:
    """Device time of the leaf operations whose name stack holds the scope
    ``p["scope"]`` anywhere (``swa`` and ``nope`` sit inside ``attn``) over
    the device time of all the window's leaves."""
    if _dims(ctx) is None:
        return None
    scopes = lfm2_counts.program_scopes()
    total = mine = 0.0
    for op in device_scopes._run_leaves(tuple(ctx.trace.window)) or ():
        total += op.seconds
        if p["scope"] in device_scopes.scope_path(op.record.tf_op, scopes):
            mine += op.seconds
    return 100.0 * mine / total if mine else None


def step_host_gap_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Between consecutive ``jit_decode_step`` executions of the window, the
    time in which the device ran nothing (a prefill between two steps is
    work, not a gap): the mean."""
    if _dims(ctx) is None:
        return None
    runs = _runs(ctx, DECODE)
    if len(runs) < 2:
        return None
    gaps = [max(0, (b.start - a.end)
                - lfm2_counts._busy_inside(ctx, a.end, b.start))
            for a, b in zip(runs, runs[1:])]
    return statistics.fmean(gaps) * NS * 1e3
