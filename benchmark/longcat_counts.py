"""Operations and bytes of LongCat-Flash's layer from shapes alone, and the
readers of its cell's per-layer metrics.

The counts are what the *algorithm* needs. A forward's model FLOPs are its
matmuls at the padded tokens it ran (two MLA blocks' projections, two FFNs
and the router a layer, the held experts at the *expected* ``top_k x held /
outputs`` pairs a token, the head at the last positions) and its causal
attention at q/k heads of ``nope_dim + rope_dim`` and v heads of ``v_dim``.
The grouped product is counted at the pairs the program's router really
sent to the held experts (its ``moe.route`` spans carry the counters'
increments), with the held experts' weights read once a layer call.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no call of that name (the
parent commit's program, another architecture's cell), or dims without
``held``. A forward's shape is read off its own ``flash_fwd`` calls'
results, so the counts follow the lengths the window's calls really had.
The trace helpers are ``sala_counts``'s, imported, not copied.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark import peaks, program_spans
from benchmark.flops import MATMUL
from benchmark.reducers import Context
from benchmark.sala_counts import _device_ops, min_seconds, window_calls
from benchmark.trace_reduce import NS, merge

FLASH_CALL = "flash_fwd"
RAGGED_DOT = "ragged-dot"      # what the TPU compiler names lax.ragged_dot
ROUTE_SPAN = "moe.route"       # ray_tpu.parallel.expert._record


# -- counts ------------------------------------------------------------------


def mla_params(dims: Dict[str, Any]) -> int:
    """One latent-attention block's matmul weights."""
    d, h = dims["d_model"], dims["n_heads"]
    qk = dims["nope_dim"] + dims["rope_dim"]
    return (d * dims["q_rank"] + dims["q_rank"] * h * qk
            + d * (dims["kv_rank"] + dims["rope_dim"])
            + dims["kv_rank"] * h * (dims["nope_dim"] + dims["v_dim"])
            + h * dims["v_dim"] * d)


def expert_params(dims: Dict[str, Any]) -> int:
    """One expert's weights (SwiGLU: gate, up, down)."""
    return 3 * dims["d_model"] * dims["expert_width"]


def expected_pairs_per_token(dims: Dict[str, Any]) -> float:
    """Routed pairs a token sends to the held experts if the router spreads
    its ``top_k`` choices evenly over its outputs."""
    return dims["top_k"] * dims["held"][1] / (dims["n_routed"]
                                              + dims["n_zero"])


def layer_matmul_flops_per_token(dims: Dict[str, Any]) -> float:
    """One layer's matmul FLOPs a token outside attention's scores: two MLA
    blocks, two FFNs, the router, the held experts at the expected load."""
    d = dims["d_model"]
    return MATMUL * (2 * mla_params(dims) + 2 * 3 * d * dims["d_ff"]
                     + d * (dims["n_routed"] + dims["n_zero"])
                     + expected_pairs_per_token(dims) * expert_params(dims))


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def mla_attn_flops(batch: int, seq: int, dims: Dict[str, Any]) -> float:
    """One attention call: QK^T at ``nope_dim + rope_dim`` and PV at
    ``v_dim`` over the causal pairs of every head."""
    width = dims["nope_dim"] + dims["rope_dim"] + dims["v_dim"]
    return MATMUL * width * causal_pairs(seq) * dims["n_heads"] * batch


def mla_attn_bytes(batch: int, seq: int, dims: Dict[str, Any],
                   itemsize: int = 2) -> float:
    """q and k in at their width, v in and o out at v's, once each."""
    width = 2 * (dims["nope_dim"] + dims["rope_dim"]) + 2 * dims["v_dim"]
    return batch * seq * dims["n_heads"] * width * itemsize


def forward_flops(batch: int, seq: int, dims: Dict[str, Any]) -> float:
    """Model FLOPs of one served forward of ``batch`` padded prompts of
    ``seq`` tokens: every layer at every padded token, two attention calls
    a layer, the head at the ``batch`` last positions."""
    layers = dims["n_layers"]
    return (layers * (batch * seq * layer_matmul_flops_per_token(dims)
                      + 2 * mla_attn_flops(batch, seq, dims))
            + MATMUL * batch * dims["d_model"] * dims["vocab_size"])


def expert_matmul_flops(pairs: float, dims: Dict[str, Any]) -> float:
    return MATMUL * pairs * expert_params(dims)


def expert_matmul_bytes(pairs: float, layer_calls: float,
                        dims: Dict[str, Any], itemsize: int = 2) -> float:
    """The held experts' weights once a layer call, and each routed pair's
    token in and its result out."""
    return itemsize * (layer_calls * dims["held"][1] * expert_params(dims)
                       + pairs * 2 * dims["d_model"])


# -- the window's operations ---------------------------------------------------


def _window_ops(ctx: Context):
    """The first device's operations inside the window and the model's
    sizes; ``None`` where the trace has no device plane or the cell no such
    layer."""
    dims = ctx.counters.get("dims", {})
    if ctx.trace is None or not ctx.trace.devices or "held" not in dims:
        return None
    path = program_spans.find_trace(tuple(ctx.trace.window))
    if path is None:
        return None
    lo, hi = ctx.trace.window
    ops = [op for op in _device_ops(path) if op[3] >= lo and op[4] <= hi]
    return (ops, dims) if ops else None


def _busy_s(ctx: Context) -> float:
    return ctx.trace.first.busy_ns(ctx.trace.window) * NS


def _flash_calls(ctx: Context, ops) -> List[Tuple[Tuple[int, ...], float]]:
    return window_calls(ops, ctx.trace.window, [FLASH_CALL]).get(
        FLASH_CALL, [])


def _route_spans(ctx: Context) -> List[Dict[str, float]]:
    """The attributes of the program's ``moe.route`` spans inside the
    window: one a forward, the counters' increments for its layers."""
    spans = program_spans.inside(
        program_spans.named(program_spans.program_spans(ctx), ROUTE_SPAN),
        tuple(ctx.trace.window))
    keys = ("held", "absent", "zero", "load_max", "layers", "experts")
    return [{k: float(s.attrs[k]) for k in keys} for s in spans
            if all(k in s.attrs for k in keys)]


def expert_ops(ops, dims: Dict[str, Any], pair_counts) -> List[Tuple[int,
                                                                     int]]:
    """The intervals of the mixture's operations, router to combine. Scopes
    do not reach the device plane, so they are told by what they are: the
    grouped products by name, the sorts (the router's ``top_k``), every
    operation inside a loop nested in the layers' loop (the dropless steps:
    the rows' tokens, gather, products, weighing, scatter-add), and every
    other operation whose result has the router's width, the experts a
    token as its last dimension, the experts held among its last two, or
    the number of pairs of a call among its dimensions."""
    loops = [(s, e) for _, category, _, s, e in ops if category == "while"]
    inner = [(s, e) for s, e in loops
             if any(a <= s and e <= b and (a, b) != (s, e) for a, b in loops)]
    outputs, top_k = dims["n_routed"] + dims["n_zero"], dims["top_k"]
    count = dims["held"][1]
    mine = []
    for name, category, shape, start, end in ops:
        if category in ("while", "conditional", "call") or end <= start:
            continue
        if (RAGGED_DOT in name or category == "sort"
                or outputs in shape or (shape and shape[-1] == top_k)
                or count in shape[-2:]
                or any(n in pair_counts for n in shape)
                or any(a <= start and end <= b for a, b in inner)):
            mine.append((start, end))
    return merge(mine)


# -- readers -------------------------------------------------------------------


def fwd_mfu_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of the forwards the window ran over its device busy time
    x the chip's bf16 peak. A forward of [batch, seq] shows as ``2 x
    layers`` ``flash_fwd`` calls whose results are [batch x heads, seq,
    v_dim]."""
    got = _window_ops(ctx)
    if not got:
        return None
    ops, dims = got
    calls = _flash_calls(ctx, ops)
    busy = _busy_s(ctx)
    if not calls or not busy:
        return None
    per_forward = 2 * dims["n_layers"]
    flops = sum(forward_flops(rows // dims["n_heads"], seq, dims)
                for (rows, seq, _), _ in calls) / per_forward
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"forward mfu: {len(calls) / per_forward:.1f} forwards, "
        f"{flops / 1e12:.3f} model TFLOP in {busy:.3f} s busy")
    return 100.0 * flops / (busy * peak)


def mla_attn_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the ``flash_fwd`` calls over the window's busy time."""
    got = _window_ops(ctx)
    calls = _flash_calls(ctx, got[0]) if got else []
    busy = _busy_s(ctx) if calls else 0.0
    return 100.0 * sum(s for _, s in calls) / busy if busy else None


def mla_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                          ) -> Optional[float]:
    """The least time for the window's ``flash_fwd`` calls over their device
    time."""
    got = _window_ops(ctx)
    calls = _flash_calls(ctx, got[0]) if got else []
    if not calls:
        return None
    dims = got[1]
    least, bounds = 0.0, set()
    for (rows, seq, _), _ in calls:
        batch = rows // dims["n_heads"]
        s, bound = min_seconds(mla_attn_flops(batch, seq, dims),
                               mla_attn_bytes(batch, seq, dims),
                               ctx.device_kind)
        least += s
        bounds.add(bound)
    spent = sum(s for _, s in calls)
    ctx.notes.append(
        f"latent attention roofline: {len(calls)} calls, least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f}; bound by "
        f"{sorted(bounds)}")
    return 100.0 * least / spent


def expert_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the mixture's operations (``expert_ops``) over the
    window's busy time."""
    got = _window_ops(ctx)
    if not got:
        return None
    ops, dims = got
    deploy = ctx.cell.deploy.get("deployment", {})
    pair_counts = {dims["top_k"] * b * length
                   for b in deploy.get("pad_batch_to", ())
                   for length in deploy.get("length_buckets", ())}
    mine = expert_ops(ops, dims, pair_counts)
    busy = _busy_s(ctx)
    if not mine or not busy:
        return None
    spent = sum(e - s for s, e in mine) * NS
    products = sum((e - s) * NS for name, _, _, s, e in ops
                   if RAGGED_DOT in name)
    ctx.notes.append(
        f"mixture: {spent * 1e3:.3f} ms of {busy * 1e3:.3f} busy, of which "
        f"the grouped products {products * 1e3:.3f}")
    return 100.0 * spent / busy


def expert_matmul_roofline_pct(ctx: Context, p: Dict[str, Any]
                               ) -> Optional[float]:
    """The least time for the window's grouped products, at the pairs the
    router sent to the held experts, over the device time of the
    ``ragged-dot`` calls."""
    got = _window_ops(ctx)
    if not got:
        return None
    ops, dims = got
    spent = sum((end - start) * NS for name, _, _, start, end in ops
                if RAGGED_DOT in name and "metadata" not in name)
    routed = _route_spans(ctx)
    if not spent or not routed:
        return None
    pairs = sum(r["held"] for r in routed)
    layer_calls = sum(r["layers"] for r in routed)
    least, bound = min_seconds(
        expert_matmul_flops(pairs, dims),
        expert_matmul_bytes(pairs, layer_calls, dims), ctx.device_kind)
    ctx.notes.append(
        f"grouped product roofline: {pairs:.0f} pairs in {layer_calls:.0f} "
        f"layer calls, least {least * 1e3:.3f} ms of {spent * 1e3:.3f}; "
        f"bound by {bound}")
    return 100.0 * least / spent


def expert_load_max_over_mean(ctx: Context, p: Dict[str, Any]
                              ) -> Optional[float]:
    """Over the window's layer calls, the most-loaded held expert's pairs
    over the mean held expert's."""
    if ctx.trace is None:
        return None
    routed = _route_spans(ctx)
    held = sum(r["held"] for r in routed)
    if not held:
        return None
    return sum(r["load_max"] * r["experts"] for r in routed) / held
