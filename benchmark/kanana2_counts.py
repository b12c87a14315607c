"""Operations and bytes of Kanana-2's training step from shapes alone, and the
readers of its cell's per-layer metrics.

The counts are what the *algorithm* needs, no recomputation (``remat``)
counted. A forward's model FLOPs a token are its matmuls (latent attention's
four projections, the dense FFN or the router, the shared experts and the
routed pairs a token really sent to the held experts, the head) and its
causal attention at q/k heads of ``nope_dim + rope_dim`` and v heads of
``v_dim``; a step is ``flops.TRAIN_PASSES`` forwards. The flash kernel's
three calls are counted as ``flops.py`` counts them (the products each call
cannot do without, given what it is handed), each product at its own width.
The grouped products are counted at the pairs the router sent: three a pair
forward and six backward (``d hidden``, two for ``d x``, three weight
gradients), against the held experts' weights read once forward, once
backward and once transposed, and their float32 gradient written once, a
layer.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no step program, no call of
that name, or dims without ``kv_rank`` and ``shared_width`` (another
architecture's cell). The trace helpers are ``device_scopes``',
``lfm2_counts``', ``longcat_counts``' and ``sala_counts``', imported, not
copied; the experts' load ratio is ``longcat_counts``' own reader, named by
the metric's file.
"""

from __future__ import annotations

import functools
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import (device_scopes, lfm2_counts, longcat_counts, peaks,
                       program_spans)
from benchmark.flops import MATMUL, TRAIN_PASSES
from benchmark.longcat_counts import causal_pairs
from benchmark.reducers import Context
from benchmark.sala_counts import min_seconds
from benchmark.trace_reduce import NS, Interval

# the products each of the kernel's calls cannot do without, as (q/k-wide,
# v-wide): forward QK^T | PV; dq rebuilds S and dP and makes dQ; dk/dv
# rebuilds the same two and makes dV and dK
FLASH_PRODUCTS = {"flash_fwd": (1, 1), "flash_dq": (2, 1), "flash_dkv": (2, 2)}
# the [L, heads, width] arrays a call reads and writes, as (q/k-wide, v-wide)
FLASH_ARRAYS = {"flash_fwd": (2, 2),      # q k | v o
                "flash_dq": (3, 3),       # q k dq | v o do
                "flash_dkv": (3, 4)}      # q k dk | v o do dv
EXPERT_PRODUCTS = {"forward": 3, "backward": 6}     # a routed pair


# -- counts ------------------------------------------------------------------


def is_moe(i: int, dims: Dict[str, Any]) -> bool:
    return dims["layer_ids"][i] >= dims["first_k_dense"]


def moe_layers(dims: Dict[str, Any]) -> int:
    return sum(is_moe(i, dims) for i in range(dims["n_layers"]))


def mla_params(dims: Dict[str, Any]) -> int:
    """Latent attention's matmul weights, no q bottleneck: ``W_q``,
    ``W_kva``, ``W_kvb``, ``W_o``."""
    d, h = dims["d_model"], dims["n_heads"]
    qk = dims["nope_dim"] + dims["rope_dim"]
    return (d * h * qk + d * (dims["kv_rank"] + dims["rope_dim"])
            + dims["kv_rank"] * h * (dims["nope_dim"] + dims["v_dim"])
            + h * dims["v_dim"] * d)


def expert_params(dims: Dict[str, Any]) -> int:
    """One routed expert's weights (SwiGLU: gate, up, down)."""
    return 3 * dims["d_model"] * dims["expert_width"]


def expected_pairs_per_token(dims: Dict[str, Any]) -> float:
    """Routed pairs a token sends to the held experts of one layer if the
    router spreads its ``top_k`` choices evenly."""
    return dims["top_k"] * dims["held"][1] / dims["n_routed"]


def attn_flops_per_token(seq: int, dims: Dict[str, Any]) -> float:
    """One layer's causal attention, a token of a sequence of ``seq``:
    QK^T at ``nope_dim + rope_dim`` and PV at ``v_dim``, every head."""
    widths = dims["nope_dim"] + dims["rope_dim"] + dims["v_dim"]
    return MATMUL * widths * causal_pairs(seq) * dims["n_heads"] / seq


def layer_flops_per_token(i: int, seq: int, dims: Dict[str, Any],
                          pairs_per_token: Optional[float] = None) -> float:
    """Layer ``i``'s forward FLOPs a token: MLA's projections and scores,
    then the dense FFN, or the router, the shared experts and the routed
    pairs a token sends to the held experts (``expected_pairs_per_token``
    where none is given)."""
    d = dims["d_model"]
    mixer = MATMUL * mla_params(dims) + attn_flops_per_token(seq, dims)
    if not is_moe(i, dims):
        return mixer + MATMUL * 3 * d * dims["d_ff"]
    if pairs_per_token is None:
        pairs_per_token = expected_pairs_per_token(dims)
    return mixer + MATMUL * (d * dims["n_routed"]
                             + 3 * d * dims["shared_width"]
                             + pairs_per_token * expert_params(dims))


def forward_flops_per_token(seq: int, dims: Dict[str, Any],
                            pairs_per_token: Optional[float] = None) -> float:
    """Every layer and the untied head over the (sliced) vocabulary."""
    return (sum(layer_flops_per_token(i, seq, dims, pairs_per_token)
                for i in range(dims["n_layers"]))
            + MATMUL * dims["d_model"] * dims["vocab_size"])


def train_flops_per_token(seq: int, dims: Dict[str, Any],
                          pairs_per_token: Optional[float] = None) -> float:
    """Forward and a backward of twice its cost, no recomputation."""
    return TRAIN_PASSES * forward_flops_per_token(seq, dims, pairs_per_token)


def flash_call_flops(call: str, batch: int, seq: int, dims: Dict[str, Any]
                     ) -> float:
    wide, narrow = FLASH_PRODUCTS[call]
    width = (wide * (dims["nope_dim"] + dims["rope_dim"])
             + narrow * dims["v_dim"])
    return MATMUL * width * causal_pairs(seq) * dims["n_heads"] * batch


def flash_call_bytes(call: str, batch: int, seq: int, dims: Dict[str, Any],
                     itemsize: int = 2) -> float:
    """Each array a call reads or writes, once, and the float32 row
    statistics (lse; delta too in the backward)."""
    wide, narrow = FLASH_ARRAYS[call]
    width = (wide * (dims["nope_dim"] + dims["rope_dim"])
             + narrow * dims["v_dim"])
    rows = (1 if call == "flash_fwd" else 2) * 4
    return batch * seq * dims["n_heads"] * (width * itemsize + rows)


def expert_step_flops(pairs: float, dims: Dict[str, Any]) -> float:
    """The grouped products of ``pairs`` routed pairs, forward and backward:
    nine products of ``d_model x expert_width`` a pair."""
    return (MATMUL * sum(EXPERT_PRODUCTS.values()) * pairs
            * dims["d_model"] * dims["expert_width"])


def expert_step_bytes(pairs: float, layer_calls: float, dims: Dict[str, Any],
                      itemsize: int = 2) -> float:
    """A layer's held experts' weights read forward, backward and transposed,
    their float32 gradient written, and each pair's rows in and out of the
    nine products (``d_model`` wide on one side, ``expert_width`` on the
    other)."""
    held = dims["held"][1] * expert_params(dims)
    rows = sum(EXPERT_PRODUCTS.values()) * (dims["d_model"]
                                            + dims["expert_width"])
    return (layer_calls * held * (3 * itemsize + 4)
            + pairs * rows * itemsize)


# -- the window's steps ---------------------------------------------------------


def _dims(ctx: Context) -> Optional[Dict[str, Any]]:
    dims = ctx.counters.get("dims", {})
    if (ctx.trace is None or not ctx.trace.devices
            or "kv_rank" not in dims or "shared_width" not in dims):
        return None
    return dims


def _steps(ctx: Context, p: Dict[str, Any]):
    """The whole executions of the step program inside the window."""
    return ctx.trace.first.executions(p.get("program", "jit_step"),
                                      ctx.trace.window)


def _head(op: device_scopes.Op) -> str:
    """An operation's own name: its HLO text up to `` = ``."""
    return op.record.name.split(" = ")[0].lstrip("%")


@functools.lru_cache(maxsize=2)
def _inside(window: Interval, lo: int, hi: int
            ) -> Tuple[device_scopes.Op, ...]:
    """The window's leaf operations that lie inside ``[lo, hi]``."""
    return tuple(op for op in device_scopes._run_leaves(window) or ()
                 if op.start >= lo and op.end <= hi)


def _step_ops(ctx: Context, p: Dict[str, Any]):
    """``(leaf operations inside the window's whole steps, steps)``."""
    runs = _steps(ctx, p)
    if not runs:
        return (), 0
    return _inside(tuple(ctx.trace.window), runs[0].start,
                   runs[-1].end), len(runs)


def _kernel_calls(ops, call: str) -> List[device_scopes.Op]:
    named = re.compile(rf"(^|_){call}(_|\.|$)")
    return [op for op in ops
            if program_spans.KERNEL_CATEGORY in op.record.name
            and named.search(_head(op))]


def _held_pairs_per_step(ctx: Context, dims: Dict[str, Any]
                         ) -> Tuple[float, str]:
    """Routed pairs a step sent to the held experts of all its mixture
    layers: the mean over the window's ``moe.route`` spans (one a step, fed
    from the step's own ``moe_load``), else the even spread's."""
    held = [r["held"] for r in longcat_counts._route_spans(ctx)]
    if held:
        return statistics.fmean(held), f"{len(held)} moe.route spans"
    tokens = ctx.counters.get("tokens_per_step", 0)
    return (tokens * expected_pairs_per_token(dims) * moe_layers(dims),
            "the even spread (no moe.route span in the window)")


# -- readers -------------------------------------------------------------------


def step_mfu_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of a step (``train_flops_per_token`` at the pairs the
    router really sent, x the step's tokens) over the device busy time of
    one execution of the step x the chip's bf16 peak; the median over the
    window's steps."""
    dims = _dims(ctx)
    runs = _steps(ctx, p) if dims else []
    tokens = ctx.counters.get("tokens_per_step")
    if not runs or not tokens:
        return None
    dev = ctx.trace.first
    busy = statistics.median(dev.busy_inside(r) * NS for r in runs)
    pairs, source = _held_pairs_per_step(ctx, dims)
    per_token = train_flops_per_token(
        ctx.counters["seq_len"], dims,
        pairs / tokens / max(1, moe_layers(dims)))
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"step mfu: {per_token / 1e9:.3f} model GFLOP a token x {tokens} "
        f"tokens ({pairs:.0f} held pairs a step by {source}) in "
        f"{busy * 1e3:.3f} ms busy")
    return 100.0 * per_token * tokens / (busy * peak)


def inner_scope_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the leaves under ``p["scope"]`` inside
    ``p["inside"]`` (``device_scopes.INNER``: ``core`` in ``attn``) over
    the device time of all the window's leaves."""
    if _dims(ctx) is None:
        return None
    rows = device_scopes._run_rows(tuple(ctx.trace.window),
                                   lfm2_counts.program_scopes())
    total = sum(row.seconds for key, row in rows.items() if len(key) == 1)
    mine = rows.get((p["inside"], p["scope"]))
    return 100.0 * mine.seconds / total if mine and total else None


def _flash_roofline(ctx: Context, p: Dict[str, Any], calls: Tuple[str, ...],
                    label: str) -> Optional[float]:
    dims = _dims(ctx)
    ops, steps = _step_ops(ctx, p) if dims else ((), 0)
    if not steps:
        return None
    batch = max(1, ctx.counters["sequences_per_step"]
                // ctx.counters.get("devices", 1))
    seq = ctx.counters["seq_len"]
    least = spent = 0.0
    counted, bounds = {}, {}
    for call in calls:
        mine = _kernel_calls(ops, call)
        if not mine:
            return None
        s, bounds[call] = min_seconds(
            flash_call_flops(call, batch, seq, dims),
            flash_call_bytes(call, batch, seq, dims), ctx.device_kind)
        least += len(mine) * s
        spent += sum(op.seconds for op in mine)
        counted[call] = len(mine) / steps
    ctx.notes.append(
        f"latent attention {label} roofline: calls a step {counted}, least "
        f"{least / steps * 1e3:.3f} ms of {spent / steps * 1e3:.3f} a step; "
        f"bound by {bounds}")
    return 100.0 * least / spent


def mla_fwd_roofline_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The least time of the window's ``flash_fwd`` calls (q and k heads of
    192, v heads of 128; twice a layer under ``remat``: what runs is
    counted) over their device time."""
    return _flash_roofline(ctx, p, ("flash_fwd",), "forward")


def mla_bwd_roofline_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The least time of the window's ``flash_dq`` and ``flash_dkv`` calls
    at the two head widths over their device time."""
    return _flash_roofline(ctx, p, ("flash_dq", "flash_dkv"), "backward")


def expert_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the dropless loop's operations, forward and backward
    (``lfm2_counts._expert_seconds``: the leaves under the ``experts``
    scope, and the grouped products by name), over the device time of all
    the window's leaves."""
    if _dims(ctx) is None:
        return None
    total, spent, products = lfm2_counts._expert_seconds(
        tuple(ctx.trace.window), lfm2_counts.program_scopes())
    if not spent or not total:
        return None
    ctx.notes.append(
        f"dropless loop, forward and backward: {spent * 1e3:.3f} ms of "
        f"{total * 1e3:.3f}, of which the grouped products "
        f"{products * 1e3:.3f}")
    return 100.0 * spent / total


def expert_matmul_roofline_pct(ctx: Context, p: Dict[str, Any]
                               ) -> Optional[float]:
    """The least time of a step's grouped products, forward and backward, at
    the pairs the router sent (``expert_step_flops`` against
    ``expert_step_bytes``) over the device time of the step's ``ragged-dot``
    calls."""
    dims = _dims(ctx)
    ops, steps = _step_ops(ctx, p) if dims else ((), 0)
    spent = sum(op.seconds for op in ops if lfm2_counts._is_product(op))
    if not steps or not spent:
        return None
    pairs, source = _held_pairs_per_step(ctx, dims)
    least, bound = min_seconds(
        expert_step_flops(pairs, dims),
        expert_step_bytes(pairs, moe_layers(dims), dims), ctx.device_kind)
    ctx.notes.append(
        f"grouped product roofline: {pairs:.0f} held pairs a step by "
        f"{source}, least {least * 1e3:.3f} ms of "
        f"{spent / steps * 1e3:.3f} a step; bound by {bound}")
    return 100.0 * least * steps / spent
