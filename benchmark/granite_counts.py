"""Operations and bytes of Granite 4.0-H's layers from shapes alone, and the
readers of its generating cell's per-layer metrics.

The counts are what the *algorithm* needs. A token's model FLOPs are its
matmuls (a mixer's projections, the dense FFN, the tied head where a position
is read), the convolution's taps and the scan: in prefill the chunked scan's
four products at the chunk the configuration publishes (``ssd_fwd_flops``:
``C B^T`` once a chunk, and a head's masked product, its read of the carried
state and the state's update), in decode the recurrence's two multiply-adds a
state element. Prefill's attention is its causal pairs at 64-wide heads; a
decode step's scores over its cache are left out of the model FLOPs (10 MFLOP
of a token's 6.4 GFLOP). A decode step's bytes are what it must move: every
weight once, the recurrent state read and written in float32, the
convolution's tail read and written, and the K/V cache read over all its
positions, as the program's masked product reads it.

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no execution of that name,
no ``mamba`` scope, no ``serve.generate.*`` span (a program from before the
engine), or dims without ``mamba_heads``. The trace helpers are
``lfm2_counts``', ``sala_counts``' and ``device_scopes``', imported, not
copied.
"""

from __future__ import annotations

import bisect
import functools
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import device_scopes, lfm2_counts, peaks, program_spans
from benchmark.flops import MATMUL
from benchmark.longcat_counts import causal_pairs
from benchmark.reducers import Context
from benchmark.sala_counts import RESULT, _named, min_seconds
from benchmark.trace_reduce import NS, Interval

MAMBA, ATTENTION = "mamba", "attention"      # ``layer_types``' values
SSD_CALL = "ssd_fwd"                         # ops/ssd.py KERNEL_NAME
PREFILL, DECODE = "jit_prefill", "jit_decode_step"
PREFILL_SPAN, STEP_SPAN = "serve.generate.prefill", "serve.generate.step"
STATE_BYTES, ACT_BYTES = 4, 2                # float32 state, bfloat16 the rest

# The accepted readers under this module's name (the cell's metric files name
# their readers here, as the other configurations' do).
execution_busy_ms = lfm2_counts.execution_busy_ms
scope_share_pct = lfm2_counts.scope_share_pct


# -- counts ------------------------------------------------------------------


def mamba_sizes(dims: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """``(heads, head_dim, d_state, d_inner)``."""
    h, p = dims["mamba_heads"], dims["mamba_head_dim"]
    return h, p, dims["d_state"], h * p


def mamba_params(dims: Dict[str, Any]) -> int:
    """A Mamba mixer's matmul weights: ``W_in`` d -> 2 d_inner + 2 d_state +
    heads and ``W_out`` d_inner -> d."""
    h, _, n, inner = mamba_sizes(dims)
    return dims["d_model"] * (2 * inner + 2 * n + h) + inner * dims["d_model"]


def attention_params(dims: Dict[str, Any]) -> int:
    return 2 * dims["d_model"] * dims["head_dim"] * (
        dims["n_heads"] + dims["n_kv_heads"])


def ffn_params(dims: Dict[str, Any]) -> int:
    return 3 * dims["d_model"] * dims["d_ff"]


def layers(dims: Dict[str, Any]) -> Tuple[int, int]:
    """``(Mamba layers, attention layers)``."""
    types = dims["layer_types"]
    return types.count(MAMBA), types.count(ATTENTION)


def param_count(dims: Dict[str, Any]) -> int:
    """Every parameter: the layers' matrices, the convolution's taps and
    bias, the norms and per-head scalars, the tied embedding, the final
    norm."""
    h, _, n, inner = mamba_sizes(dims)
    d = dims["d_model"]
    conv_dim = inner + 2 * n
    small = conv_dim * (dims["conv_width"] + 1) + 3 * h + inner
    n_mamba, n_attn = layers(dims)
    return (n_mamba * (mamba_params(dims) + small)
            + n_attn * attention_params(dims)
            + (n_mamba + n_attn) * (ffn_params(dims) + 2 * d)
            + dims["vocab_size"] * d + d)


def ssd_fwd_flops(batch: int, length: int, dims: Dict[str, Any]) -> float:
    """One chunked scan over ``length`` positions (whole chunks): a chunk's
    ``C B^T`` once, and for each head the masked product with ``dt x``, the
    read of the carried state and the state's update."""
    h, p, n, _ = mamba_sizes(dims)
    q = dims["chunk"]
    chunks = -(-length // q)
    per_chunk = MATMUL * q * q * n + h * MATMUL * (q * q * p + 2 * q * n * p)
    return batch * chunks * per_chunk


def ssd_fwd_bytes(batch: int, length: int, dims: Dict[str, Any]) -> float:
    """x in and y out, B and C, the steps' sums (float32, as a column and as
    a row), the state in and out."""
    h, p, n, inner = mamba_sizes(dims)
    return batch * (length * (2 * inner * ACT_BYTES + 2 * n * ACT_BYTES
                              + 2 * h * STATE_BYTES)
                    + 2 * h * p * n * STATE_BYTES)


def ssd_step_flops(slots: int, dims: Dict[str, Any]) -> float:
    """The recurrence for one token a slot: a multiply-add a state element
    for the update and one for ``S C``."""
    h, p, n, _ = mamba_sizes(dims)
    return slots * 2 * MATMUL * h * p * n


def ssd_step_bytes(slots: int, dims: Dict[str, Any]) -> float:
    """The state read and written, float32."""
    h, p, n, _ = mamba_sizes(dims)
    return slots * 2 * h * p * n * STATE_BYTES


def token_flops(dims: Dict[str, Any]) -> float:
    """A token's FLOPs in every layer outside the scan and attention's
    scores: the mixers' projections, the convolution's taps, the FFNs."""
    _, _, n, inner = mamba_sizes(dims)
    n_mamba, n_attn = layers(dims)
    conv = MATMUL * dims["conv_width"] * (inner + 2 * n)
    return (n_mamba * (MATMUL * mamba_params(dims) + conv)
            + n_attn * MATMUL * attention_params(dims)
            + (n_mamba + n_attn) * MATMUL * ffn_params(dims))


def head_flops(positions: int, dims: Dict[str, Any]) -> float:
    return MATMUL * positions * dims["d_model"] * dims["vocab_size"]


def prefill_flops(batch: int, length: int, dims: Dict[str, Any]) -> float:
    """One prefill of ``batch`` padded prompts of ``length``: every layer at
    every padded position, a scan a Mamba layer, the causal pairs of an
    attention layer, the head at the ``batch`` last positions."""
    n_mamba, n_attn = layers(dims)
    attn = (MATMUL * 2 * dims["head_dim"] * causal_pairs(length)
            * dims["n_heads"] * batch)
    return (batch * length * token_flops(dims)
            + n_mamba * ssd_fwd_flops(batch, length, dims) + n_attn * attn
            + head_flops(batch, dims))


def decode_step_flops(slots: int, dims: Dict[str, Any]) -> float:
    """One decode step over ``slots`` slots, empty ones too (the program has
    one shape): every layer, the recurrence, the head at every slot."""
    n_mamba, _ = layers(dims)
    return (slots * token_flops(dims) + n_mamba * ssd_step_flops(slots, dims)
            + head_flops(slots, dims))


def state_bytes(slots: int, cache_len: int, dims: Dict[str, Any]
                ) -> Dict[str, float]:
    """What ``slots`` sequences keep, by leaf of ``DecodeState``."""
    h, p, n, inner = mamba_sizes(dims)
    n_mamba, n_attn = layers(dims)
    return {
        "ssm": n_mamba * slots * h * p * n * STATE_BYTES,
        "conv": n_mamba * slots * (dims["conv_width"] - 1) * (inner + 2 * n)
        * ACT_BYTES,
        "kv": n_attn * slots * cache_len * 2 * dims["n_kv_heads"]
        * dims["head_dim"] * ACT_BYTES}


def decode_step_bytes(slots: int, cache_len: int, dims: Dict[str, Any]
                      ) -> float:
    """What a step must move: every weight once, the state and the tail read
    and written, the K/V cache read (over all its positions, as the masked
    product reads it; the token's own row written is nothing beside it)."""
    held = state_bytes(slots, cache_len, dims)
    return (param_count(dims) * ACT_BYTES + 2 * held["ssm"]
            + 2 * held["conv"] + held["kv"])


# -- the window's operations ---------------------------------------------------


def _dims(ctx: Context) -> Optional[Dict[str, Any]]:
    dims = ctx.counters.get("dims", {})
    if (ctx.trace is None or not ctx.trace.devices
            or "mamba_heads" not in dims):
        return None
    return dims


def _runs(ctx: Context, program: str):
    return ctx.trace.first.executions(program, ctx.trace.window)


def _busy_ms(ctx: Context, runs) -> List[float]:
    return [lfm2_counts._busy_inside(ctx, r.start, r.end) * NS * 1e3
            for r in runs]


@functools.lru_cache(maxsize=2)
def _ssd_calls_in(window: Interval) -> Tuple[Tuple[Tuple[int, ...], float],
                                             ...]:
    wanted = _named(SSD_CALL)
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


def _ssd_calls(ctx: Context):
    """The window's ``ssd_fwd`` calls as (first result's shape [batch, heads,
    padded length, head_dim], seconds)."""
    return _ssd_calls_in(tuple(ctx.trace.window))


def _spans(ctx: Context, name: str) -> List[program_spans.Span]:
    return program_spans.inside(
        program_spans.named(program_spans.program_spans(ctx), name),
        tuple(ctx.trace.window))


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
    length, read off its ``ssd_fwd`` calls; each decode step at every slot,
    empty ones too) over the window's device busy time x the chip's bf16
    peak."""
    dims = _dims(ctx)
    if dims is None:
        return None
    busy = lfm2_counts._busy_s(ctx)
    n_mamba, _ = layers(dims)
    calls = _ssd_calls(ctx)
    steps = len(_runs(ctx, DECODE))
    slots = int(ctx.counters.get("slots", 0))
    if not busy or not (calls or steps) or not n_mamba:
        return None
    prefill = sum(prefill_flops(shape[0], shape[2], dims)
                  for shape, _ in calls) / n_mamba
    decode = steps * decode_step_flops(slots, dims)
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"window mfu: {len(calls) / n_mamba:.1f} prefills of "
        f"{sum(s[0] * s[2] for s, _ in calls) / n_mamba:.0f} padded tokens "
        f"({prefill / 1e12:.3f} model TFLOP) and {steps} steps of {slots} "
        f"slots ({decode / 1e12:.3f}) in {busy:.3f} s busy; "
        f"{ctx.counters.get('prompt_tokens')} prompt and "
        f"{ctx.counters.get('new_tokens')} generated tokens were answered")
    return 100.0 * (prefill + decode) / (busy * peak)


def decode_hbm_roofline_pct(ctx: Context, p: Dict[str, Any]
                            ) -> Optional[float]:
    """The bytes a decode step must move (``decode_step_bytes``) over the
    chip's bandwidth, over a step's device time (the mean over the window's
    ``jit_decode_step`` executions)."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    slots, cache = ctx.counters.get("slots"), ctx.counters.get("cache_len")
    if not runs or not slots or not cache:
        return None
    spent = statistics.fmean(_busy_ms(ctx, runs)) / 1e3
    nbytes = decode_step_bytes(int(slots), int(cache), dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    ctx.notes.append(
        f"decode step roofline: {nbytes / 1e9:.3f} GB a step "
        f"({param_count(dims) * ACT_BYTES / 1e9:.3f} of weights, "
        f"{state_bytes(int(slots), int(cache), dims)}), least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f} over {len(runs)} steps")
    return 100.0 * least / spent if spent else None


def ssm_step_roofline_pct(ctx: Context, p: Dict[str, Any]
                          ) -> Optional[float]:
    """The state's bytes a step (read and written, float32) over the chip's
    bandwidth, over the device time of the operations under ``mamba`` /
    ``core`` inside the window's ``jit_decode_step`` executions."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    slots = ctx.counters.get("slots")
    if not runs or not slots:
        return None
    starts = [r.start for r in runs]
    scopes = lfm2_counts.program_scopes()
    spent = 0.0
    for op in device_scopes._run_leaves(tuple(ctx.trace.window)) or ():
        path = device_scopes.scope_path(op.record.tf_op, scopes)
        if path[:1] != ("mamba",) or "core" not in path:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= runs[i].end:
            spent += op.seconds
    if not spent:
        return None
    n_mamba, _ = layers(dims)
    nbytes = len(runs) * n_mamba * ssd_step_bytes(int(slots), dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    ctx.notes.append(
        f"state step roofline: {nbytes / len(runs) / 1e9:.3f} GB of state a "
        f"step, least {least / len(runs) * 1e3:.3f} ms of "
        f"{spent / len(runs) * 1e3:.3f} a step under mamba/core")
    return 100.0 * least / spent


def ssd_prefill_roofline_pct(ctx: Context, p: Dict[str, Any]
                             ) -> Optional[float]:
    """The least time for the window's ``ssd_fwd`` calls (the larger of FLOPs
    over the peak and bytes over the bandwidth, at the shapes the calls had)
    over their device time."""
    dims = _dims(ctx)
    calls = _ssd_calls(ctx) if dims else ()
    if not calls:
        return None
    least, bounds = 0.0, set()
    for shape, _ in calls:
        s, bound = min_seconds(ssd_fwd_flops(shape[0], shape[2], dims),
                               ssd_fwd_bytes(shape[0], shape[2], dims),
                               ctx.device_kind)
        least += s
        bounds.add(bound)
    spent = sum(s for _, s in calls)
    ctx.notes.append(
        f"scan roofline: {len(calls)} ssd_fwd calls, least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f}; bound by "
        f"{sorted(bounds)}")
    return 100.0 * least / spent


def span_attr_mean(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The mean of attribute ``p["attr"]`` over the spans named ``p["span"]``
    inside the window, times ``p["scale"]``."""
    if ctx.trace is None:
        return None
    values = [float(s.attrs[p["attr"]]) for s in _spans(ctx, p["span"])
              if p["attr"] in s.attrs]
    if not values:
        return None
    return statistics.fmean(values) * p.get("scale", 1.0)


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
