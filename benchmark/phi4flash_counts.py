"""Operations and bytes of Phi-4-mini-flash's layers from shapes alone, and the
readers of its generating cell's per-layer metrics.

The counts are what the *algorithm* needs. A token's model FLOPs are its
matmuls (a mixer's projections, the dense FFN, the tied head where a position
is read), the convolution's taps, the scan's two multiply-adds a state
element, and differential attention's two score products and two value
products a pair of heads over the rows a query sees. A prefill runs the
self-decoder over every padded position, the full attention layer's K and V
there too, and everything after on one position. A decode step's bytes are
what it must move: every weight once (the tied embedding as the head), the
nine float32 states and their convolution tails read and written, and the K
and V rows the slots hold **as the attention layers see them**: a window
layer's ring, and the one full cache once for each of the eight layers that
read it (the engine's ``live_rows``).

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no execution of that name,
no scope of that name (a program from before SambaY's kinds), or dims
without ``d_inner``. The trace helpers are ``lfm2_counts``',
``granite_counts``', ``smallthinker_counts``' and ``device_scopes``',
imported, not copied.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from benchmark import (device_scopes, granite_counts, lfm2_counts, peaks,
                       program_spans, smallthinker_counts)
from benchmark.flops import MATMUL
from benchmark.reducers import Context
from benchmark.sala_counts import min_seconds
from benchmark.smallthinker_counts import band_pairs

MAMBA, SWA, FULL, CROSS, GMU = "mamba", "swa", "full", "cross", "gmu"
PREFILL, DECODE = granite_counts.PREFILL, granite_counts.DECODE
DECODE_CALL = "decode_attn"                  # ops/decode_attention.py
STATE_BYTES, ACT_BYTES = 4, 2                # float32 state, bfloat16 the rest

# What an accepted reader reads on this cell as it is, it reads: the cell is
# appended to that entry's list (one entry a reader), and ``gmu_share_pct``
# names ``lfm2_counts.scope_share_pct`` with a scope of its own. This module
# holds what only this configuration's counts can say.


# -- counts ------------------------------------------------------------------


def count(dims: Dict[str, Any], *kinds: str) -> int:
    return sum(dims["layer_types"].count(kind) for kind in kinds)


def mamba_params(dims: Dict[str, Any]) -> int:
    """A Mamba-1 mixer's matmul weights: ``W_in`` d -> 2 d_inner, ``W_x``
    d_inner -> dt_rank + 2 d_state, ``W_dt`` dt_rank -> d_inner, ``W_out``
    d_inner -> d."""
    d, inner = dims["d_model"], dims["d_inner"]
    return (2 * d * inner + inner * (dims["dt_rank"] + 2 * dims["d_state"])
            + dims["dt_rank"] * inner + inner * d)


def mamba_small(dims: Dict[str, Any]) -> int:
    """The taps and their bias, ``dt``'s bias, ``A_log`` and ``D``."""
    return dims["d_inner"] * (dims["conv_width"] + 3 + dims["d_state"])


def attention_params(dims: Dict[str, Any], cross: bool = False) -> int:
    """The fused q, k, v projection (q alone in a cross layer) and the
    output projection."""
    d, hd = dims["d_model"], dims["head_dim"]
    heads = dims["n_heads"] + (0 if cross else 2 * dims["n_kv_heads"])
    return d * heads * hd + dims["n_heads"] * hd * d


def attention_small(dims: Dict[str, Any], cross: bool = False) -> int:
    """The two biases, the four lambda vectors and the sub-norm's weight."""
    hd = dims["head_dim"]
    heads = dims["n_heads"] + (0 if cross else 2 * dims["n_kv_heads"])
    return heads * hd + dims["d_model"] + 4 * hd + 2 * hd


def gmu_params(dims: Dict[str, Any]) -> int:
    return 2 * dims["d_model"] * dims["d_inner"]


def ffn_params(dims: Dict[str, Any]) -> int:
    return 3 * dims["d_model"] * dims["d_ff"]


def mixer_params(kind: str, dims: Dict[str, Any]) -> int:
    """A mixer's matmul weights."""
    return (mamba_params(dims) if kind == MAMBA else
            gmu_params(dims) if kind == GMU else
            attention_params(dims, kind == CROSS))


def param_count(dims: Dict[str, Any]) -> int:
    """Every parameter: the layers' matrices and small leaves, the two
    LayerNorms a layer, the tied embedding, the final LayerNorm."""
    d = dims["d_model"]
    small = {MAMBA: mamba_small(dims), GMU: 0,
             CROSS: attention_small(dims, True)}
    layers = sum(mixer_params(kind, dims)
                 + small.get(kind, attention_small(dims))
                 + ffn_params(dims) + 4 * d for kind in dims["layer_types"])
    return layers + dims["vocab_size"] * d + 2 * d


def row_bytes(dims: Dict[str, Any]) -> int:
    """One position of one layer in a cache: k and v at the K/V heads."""
    return 2 * dims["n_kv_heads"] * dims["head_dim"] * ACT_BYTES


def state_bytes(slots: int, cache_len: int, dims: Dict[str, Any]
                ) -> Dict[str, float]:
    """What ``slots`` sequences keep, by leaf of ``DecodeState``: the scan
    layers' states and tails, the window layers' rings, and one full cache
    whatever the number of layers that read it."""
    inner = dims["d_inner"]
    n_mamba = count(dims, MAMBA)
    return {
        "ssm": n_mamba * slots * inner * dims["d_state"] * STATE_BYTES,
        "conv": n_mamba * slots * (dims["conv_width"] - 1) * inner
        * ACT_BYTES,
        "ring": count(dims, SWA) * slots * min(dims["window"], cache_len)
        * row_bytes(dims),
        "kv": count(dims, FULL) * slots * cache_len * row_bytes(dims)}


def slot_rows(cache_len: int, dims: Dict[str, Any]) -> int:
    """The rows one slot is allocated as the attention layers see them: a
    window layer's ring, and the full cache once for each layer that reads
    it."""
    return (count(dims, SWA) * min(dims["window"], cache_len)
            + count(dims, FULL, CROSS) * cache_len)


def step_weight_bytes(dims: Dict[str, Any]) -> int:
    """The weights a decode step must read: all of them (of the tied
    embedding the head reads every row)."""
    return ACT_BYTES * param_count(dims)


def decode_step_bytes(slots: int, live_rows: float, dims: Dict[str, Any]
                      ) -> float:
    """What a step must move: every weight once, the states and the tails
    read and written, and the ``live_rows`` K and V rows the slots hold as
    the attention layers see them (``TransformerGenerator.live_rows``)."""
    held = state_bytes(slots, 0, dims)
    return (step_weight_bytes(dims) + 2 * held["ssm"] + 2 * held["conv"]
            + live_rows * row_bytes(dims))


def scan_flops(positions: float, dims: Dict[str, Any]) -> float:
    """The recurrence over ``positions`` tokens of one layer: a multiply-add
    a state element for the update and one for ``S C``."""
    return positions * 2 * MATMUL * dims["d_inner"] * dims["d_state"]


def scan_step_bytes(slots: int, dims: Dict[str, Any]) -> float:
    """One layer's states read and written, float32."""
    return slots * 2 * dims["d_inner"] * dims["d_state"] * STATE_BYTES


def scan_prefill_bytes(length: int, dims: Dict[str, Any]) -> float:
    """One layer's scan over a prompt: x in (the compute dtype), the step
    in and y out (float32), B and C, the state out."""
    return (length * (dims["d_inner"] * (ACT_BYTES + 2 * STATE_BYTES)
                      + 2 * dims["d_state"] * STATE_BYTES)
            + dims["d_inner"] * dims["d_state"] * STATE_BYTES)


def attn_flops(pairs: float, dims: Dict[str, Any]) -> float:
    """Differential attention over ``pairs`` (query, key) pairs: every query
    head's scores at ``head_dim`` and its map times its group's values at
    ``2 head_dim``."""
    return MATMUL * 3 * dims["head_dim"] * pairs * dims["n_heads"]


def layer_token_flops(kind: str, dims: Dict[str, Any]) -> float:
    """A token's FLOPs in one layer outside the scan and the scores."""
    conv = (MATMUL * dims["conv_width"] * dims["d_inner"]
            if kind == MAMBA else 0)
    return MATMUL * (mixer_params(kind, dims) + ffn_params(dims)) + conv


def head_flops(positions: int, dims: Dict[str, Any]) -> float:
    return MATMUL * positions * dims["d_model"] * dims["vocab_size"]


def prefill_flops(length: int, dims: Dict[str, Any]) -> float:
    """One prompt padded to ``length``: the self-decoder at every padded
    position (a scan a Mamba layer, the band of a window layer), the full
    layer's K and V projection there, and from that layer's query on one
    position: its scores over ``length`` rows and those of every cross
    layer, the head at that position."""
    types = dims["layer_types"]
    split = types.index(FULL) if FULL in types else len(types)
    before, after = types[:split], types[split:]
    kv = MATMUL * dims["d_model"] * 2 * dims["n_kv_heads"] * dims["head_dim"]
    return (length * sum(layer_token_flops(kind, dims) for kind in before)
            + before.count(MAMBA) * scan_flops(length, dims)
            + before.count(SWA) * attn_flops(
                band_pairs(length, dims["window"]), dims)
            + (length * kv if after else 0)
            + sum(layer_token_flops(kind, dims) for kind in after)
            + attn_flops(length * count(dims, FULL, CROSS), dims)
            + head_flops(1, dims))


def decode_step_flops(slots: int, live_rows: float, dims: Dict[str, Any]
                      ) -> float:
    """One decode step over ``slots`` slots, empty ones too (the program has
    one shape): every layer, the recurrence, the scores over the
    ``live_rows`` rows the occupied slots hold, the head at every slot."""
    return (slots * sum(layer_token_flops(kind, dims)
                        for kind in dims["layer_types"])
            + count(dims, MAMBA) * scan_flops(slots, dims)
            + attn_flops(live_rows, dims) + head_flops(slots, dims))


# -- the window's operations ---------------------------------------------------


def _dims(ctx: Context) -> Optional[Dict[str, Any]]:
    dims = ctx.counters.get("dims", {})
    if (ctx.trace is None or not ctx.trace.devices
            or "d_inner" not in dims or "layer_types" not in dims):
        return None
    return dims


_runs = granite_counts._runs
_busy_ms = granite_counts._busy_ms


def _step_attr(ctx: Context, name: str) -> List[float]:
    """Attribute ``name`` of the window's ``serve.generate.step`` spans."""
    return [float(s.attrs[name])
            for s in granite_counts._spans(ctx, granite_counts.STEP_SPAN)
            if name in s.attrs]


def _prefill_lengths(ctx: Context, dims: Dict[str, Any]) -> List[int]:
    """The padded length of each prefill the window ran: a prompt shows as
    one ``flash_fwd`` call a window layer, whose result is [heads, length, 2
    head_dim]."""
    n = count(dims, SWA)
    calls = lfm2_counts._flash_calls(ctx) if n else ()
    return [shape[1] for shape, _ in calls[::n]]


_SUMMED: Dict[Any, Dict[Any, float]] = {}


def _summed(ctx: Context) -> Dict[Any, float]:
    """The window's leaf operations summed once a run, however many readers
    ask (a window of this cell is 1.7 million of them): device seconds by
    ``(the operation's scopes outermost first, the program whose execution it
    ran inside (``jit_decode_step``, ``jit_prefill`` or None), whether it is
    the ``decode_attn`` call)``."""
    window = tuple(ctx.trace.window)
    if window not in _SUMMED:
        _SUMMED.clear()
        scopes = lfm2_counts.program_scopes()
        programs = [(name, runs, [r.start for r in runs])
                    for name in (DECODE, PREFILL)
                    for runs in [_runs(ctx, name)]]
        sums: Dict[Any, float] = {}
        for op in device_scopes._run_leaves(window) or ():
            inside = next((name for name, runs, starts in programs
                           if smallthinker_counts._inside(op, starts, runs)),
                          None)
            head = op.record.name.split(" = ")[0]
            key = (device_scopes.scope_path(op.record.tf_op, scopes), inside,
                   program_spans.KERNEL_CATEGORY in op.record.name
                   and DECODE_CALL in head)
            sums[key] = sums.get(key, 0.0) + op.seconds
        _SUMMED[window] = sums
    return _SUMMED[window]


def _seconds_under(ctx: Context, program: str, scopes_held: Sequence[str]
                   ) -> float:
    """Device time inside ``program``'s executions of the leaf operations
    whose name stack starts with ``scopes_held``'s first and holds them
    all."""
    return sum(seconds for (path, inside, _), seconds in _summed(ctx).items()
               if inside == program and path[:1] == tuple(scopes_held[:1])
               and set(scopes_held) <= set(path))


# -- readers -------------------------------------------------------------------


def window_mfu_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of every token the window ran (each prefill at its padded
    length, read off its ``flash_fwd`` calls, one a window layer; each decode
    step at every slot, empty ones too, its scores over the rows its span
    says the slots hold) over the window's device busy time x the chip's bf16
    peak."""
    dims = _dims(ctx)
    if dims is None:
        return None
    busy = lfm2_counts._busy_s(ctx)
    lengths = _prefill_lengths(ctx, dims)
    steps, live = len(_runs(ctx, DECODE)), _step_attr(ctx, "live_rows")
    slots = int(ctx.counters.get("slots", 0))
    if not busy or not (lengths or steps) or not live:
        return None
    prefill = sum(prefill_flops(n, dims) for n in lengths)
    decode = steps * decode_step_flops(slots, statistics.fmean(live), dims)
    peak = peaks.peak(ctx.device_kind).bf16_flops_per_s
    ctx.notes.append(
        f"window mfu: {len(lengths)} prefills of {sum(lengths)} padded "
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
    live = _step_attr(ctx, "live_rows") if runs else []
    slots = ctx.counters.get("slots")
    if not live or not slots:
        return None
    spent = statistics.fmean(_busy_ms(ctx, runs)) / 1e3
    rows = statistics.fmean(live)
    nbytes = decode_step_bytes(int(slots), rows, dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    held = state_bytes(int(slots), 0, dims)
    ctx.notes.append(
        f"decode step roofline: {nbytes / 1e9:.3f} GB a step must move "
        f"({step_weight_bytes(dims) / 1e9:.3f} of weights, "
        f"{2 * (held['ssm'] + held['conv']) / 1e9:.3f} of states and tails "
        f"in and out, {rows * row_bytes(dims) / 1e9:.3f} of the {rows:.0f} "
        f"rows the attention layers see), least {least * 1e3:.3f} ms of "
        f"{spent * 1e3:.3f} over {len(runs)} steps")
    return 100.0 * least / spent if spent else None


def diff_attn_roofline_pct(ctx: Context, p: Dict[str, Any]
                           ) -> Optional[float]:
    """The decode steps' attention: the K and V rows its calls read (the
    steps' ``read_rows``: every slot's tiles up to its newest row, a ring or
    the full cache, once a reading layer) over the chip's bandwidth, over the
    device time of the ``decode_attn`` calls inside the window's
    ``jit_decode_step`` executions."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    read = _step_attr(ctx, "read_rows") if runs else []
    spent = sum(seconds for (_, inside, attend), seconds
                in _summed(ctx).items()
                if attend and inside == DECODE) if read else 0.0
    if not spent:
        return None
    nbytes = statistics.fmean(read) * len(runs) * row_bytes(dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    ctx.notes.append(
        f"decode attention roofline: {nbytes / len(runs) / 1e9:.3f} GB of "
        f"rows read a step, least {least / len(runs) * 1e3:.3f} ms of "
        f"{spent / len(runs) * 1e3:.3f} a step in {DECODE_CALL}")
    return 100.0 * least / spent


def mamba1_step_roofline_pct(ctx: Context, p: Dict[str, Any]
                             ) -> Optional[float]:
    """The scan layers' states a step (read and written, float32) over the
    chip's bandwidth, over the device time of the operations under ``mamba``
    / ``core`` inside the window's ``jit_decode_step`` executions."""
    dims = _dims(ctx)
    runs = _runs(ctx, DECODE) if dims else []
    slots = ctx.counters.get("slots")
    spent = (_seconds_under(ctx, DECODE, ("mamba", "core"))
             if runs and slots else 0.0)
    if not spent:
        return None
    nbytes = len(runs) * count(dims, MAMBA) * scan_step_bytes(int(slots),
                                                              dims)
    least = nbytes / peaks.peak(ctx.device_kind).hbm_bytes_per_s
    ctx.notes.append(
        f"scan step roofline: {nbytes / len(runs) / 1e9:.3f} GB of state a "
        f"step, least {least / len(runs) * 1e3:.3f} ms of "
        f"{spent / len(runs) * 1e3:.3f} a step under mamba/core")
    return 100.0 * least / spent


def mamba1_prefill_roofline_pct(ctx: Context, p: Dict[str, Any]
                                ) -> Optional[float]:
    """The least time for the prefills' scans (a layer and prompt the larger
    of the recurrence's FLOPs over the peak and the bytes of x, the step, y,
    B, C and the state over the bandwidth, at the padded lengths the window's
    prefills had) over the device time of the operations under ``mamba`` /
    ``core`` inside the window's ``jit_prefill`` executions."""
    dims = _dims(ctx)
    runs = _runs(ctx, PREFILL) if dims else []
    lengths = _prefill_lengths(ctx, dims) if runs else []
    spent = (_seconds_under(ctx, PREFILL, ("mamba", "core")) if lengths
             else 0.0)
    if not spent:
        return None
    least = count(dims, MAMBA) * sum(
        min_seconds(scan_flops(n, dims), scan_prefill_bytes(n, dims),
                    ctx.device_kind)[0] for n in lengths)
    ctx.notes.append(
        f"scan prefill roofline: {len(lengths)} prompts of {sum(lengths)} "
        f"padded tokens through {count(dims, MAMBA)} scans, least "
        f"{least * 1e3:.3f} ms of {spent * 1e3:.3f} under mamba/core")
    return 100.0 * least / spent


def cross_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The one full cache's eight layers: the accepted
    ``nested_scope_share_pct`` of ``global`` (the layer that writes it) and of
    ``cross`` (the seven that read it), summed: a layer enters one of the
    two."""
    shares = [smallthinker_counts.nested_scope_share_pct(ctx, {"scope": name})
              for name in ("global", "cross")]
    return None if None in shares else sum(shares)


def cache_live_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The mean ``live_rows`` of the window's steps over the rows allocated
    as the attention layers see them (``slots x slot_rows``)."""
    dims = ctx.counters.get("dims", {})
    live = (_step_attr(ctx, "live_rows")
            if ctx.trace is not None and "d_inner" in dims else [])
    slots, cache = ctx.counters.get("slots"), ctx.counters.get("cache_len")
    if not live or not slots or not cache:
        return None
    return 100.0 * statistics.fmean(live) / (
        int(slots) * slot_rows(int(cache), dims))
