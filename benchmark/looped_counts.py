"""Operations of the looped decoder from shapes alone, and the readers of
its per-layer metrics.

``dims`` is the configuration as ``adapters.looped_decoder.dims`` gives it:
the dense decoder's sizes and ``total_ut_steps`` = T. A token passes the
stack T times and meets the head T times (every exit's cross entropy is in
the loss), so a training step needs ``TRAIN_PASSES * T`` times the dense
forward's operations; the gate's 2 * d_model a token and pass is left out
(0.0003% of a block). Nothing here counts recomputation (``remat``).

The readers take ``reducers.Context`` like any other and return ``None``
where there is nothing to read: no device plane, no such program, no
kernel of that name, or dims without ``total_ut_steps`` (another
architecture's cell).
"""

from __future__ import annotations

import functools
import re
import statistics
from typing import Any, Dict, List, Optional

from benchmark import flops, peaks, program_spans
from benchmark.reducers import Context
from benchmark.trace_reduce import (DEVICE_PLANE, OPS_LINE, Event, leaves,
                                    matching, union_ns)

FLASH_FWD = re.compile(r"(^|_)flash_fwd(_|\.|$)")
SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def forward_flops_per_token(dims: Dict[str, Any], seq: int) -> float:
    """T passes over the stack, and the head after each."""
    return dims["total_ut_steps"] * flops.forward_flops_per_token(dims, seq)


def train_flops_per_token(dims: Dict[str, Any], seq: int) -> float:
    """3 x T x [L x (2 x block weights + 2 x heads x seq x head_dim) + 2 x
    d_model x vocabulary]: forward and a backward of twice its cost, no
    recomputation counted."""
    return flops.TRAIN_PASSES * forward_flops_per_token(dims, seq)


def head_flops_share(dims: Dict[str, Any], seq: int) -> float:
    """The T heads' share of a token's operations."""
    head = flops.MATMUL * dims["d_model"] * dims["vocab_size"]
    return head / flops.forward_flops_per_token(dims, seq)


def flash_calls_per_step(dims: Dict[str, Any], remat: bool) -> Dict[str, int]:
    """How often each of the kernel's three calls runs in one training step
    on one device: once a block (the forward once more under ``remat``), in
    every layer of every pass."""
    blocks = dims["n_layers"] * dims["total_ut_steps"]
    return {"fwd": (2 if remat else 1) * blocks, "dq": blocks, "dkv": blocks}


def flash_min_seconds_per_step(dims: Dict[str, Any], sequences: int, seq: int,
                               remat: bool, device_kind: str
                               ) -> Dict[str, Any]:
    """The least time the chip could take for one step's attention calls:
    for each call the larger of FLOPs over peak and bytes over peak
    bandwidth (``flops.flash_min_seconds``), times how often it runs."""
    peak = peaks.peak(device_kind)
    least, bounds = 0.0, {}
    for kind, n in flash_calls_per_step(dims, remat).items():
        m = flops.flash_min_seconds(
            kind, sequences, seq, dims["n_heads"], dims["head_dim"],
            peak.bf16_flops_per_s, peak.hbm_bytes_per_s)
        least += n * m["seconds"]
        bounds[kind] = m["bound"]
    return {"seconds": least, "bounds": bounds}


# -- readers ---------------------------------------------------------------


def _steps(ctx: Context, p: Dict[str, Any]):
    """The first device and the whole executions of ``p["program"]`` in
    the window; ``(None, [])`` without a device plane or for an
    architecture that is not looped."""
    if (ctx.trace is None or not ctx.trace.devices
            or "total_ut_steps" not in ctx.counters.get("dims", {})):
        return None, []
    dev = ctx.trace.first
    return dev, dev.executions(p["program"], ctx.trace.window)


def _remat(ctx: Context) -> bool:
    return bool(ctx.cell.deploy.get("model", {}).get("remat", True))


def flash_roofline_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """``flash_min_seconds_per_step`` over the device time of the step's
    Mosaic calls; the median over the window's steps."""
    dev, runs = _steps(ctx, p)
    if not runs:
        return None
    c = ctx.counters
    least = flash_min_seconds_per_step(
        c["dims"], max(1, c["sequences_per_step"] // c["devices"]),
        c["seq_len"], _remat(ctx), ctx.device_kind)
    shares = []
    for r in runs:
        mine = matching(dev.ops_inside(r), p["ops"])
        if mine:
            shares.append(100.0 * least["seconds"]
                          / sum(e.seconds for e in mine))
    if not shares:
        return None
    ctx.notes.append(
        f"flash roofline (looped): "
        f"{sum(flash_calls_per_step(c['dims'], _remat(ctx)).values())} "
        f"calls a step on a device, least {least['seconds'] * 1e3:.3f} ms; "
        f"bound by {least['bounds']}")
    return statistics.median(shares)


def flash_fwd_calls_per_step(ctx: Context, p: Dict[str, Any]
                             ) -> Optional[float]:
    """How many times the Mosaic kernel named ``flash_fwd`` ran inside one
    execution of the step; the median over the window's steps. Under
    ``remat`` the looped stack makes it 2 x layers x passes."""
    dev, runs = _steps(ctx, p)
    counts = [sum(1 for e in leaves(dev.ops_inside(r))
                  if program_spans.KERNEL_CATEGORY in e.category
                  and FLASH_FWD.search(e.name)) for r in runs]
    counts = [n for n in counts if n]
    if not counts:
        return None
    want = flash_calls_per_step(ctx.counters["dims"], _remat(ctx))["fwd"]
    ctx.notes.append(f"flash_fwd calls a step: {statistics.median(counts)} "
                     f"(layers x passes x {1 + _remat(ctx)} = {want})")
    return float(statistics.median(counts))


@functools.lru_cache(maxsize=4)
def _device_ops(path: str) -> List[Event]:
    """The first device's operations with their whole HLO text as the
    name: the reduced trace keeps an operation's short name and opcode,
    and its shapes are only in the text."""
    from jax.profiler import ProfileData
    planes = {int(m.group(1)): plane
              for plane in ProfileData.from_file(path).planes
              if (m := DEVICE_PLANE.match(plane.name))}
    if not planes:
        return []
    out = []
    for line in planes[min(planes)].lines:
        if line.name == OPS_LINE:
            for ev in line.events:
                start = int(ev.start_ns)
                out.append(Event(ev.name, start, start + int(ev.duration_ns)))
    return out


def holds_size(text: str, size: int) -> bool:
    """Whether a shape in an operation's HLO text (its result's or an
    operand's) has ``size`` among its dimensions."""
    want = str(size)
    return any(want in dims.split(",") for dims in SHAPE.findall(text))


def vocab_ops_share_pct(steps, ops: List[Event], vocab_size: int,
                        notes: Optional[List[str]] = None) -> Optional[float]:
    """Over the executions ``steps``: the device time of the leaf
    operations inside one of which a shape holds ``vocab_size``, over the
    time any operation ran inside it; the median."""
    shares, caught = [], {}
    for r in steps:
        inside = [e for e in ops if e.start >= r.start and e.end <= r.end]
        busy = union_ns((e.start, e.end) for e in inside)
        mine = [e for e in leaves(inside) if holds_size(e.name, vocab_size)]
        if busy and mine:
            shares.append(100.0 * sum(e.end - e.start for e in mine) / busy)
            for e in mine:
                short = e.name.partition(" = ")[0].lstrip("%")
                caught[short] = caught.get(short, 0.0) + e.seconds
    if not shares:
        return None
    if notes is not None:
        top = sorted(caught.items(), key=lambda kv: -kv[1])[:10]
        notes.append(
            f"operations with a {vocab_size}-sized dimension, seconds over "
            f"{len(shares)} steps: "
            + ", ".join(f"{name} {s:.6f}" for name, s in top))
    return statistics.median(shares)


def exit_head_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The share of a step's busy time in operations one of whose shapes
    holds the vocabulary's size: the T heads, their float32 log-softmax and
    gather and their backward, and the embedding's gather and scatter. By
    shape, because this runtime's device plane carries no scope."""
    dev, runs = _steps(ctx, p)
    if not runs:
        return None
    path = program_spans.find_trace(tuple(ctx.trace.window))
    if path is None:
        return None
    return vocab_ops_share_pct(runs, _device_ops(path),
                               ctx.counters["dims"]["vocab_size"], ctx.notes)
