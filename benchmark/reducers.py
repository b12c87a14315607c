"""Per-layer metrics: each is a data file (``layer_metrics/<name>.json``)
that names a reader here and its parameters.

A reader takes the run's ``Context`` (the reduced trace, what the job
counted, the cell) and returns one number, or ``None`` when it finds
nothing to read; the harness then leaves the metric out of the line. A
file may name a reader of a later PR's own module as ``"module:function"``,
so a new metric needs no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib
import statistics
from typing import Any, Callable, Dict, List, Optional

from benchmark import flops, peaks
from benchmark.manifest import Cell
from benchmark.trace_reduce import NS, Reduced, exposed_ns, matching

Reader = Callable[["Context", Dict[str, Any]], Optional[float]]


@dataclasses.dataclass
class Context:
    cell: Cell
    trace: Optional[Reduced]
    counters: Dict[str, Any]
    device_kind: str
    notes: List[str] = dataclasses.field(default_factory=list)


READERS: Dict[str, Reader] = {}


def reader(fn: Reader) -> Reader:
    READERS[fn.__name__] = fn
    return fn


def resolve(name: str) -> Reader:
    if ":" in name:
        module, attr = name.split(":", 1)
        return getattr(importlib.import_module(module), attr)
    if name not in READERS:
        raise KeyError(f"no reader {name!r} (known: {sorted(READERS)})")
    return READERS[name]


def evaluate(metrics: List[Dict[str, Any]], ctx: Context
             ) -> Dict[str, Optional[float]]:
    out = {}
    for m in metrics:
        out[m["name"]] = resolve(m["reducer"])(ctx, m.get("params", {}))
    for note in ctx.notes:
        print(f"[bench] {note}", flush=True)
    return out


# -- what the job counted --------------------------------------------------


@reader
def counter(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """``counters[key] * scale``."""
    value = ctx.counters.get(p["key"])
    return None if value is None else float(value) * p.get("scale", 1.0)


@reader
def counter_ratio(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """``counters[num] / counters[den] * scale``."""
    num, den = ctx.counters.get(p["num"]), ctx.counters.get(p["den"])
    if num is None or not den:
        return None
    return float(num) / float(den) * p.get("scale", 1.0)


# -- what the trace shows --------------------------------------------------


def _executions(ctx: Context, p: Dict[str, Any]):
    if ctx.trace is None or not ctx.trace.devices:
        return None, []
    dev = ctx.trace.first
    return dev, dev.executions(p["program"], ctx.trace.window)


@reader
def execution_gap_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Mean device-idle gap between consecutive executions of a program."""
    _, runs = _executions(ctx, p)
    if len(runs) < 2:
        return None
    return statistics.fmean(max(0, b.start - a.end)
                            for a, b in zip(runs, runs[1:])) * NS * 1e3


@reader
def execution_busy_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device busy time inside one execution of a program: the median over
    the window's executions, or with ``"stat": "mean"`` the mean."""
    dev, runs = _executions(ctx, p)
    if not runs:
        return None
    busy = [dev.busy_inside(r) * NS * 1e3 for r in runs]
    return (statistics.fmean(busy) if p.get("stat") == "mean"
            else statistics.median(busy))


@reader
def op_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Device time of the operations that match, over device busy time."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    busy = ctx.trace.first.busy_ns(ctx.trace.window)
    mine = matching(ctx.trace.first.ops_inside(ctx.trace.window), p["ops"])
    if not busy or not mine:
        return None
    return 100.0 * sum(e.end - e.start for e in mine) / busy


@reader
def flash_roofline_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The least time the chip could take for one step's attention calls
    (forward, once more under remat, dq and dk/dv in every layer; for each
    the larger of FLOPs over peak and bytes over peak bandwidth, from
    ``flops.py``) over the device time of the step's Mosaic calls; the
    median over the window's steps."""
    dev, runs = _executions(ctx, p)
    if not runs:
        return None
    c, peak = ctx.counters, peaks.peak(ctx.device_kind)
    dims = c["dims"]
    per_device = max(1, c["sequences_per_step"] // c["devices"])
    remat = ctx.cell.deploy.get("model", {}).get("remat", True)
    calls = {"fwd": 2 if remat else 1, "dq": 1, "dkv": 1}
    least, bounds = 0.0, {}
    for kind, n in calls.items():
        m = flops.flash_min_seconds(
            kind, per_device, c["seq_len"], dims["n_heads"],
            dims["head_dim"], peak.bf16_flops_per_s, peak.hbm_bytes_per_s)
        least += n * dims["n_layers"] * m["seconds"]
        bounds[kind] = m["bound"]
    shares = []
    for r in runs:
        mine = matching(dev.ops_inside(r), p["ops"])
        if mine:
            shares.append(100.0 * least / sum(e.seconds for e in mine))
    if not shares:
        return None
    ctx.notes.append(
        f"flash roofline: {sum(calls.values()) * dims['n_layers']} calls a "
        f"step on a device, least {least * 1e3:.3f} ms; bound by {bounds}")
    return statistics.median(shares)


@reader
def collective_exposed_ms(ctx: Context, p: Dict[str, Any]
                          ) -> Optional[float]:
    """Time a step spends inside collective operations while no other
    operation runs, on the first device; the mean over the window's steps.
    A program with no collective reads 0 only where it has several
    devices."""
    dev, runs = _executions(ctx, p)
    if not runs or ctx.counters.get("devices", 1) < 2:
        return None
    return statistics.fmean(exposed_ns(dev.ops_inside(r)) * NS * 1e3
                            for r in runs)
