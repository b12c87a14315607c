"""Readers of the program's own spans in the profiler's trace.

While a profiler session is on, every ``ray_tpu.observability.span`` is
also a ``jax.profiler.TraceAnnotation`` named ``ray_tpu.<name>`` on the
thread that opened it, with its ids and attributes, on the device's clock
(``ray_tpu/observability/metric_names.py`` lists the names). The harness's
reduction keeps only the benchmark's own ``bench.`` spans, so the readers
here go back to the run's ``.xplane.pb`` for the program's, and take the
window and the device's operations from the reduced trace they are given.

A reader returns ``None`` where the trace has no such span (a program from
before the spans, a cell of the other job) and the harness leaves the
metric out. The arithmetic works on plain ``Span`` tuples, so synthetic
planes test it on the CPU; ``read_spans`` is the only part that touches a
file.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import statistics
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.reducers import Context
from benchmark.trace_reduce import (NS, Interval, clip, gaps, leaves, merge,
                                    union_ns)

PREFIX = "ray_tpu."                  # observability.ANNOTATION_PREFIX
# observability.ANNOTATION_ID_PREFIX: the letter an id is written behind,
# so that the trace does not store one that looks like a number as one
ID_PREFIX = "t"
IDS = ("trace_id", "span_id", "parent_span_id")
WINDOW_EVENT = "bench.window"        # harness.SPAN_PREFIX + WINDOW_SPAN
KERNEL_CATEGORY = "tpu_custom_call"  # a Mosaic kernel's custom-call target


@dataclasses.dataclass(frozen=True)
class Span:
    name: str                 # without the prefix
    start: int                # nanoseconds on the trace's clock
    end: int
    thread: int               # which of the host's threads opened it
    attrs: Dict[str, Any]

    @property
    def interval(self) -> Interval:
        return (self.start, self.end)


# -- the run's trace -------------------------------------------------------


@functools.lru_cache(maxsize=None)
def read_spans(path: str) -> Tuple[Optional[Interval], Tuple[Span, ...]]:
    """One ``.xplane.pb``: the benchmark's window (the first
    ``bench.window`` event, as ``trace_reduce.load`` takes it) and every
    host event named ``ray_tpu.*``, with the ordinal of its thread's line
    among the host's lines and its attributes."""
    from jax.profiler import ProfileData
    window: Optional[Interval] = None
    spans: List[Span] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                name = ev.name
                if name != WINDOW_EVENT and not name.startswith(PREFIX):
                    continue
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if name == WINDOW_EVENT:
                    window = window or (start, end)
                else:
                    spans.append(Span(name[len(PREFIX):], start, end, thread,
                                      _attributes(ev.stats)))
    return window, tuple(spans)


def _attributes(stats) -> Dict[str, Any]:
    """An event's attributes, its ids as the program's other sink has
    them: without the letter, and empty where there is none (a root
    span's parent)."""
    attrs = dict(stats)
    for key in IDS:
        value = str(attrs.get(key, ""))
        attrs[key] = value[1:] if value.startswith(ID_PREFIX) else value
    return attrs


@functools.lru_cache(maxsize=None)
def find_trace(window: Interval) -> Optional[str]:
    """The path of the run whose window this is. ``reducers.Context``
    gives a reader the reduced trace and no path to the file, but the
    harness's trace directory (``tempfile.mkdtemp(prefix="bench_trace_")``)
    still stands while the readers run. Several runs may share the
    temporary directory (the tests run six workers at once), so "the
    newest" is not enough: the run's file is the one whose ``bench.window``
    event equals the reduced trace's window to the nanosecond."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "plugins",
                           "profile", "*", "*.xplane.pb")
    for path in glob.glob(pattern):
        try:
            if read_spans(path)[0] == window:
                return path
        except Exception:  # noqa: BLE001 - another run's file, half written or gone
            continue
    return None


def program_spans(ctx: Context) -> Sequence[Span]:
    """The program's spans of this run; none where there is no trace."""
    if ctx.trace is None:
        return ()
    path = find_trace(tuple(ctx.trace.window))
    return read_spans(path)[1] if path else ()


# -- interval arithmetic the reduction does not have -----------------------


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """The parts covered by both unions, as sorted disjoint intervals."""
    a, b = merge(a), merge(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def named(spans: Sequence[Span], name: str,
          where: Optional[Dict[str, Any]] = None) -> List[Span]:
    """The spans of that name whose attributes equal ``where``'s."""
    return [s for s in spans if s.name == name and all(
        str(s.attrs.get(k)) == str(v) for k, v in (where or {}).items())]


def inside(spans: Sequence[Span], window: Interval) -> List[Span]:
    """The spans that lie wholly inside the window: one cut by its edge
    has a duration that is part warm-up or part drain, so a mean leaves it
    out (the idle classes clip it instead)."""
    return [s for s in spans if s.start >= window[0] and s.end <= window[1]]


# -- readers ---------------------------------------------------------------

# The idle classes in order: an instant of device idle time belongs to the
# first class whose span is open then, and to "none" when no span is.
IDLE_CLASSES = (("batch_execute", "serve.batch.execute"),
                ("linger", "serve.batch.linger"),
                ("request", "serve.request"))


def idle_classes(idle: Sequence[Interval], spans: Sequence[Span],
                 window: Interval) -> Dict[str, int]:
    """Nanoseconds of the idle intervals in each class: exclusive, and
    their sum is the idle time."""
    out, rest = {}, merge(clip(idle, window))
    for cls, name in IDLE_CLASSES:
        open_ = merge(clip([s.interval for s in named(spans, name)], window))
        out[cls] = union_ns(intersect(rest, open_))
        rest = intersect(rest, gaps(open_, window))
    out["none"] = union_ns(rest)
    return out


def idle_class_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The share of the window in which the first device is idle and the
    class ``p["class"]`` holds (``IDLE_CLASSES``, or ``"none"``: no request
    is open anywhere in the process). ``None`` without a device plane, or
    where the trace lacks the class's own span (``"none"``:
    ``serve.request``)."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans, window = program_spans(ctx), ctx.trace.window
    needs = dict(IDLE_CLASSES).get(p["class"], "serve.request")
    if window[1] <= window[0] or not named(spans, needs):
        return None
    idle = gaps([(e.start, e.end) for e in ctx.trace.first.ops], window)
    return (100.0 * idle_classes(idle, spans, window)[p["class"]]
            / (window[1] - window[0]))


def span_mean_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Over the spans named ``p["span"]`` (with attributes ``p["where"]``)
    that lie inside the window: the mean of their duration in
    milliseconds, or with ``p["attr"]`` the mean of that attribute times
    ``p["scale"]`` (a wait recorded as a number of microseconds on the
    span that ends it; a negative one was never stamped and is left
    out)."""
    if ctx.trace is None:
        return None
    mine = inside(named(program_spans(ctx), p["span"], p.get("where")),
                  ctx.trace.window)
    if "attr" in p:
        values = [float(s.attrs[p["attr"]]) * p.get("scale", 1.0)
                  for s in mine if isinstance(s.attrs.get(p["attr"]),
                                              (int, float))
                  and s.attrs[p["attr"]] >= 0]
    else:
        values = [(s.end - s.start) * NS * 1e3 for s in mine]
    return statistics.fmean(values) if values else None


def span_self_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The mean, over the spans named ``p["span"]`` inside the window, of
    their duration less that of the ``p["less"]`` spans the same thread
    opened inside them, in milliseconds."""
    if ctx.trace is None:
        return None
    spans = program_spans(ctx)
    less = named(spans, p["less"])
    values = []
    for s in inside(named(spans, p["span"]), ctx.trace.window):
        held = sum(c.end - c.start for c in less if c.thread == s.thread
                   and c.start >= s.start and c.end <= s.end)
        values.append((s.end - s.start - held) * NS * 1e3)
    return statistics.fmean(values) if values else None


def gauge(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The largest sample of the gauge ``p["name"]`` in the program's
    metrics registry whose ``deployment`` tag is the cell's name (the
    serving job names its deployment after the cell). ``None`` where the
    program sets no such gauge."""
    from ray_tpu.util import metrics
    values = [value for family in metrics.snapshot()
              if family["name"] == p["name"]
              for _, tags, value in family["samples"]
              if dict(map(tuple, tags)).get("deployment") == ctx.cell.name]
    return max(values) if values else None


def kernel_ms(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The mean device time of one call of the Mosaic kernel named
    ``p["kernel"]`` (its ``pallas_call``'s ``name``, which the compiled
    custom call's own name ends in) on the first device inside the window,
    in milliseconds."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    reg = re.compile(rf"(^|_){re.escape(p['kernel'])}(_|\.|$)")
    calls = [e for e in leaves(ctx.trace.first.ops_inside(ctx.trace.window))
             if KERNEL_CATEGORY in e.category and reg.search(e.name)]
    return statistics.fmean(e.seconds for e in calls) * 1e3 if calls else None
