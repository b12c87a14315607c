"""Where a served request's time went, request by request, and whether the
host was held under it: a reader of the program's own spans in a traced
run's ``.xplane.pb``.

Every other reader of the serve path is a mean over the window
(``program_spans.span_mean_ms``, ``span_self_ms``). The tail is not a
mean: the p95 of 240 requests is the twelfth slowest, and what it waited
for is a question about single requests. Since PR 58 a request's spans
share its ``trace_id`` up to its own wait on the replica
(``serve.replica.wait``, which carries its queue wait, its call's time and
the ordinal of the batch that served it), and the stack sampler's tick
records a ``host.hold`` span whenever it wakes late
(``ray_tpu/observability/metric_names.py`` ``SPANS`` has the attributes).
This file joins them:

``requests``      one row a ``serve.request`` inside the window, its stages
                  and ``other``, the remainder, which adds them up to the
                  request's duration to the nanosecond (a generation
                  engine's request too: its wait for a slot and its time in
                  one);
``tail``          the slowest tenth of the rows, each with one class;
``holds``         the ``host.hold`` spans as the intervals they stand for;
``idle_by_span``  the first device's idle time by the innermost open
                  ``ray_tpu.`` span (the shortest of those open) or hold,
                  in any cell.

The reducers at the end have the harness's signature ``(ctx, p) ->
Optional[float]`` and return ``None`` on a trace from a program without the
two spans; no entry of ``BENCHMARK.json`` names them yet (PERF.md section
3 lists the entries they are for). ``python -m benchmark.request_tail
<file.xplane.pb>`` prints a run's tables. The arithmetic works on plain
``Span`` tuples, so synthetic ones test it on the CPU.
"""

from __future__ import annotations

import heapq
import statistics
import sys
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark.program_spans import (Span, inside, intersect, named,
                                     program_spans, read_spans)
from benchmark.reducers import Context
from benchmark.trace_reduce import NS, Interval, clip, gaps, merge, union_ns

WAIT, HOLD = "serve.replica.wait", "host.hold"
NO_SPAN = "(no span)"
# The stages that, with ``other``, add up to a request's ``latency``.
STAGES = ("proxy_self", "route", "mailbox_wait", "queue_wait", "call",
          "reply")
CLASSES = ("held", "own_call", "behind_call", "other")
TAIL_SHARE = 0.1     # the rows over the 90th percentile of latency
HELD_SHARE = 0.25    # a hold covers this share of a row's latency: "held"
STAGE_SHARE = 0.5    # a stage takes this share of it: the row's class
# Spans that are a thread parked for their whole length: the long poll a
# proxy's and a router's threads keep open on the controller. One is open
# whenever nothing else is, so the idle table leaves them out.
PARKED = frozenset({"actor.call listen_for_change"})


class Hold(NamedTuple):
    """One ``host.hold`` span as what it stands for: the process was held
    for ``[start, end]``, the span itself was opened and closed at
    ``end``."""
    start: int
    end: int
    cause: str
    holder: str
    attrs: Dict[str, Any]

    @property
    def interval(self) -> Interval:
        return (self.start, self.end)


class Row(NamedTuple):
    """One request: nanoseconds on the trace's clock, except the counts."""
    order: int          # its place among the window's requests by arrival
    start: int
    trace_id: str
    latency: int        # the duration of its serve.request
    accept_wait: int    # before serve.request opened; -1 where not stamped
    proxy_self: int     # serve.request less route, await_replica and reply
    route: int
    mailbox_wait: int   # actor.call's mailbox_wait_us
    queue_wait: int     # serve.replica.wait's queue_wait_us
    call: int           # serve.replica.wait's call_us
    reply: int
    other: int          # await_replica less the three waits inside it
    size: int           # of a generated request: its prompt's length
    size_max: int       # and the bucket it was padded to
    n: int
    padded_n: int
    batch: int
    cut: str            # why its batch's linger was cut
    call_span: int      # the serve.batch.call inside its batch's execute
    held: int           # host.hold intervals under its serve.request

    def klass(self) -> str:
        if self.held >= HELD_SHARE * self.latency:
            return "held"
        if self.call > STAGE_SHARE * self.latency:
            return "own_call"
        if self.queue_wait + self.mailbox_wait > STAGE_SHARE * self.latency:
            return "behind_call"
        return "other"


# -- from spans to rows ----------------------------------------------------


def holds(spans: Sequence[Span]) -> List[Hold]:
    out = []
    for s in named(spans, HOLD):
        held = max(0, int(s.attrs.get("held_us", 0))) * 1000
        out.append(Hold(s.start - held, s.start, str(s.attrs.get("cause", "")),
                        str(s.attrs.get("holder", "")), s.attrs))
    return sorted(out, key=lambda h: h.interval)


def _us(span: Span, key: str) -> int:
    """An attribute in microseconds as nanoseconds; -1 where it was never
    stamped."""
    value = span.attrs.get(key, -1)
    return int(value) * 1000 if isinstance(value, (int, float)) \
        and value >= 0 else -1


def _one(spans: Sequence[Span]) -> Optional[Span]:
    return spans[0] if len(spans) == 1 else None


def requests(spans: Sequence[Span], window: Interval
             ) -> Tuple[List[Row], int]:
    """The rows of the ``serve.request`` spans inside the window, by
    arrival, and how many of those spans did not join exactly one of each
    span a row is made of (a request that failed on its way, a program
    without ``serve.replica.wait``)."""
    by_trace: Dict[str, List[Span]] = {}
    for s in spans:
        by_trace.setdefault(s.attrs.get("trace_id", ""), []).append(s)
    executes: Dict[int, List[Span]] = {}
    for s in named(spans, "serve.batch.execute"):
        executes.setdefault(int(s.attrs.get("batch", 0)), []).append(s)
    lingers = sorted(named(spans, "serve.batch.linger"),
                     key=lambda s: s.end)
    calls = named(spans, "serve.batch.call")
    held = merge(h.interval for h in holds(spans))
    rows: List[Row] = []
    unjoined = 0
    mine = sorted(inside(named(spans, "serve.request"), window),
                  key=lambda s: s.start)
    for order, request in enumerate(mine):
        own = by_trace.get(request.attrs.get("trace_id", ""), [])
        route, await_, reply, wait = (
            _one(named(own, name)) for name in (
                "serve.route", "serve.await_replica", "serve.reply", WAIT))
        actor = _one(named(own, "actor.call", {"method": "handle_request"}))
        if not (route and await_ and reply and wait and actor):
            unjoined += 1
            continue
        generated = wait.attrs.get("by") == "generate"
        # its batch: the execute of that ordinal that lay inside its wait
        # (one replica numbers its batches; two replicas' ordinals collide).
        # A request of a generation engine has none: it waits for a slot
        # (``waited_us``) and its call is its time in one.
        execute = None if generated else _one([
            e for e in executes.get(int(wait.attrs.get("batch", -1)), [])
            if e.start >= wait.start and e.end <= wait.end])
        if not (generated or execute):
            unjoined += 1
            continue
        latency = request.end - request.start
        waited = max(0, _us(wait, "waited_us" if generated
                            else "queue_wait_us"))
        stage = {
            "route": route.end - route.start,
            "reply": reply.end - reply.start,
            "mailbox_wait": max(0, _us(actor, "mailbox_wait_us")),
            "queue_wait": waited,
            "call": (wait.end - wait.start - waited if generated
                     else max(0, _us(wait, "call_us"))),
        }
        awaited = await_.end - await_.start
        stage["proxy_self"] = (latency - stage["route"] - awaited
                               - stage["reply"])
        before = [s for s in lingers if execute
                  and s.thread == execute.thread and s.end <= execute.start]
        inner = [c for c in calls if execute and c.thread == execute.thread
                 and c.start >= execute.start and c.end <= execute.end]
        rows.append(Row(
            order=order, start=request.start,
            trace_id=request.attrs.get("trace_id", ""), latency=latency,
            accept_wait=_us(request, "accept_wait_us"),
            other=(awaited - stage["mailbox_wait"] - stage["queue_wait"]
                   - stage["call"]),
            size=int(wait.attrs.get("len" if generated else "size", 0)),
            size_max=int(wait.attrs.get("bucket" if generated
                                        else "size_max", 0)),
            n=int(wait.attrs.get("n", 0)),
            padded_n=int(wait.attrs.get("padded_n", 0)),
            batch=int(wait.attrs.get("batch", 0)),
            cut=str(before[-1].attrs.get("cut", "")) if before else "",
            call_span=sum(c.end - c.start for c in inner),
            held=union_ns(intersect(held, [request.interval])), **stage))
    return rows, unjoined


def tail(rows: Sequence[Row]) -> List[Row]:
    """The slowest tenth of the rows (24 of a steady window's 240; its p95
    is the twelfth), slowest first."""
    take = max(1, int(len(rows) * TAIL_SHARE)) if rows else 0
    return sorted(rows, key=lambda r: -r.latency)[:take]


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile as the load generator takes it: linear
    between the two nearest ranks."""
    ordered = sorted(values)
    at = (len(ordered) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (at - lo)


# -- the device's idle time by what the host had open ------------------------


def _label(span: Span) -> str:
    if span.name == "actor.call":
        return f"actor.call {span.attrs.get('method', '')}".rstrip()
    return span.name


def idle_table(idle: Sequence[Interval], spans: Sequence[Span],
               window: Interval) -> Dict[str, int]:
    """Nanoseconds of the idle intervals by what the host had open: a hold
    before anything (it counts as a span over the interval it stands for,
    and whatever else was open then was held too), else the innermost open
    span: of the spans open at an instant, on whatever thread, the shortest
    (on one thread that is the innermost; across threads the one that says
    most about the instant: a call inside the request that waits for it);
    ``NO_SPAN`` is an instant nothing but a ``PARKED`` span covers.
    Exclusive: the values sum to the idle time inside the window."""
    idle = merge(clip(idle, window))
    held = merge(clip([h.interval for h in holds(spans)], window))
    out: Dict[str, int] = {}
    if held:
        out[HOLD] = union_ns(intersect(idle, held))
        idle = intersect(idle, gaps(held, window))
    open_ = sorted((max(s.start, window[0]), min(s.end, window[1]),
                    s.end - s.start, _label(s))
                   for s in spans if s.name != HOLD and _label(s) not in PARKED
                   and min(s.end, window[1]) > max(s.start, window[0]))
    edges = sorted({window[0], window[1]}
                   | {t for a, b, _, _ in open_ for t in (a, b)})
    heap: List[Tuple[int, int, str]] = []   # (length, end, name) of the open
    at = gap = 0
    for lo, hi in zip(edges, edges[1:]):
        while at < len(open_) and open_[at][0] <= lo:
            _, b, length, name = open_[at]
            heapq.heappush(heap, (length, b, name))
            at += 1
        while heap and heap[0][1] <= lo:
            heapq.heappop(heap)
        name = heap[0][2] if heap else NO_SPAN
        while gap < len(idle) and idle[gap][1] <= lo:
            gap += 1
        k = gap
        while k < len(idle) and idle[k][0] < hi:
            ns = min(hi, idle[k][1]) - max(lo, idle[k][0])
            if ns > 0:
                out[name] = out.get(name, 0) + ns
            k += 1
    return {name: ns for name, ns in out.items() if ns}


# -- reducers: (ctx, p) -> Optional[float] ---------------------------------


def _rows(ctx: Context) -> Optional[List[Row]]:
    """The window's rows; ``None`` where the trace has no
    ``serve.replica.wait`` (a program from before it, a cell with no
    proxy)."""
    if ctx.trace is None:
        return None
    spans = program_spans(ctx)
    if not named(spans, WAIT):
        return None
    return requests(spans, ctx.trace.window)[0] or None


def _measures_holds(spans: Sequence[Span]) -> bool:
    """Whether the program that wrote this trace records holds: it holds
    one, or a span that came with them. A window without a hold then reads
    0 and not nothing."""
    return bool(named(spans, HOLD) or named(spans, WAIT))


def tail_class_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The share of the tail's rows whose class is ``p["class"]``
    (``CLASSES``)."""
    rows = _rows(ctx)
    if rows is None:
        return None
    mine = tail(rows)
    return 100.0 * sum(r.klass() == p["class"] for r in mine) / len(mine)


def tail_stage_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The share of the tail rows' summed latency that the stage
    ``p["stage"]`` took (``STAGES``, or ``"other"``)."""
    rows = _rows(ctx)
    if rows is None:
        return None
    mine = tail(rows)
    whole = sum(r.latency for r in mine)
    return (100.0 * sum(getattr(r, p["stage"]) for r in mine) / whole
            if whole else None)


def request_other_share_pct(ctx: Context, p: Dict[str, Any]
                            ) -> Optional[float]:
    """The median over the window's rows of ``other / latency``: how much of
    a request the spans still do not cover."""
    rows = _rows(ctx)
    if rows is None:
        return None
    return 100.0 * statistics.median(r.other / r.latency for r in rows
                                     if r.latency)


def hold_ms_per_min(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Milliseconds of holds (of the cause ``p["cause"]``, or of any) a
    minute of the window."""
    if ctx.trace is None:
        return None
    spans, window = program_spans(ctx), ctx.trace.window
    if window[1] <= window[0] or not _measures_holds(spans):
        return None
    mine = [h.interval for h in holds(spans)
            if p.get("cause") in (None, h.cause)]
    return (union_ns(clip(mine, window)) * NS * 1e3
            / ((window[1] - window[0]) * NS / 60.0))


def _idle(ctx: Context) -> List[Interval]:
    return gaps([(e.start, e.end) for e in ctx.trace.first.ops],
                ctx.trace.window)


def idle_held_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The share of the window in which the first device is idle and a hold
    is open."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans, window = program_spans(ctx), ctx.trace.window
    if window[1] <= window[0] or not _measures_holds(spans):
        return None
    held = clip([h.interval for h in holds(spans)], window)
    return (100.0 * union_ns(intersect(_idle(ctx), held))
            / (window[1] - window[0]))


def idle_by_span(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """The first device's idle seconds inside the window while the span
    named ``p["span"]`` (``host.hold``, ``"(no span)"``, ``actor.call
    <method>``) is the innermost one open; any serving or training cell.
    ``None`` where the program opened no span at all."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans = program_spans(ctx)
    if not spans:
        return None
    return idle_table(_idle(ctx), spans, ctx.trace.window).get(
        p["span"], 0) * NS


# -- the command line --------------------------------------------------------


def analyse(path: str) -> Dict[str, Any]:
    """Everything the command line prints of one ``.xplane.pb``."""
    from benchmark import harness, trace_reduce
    return describe(trace_reduce.load(path, harness.SPAN_PREFIX,
                                      harness.WINDOW_SPAN),
                    read_spans(path)[1])


def describe(reduced, spans: Sequence[Span]) -> Dict[str, Any]:
    """As plain data: the window, the rows with their classes, the holds
    and the idle table of a reduced trace and its program's spans."""
    window = reduced.window
    rows, unjoined = requests(spans, window)
    slow = {r.order for r in tail(rows)}
    idle = gaps([(e.start, e.end) for e in reduced.first.ops], window)
    return {
        "window_s": (window[1] - window[0]) * NS,
        "requests": len(rows) + unjoined, "unjoined": unjoined,
        "rows": [{**r._asdict(), "start": r.start - window[0],
                  "class": r.klass(), "tail": r.order in slow}
                 for r in rows],
        "holds": [{"at": h.end - window[0], "held": h.end - h.start,
                   "cause": h.cause, "holder": h.holder,
                   "inside": h.end > window[0] and h.start < window[1],
                   **{k: v for k, v in h.attrs.items()
                      if k not in ("trace_id", "span_id", "parent_span_id",
                                   "cause", "holder")}}
                  for h in holds(spans)],
        "idle_s": union_ns(idle) * NS,
        "idle_by_span": {name: ns * NS for name, ns in sorted(
            idle_table(idle, spans, window).items(), key=lambda kv: -kv[1])},
    }


def _ms(ns: float) -> str:
    return f"{ns * NS * 1e3:9.3f}"


def render(found: Dict[str, Any]) -> str:
    """The tables of one run, as text."""
    rows = found["rows"]
    out = [f"window {found['window_s']:.3f} s; {found['requests']} "
           f"serve.request inside it, {found['unjoined']} of them not joined"]
    slow = sorted((r for r in rows if r["tail"]), key=lambda r: -r["latency"])
    if rows:
        lat = [r["latency"] for r in rows]
        out.append(
            f"serve.request ms: p50 {_ms(percentile(lat, 50)).strip()}, "
            f"p95 {_ms(percentile(lat, 95)).strip()}; median other / "
            f"latency {100 * statistics.median(r['other'] / r['latency'] for r in rows):.2f}%")
        out.append(f"\nthe tail: the {len(slow)} slowest of {len(rows)} "
                   "(ms; order = place by arrival)")
        head = ("order", "latency", "accept", *STAGES, "other", "held",
                "size", "size_max", "n", "rows", "cut", "class")
        out.append(" ".join(f"{h:>9}" for h in head))
        for r in slow:
            out.append(" ".join(
                [f"{r['order']:9d}", _ms(r["latency"]),
                 _ms(r["accept_wait"]) if r["accept_wait"] >= 0
                 else f"{'-':>9}"]
                + [_ms(r[s]) for s in STAGES]
                + [_ms(r["other"]), _ms(r["held"]), f"{r['size']:9d}",
                   f"{r['size_max']:9d}", f"{r['n']:9d}",
                   f"{r['padded_n']:9d}", f"{r['cut']:>9}",
                   f"{r['class']:>9}"]))
        whole = sum(r["latency"] for r in slow)
        out.append("\nthe tail's classes: " + ", ".join(
            f"{c} {sum(r['class'] == c for r in slow)}" for c in CLASSES))
        out.append("the tail's latency by stage: " + ", ".join(
            f"{s} {100 * sum(r[s] for r in slow) / whole:.1f}%"
            for s in (*STAGES, "other")))
    held = [h for h in found["holds"] if h["inside"]]
    minutes = found["window_s"] / 60.0
    out.append(f"\nholds inside the window: {len(held)} "
               f"({len(found['holds'])} in the trace)")
    for cause in sorted({h["cause"] for h in held}):
        mine = [h for h in held if h["cause"] == cause]
        out.append(f"  {cause}: {len(mine)}, "
                   f"{sum(h['held'] for h in mine) * NS * 1e3 / minutes:.1f} "
                   f"ms a minute, longest "
                   f"{max(h['held'] for h in mine) * NS * 1e3:.1f} ms")
    for h in held:
        out.append(
            f"  at {h['at'] * NS:8.3f} s held {h['held'] * NS * 1e3:7.1f} ms "
            f"{h['cause']:>9} cpu {h.get('cpu_us', -1)} run_delay "
            f"{h.get('run_delay_us', -1)} throttled "
            f"{h.get('throttled_us', -1)} us gc {h.get('gc_full', -1)} "
            f"majflt {h.get('majflt', -1)} nivcsw {h.get('nivcsw', -1)} "
            f"threads {h.get('threads', -1)} holder {h['holder'] or '-'}")
    out.append(f"\nthe first device idle {found['idle_s']:.3f} s of the "
               "window, by the innermost open span")
    for name, seconds in found["idle_by_span"].items():
        out.append(f"  {seconds:9.3f} s  {name}")
    return "\n".join(out)


if __name__ == "__main__":
    print(render(analyse(sys.argv[1])))
