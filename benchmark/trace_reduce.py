"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals
to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX: planes,
their lines, and events with a start and a duration in nanoseconds. A TPU's
plane (``/device:TPU:<n>``) has a line of operations (``XLA Ops``), on
which an operation that holds others (a ``while`` with its body) spans
them, and a line of whole-program executions (``XLA Modules``). The host's
plane has a line for each thread, and a ``jax.profiler.TraceAnnotation``
appears there under its name, on the same clock.

The arithmetic below works on plain ``Event`` tuples, so synthetic planes
test it on the CPU; ``load`` is the only part that touches a file.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

NS = 1e-9
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # nanoseconds on the trace's clock
    end: int
    category: str = ""  # the operation's HLO category, where the trace has it

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * NS


# -- interval arithmetic ---------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: List[List[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in merge(intervals))


def gaps(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval covers."""
    out, at = [], window[0]
    for a, b in merge(clip(intervals, window)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def overlap_ns(a: Iterable[Interval], b: Iterable[Interval]) -> int:
    """Nanoseconds covered by both unions."""
    a, b = merge(a), merge(b)
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def leaves(events: Sequence[Event]) -> List[Event]:
    """The operations that hold no other: a ``while`` spans its body's
    operations on the same line, and only the body's did the work. Events
    of no duration (bitcasts) neither count nor make a parent of the
    operation they fall in."""
    ordered = sorted((e for e in events if e.end > e.start),
                     key=lambda e: (e.start, -e.end))
    parents, stack = set(), []
    for i, e in enumerate(ordered):
        while stack and ordered[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= ordered[stack[-1]].end:
            parents.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(ordered) if i not in parents]


def exposed_ns(events: Sequence[Event], pattern: re.Pattern = COLLECTIVE
               ) -> int:
    """Time inside collective operations during which no other operation
    runs on that device."""
    def collective(e: Event) -> bool:
        return bool(pattern.search(e.category or e.name))

    ops = leaves(events)
    mine = [(e.start, e.end) for e in ops if collective(e)]
    other = [(e.start, e.end) for e in ops if not collective(e)]
    return union_ns(mine) - overlap_ns(mine, other)


def matching(events: Sequence[Event], patterns: Sequence[str]
             ) -> List[Event]:
    """Leaf operations whose category (their name, where the trace gives
    no category) matches any pattern."""
    regs = [re.compile(p) for p in patterns]
    return [e for e in leaves(events)
            if any(r.search(e.category or e.name) for r in regs)]


# -- one device, one window ------------------------------------------------


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]
    modules: List[Event]

    def busy_ns(self, window: Interval) -> int:
        return union_ns(clip([(e.start, e.end) for e in self.ops], window))

    def executions(self, pattern: str, window: Interval) -> List[Event]:
        """Whole executions of the programs whose name matches, inside the
        window, in order."""
        reg = re.compile(pattern)
        return sorted((m for m in self.modules if reg.search(m.name)
                       and m.start >= window[0] and m.end <= window[1]),
                      key=lambda m: m.start)

    def busy_inside(self, span: Event) -> int:
        return union_ns(clip([(e.start, e.end) for e in self.ops],
                             (span.start, span.end)))

    def ops_inside(self, span) -> List[Event]:
        """The operations that lie wholly inside an event or an
        interval."""
        lo, hi = (span.start, span.end) if isinstance(span, Event) else span
        return [e for e in self.ops if e.start >= lo and e.end <= hi]


@dataclasses.dataclass
class Reduced:
    window: Interval
    devices: Dict[int, DeviceTrace]
    host: List[Event]        # spans of the host's threads, by name

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * NS

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return statistics.fmean(d.busy_ns(self.window)
                                for d in self.devices.values()) * NS

    @property
    def first(self) -> DeviceTrace:
        """The lowest-numbered device (an empty one where the trace has no
        device plane, as on the CPU)."""
        if not self.devices:
            return DeviceTrace([], [])
        return self.devices[min(self.devices)]

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        """The device operations that took most time (leaf operations of
        the first device, summed by name) and the first device's idle time
        by what the host was doing: the seconds of idle gaps that each of
        the benchmark's own spans covers."""
        by_name: Dict[str, float] = {}
        for e in leaves(self.first.ops_inside(self.window)):
            label = f"{e.name} ({e.category})" if e.category else e.name
            by_name[label] = by_name.get(label, 0.0) + e.seconds
        idle = gaps([(e.start, e.end) for e in self.first.ops], self.window)
        spans = self.host
        by_span: Dict[str, float] = {}
        for e in spans:
            ns = overlap_ns([(e.start, e.end)], idle)
            if ns:
                by_span[e.name] = by_span.get(e.name, 0.0) + ns * NS
        unnamed = union_ns(idle) - overlap_ns(
            [(e.start, e.end) for e in spans], idle)
        if unnamed:
            by_span["(no span)"] = unnamed * NS

        def ranked(d: Dict[str, float]) -> List[List[object]]:
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(by_name), "idle_gaps": ranked(by_span)}


# -- reading a file --------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str) -> Tuple[str, str]:
    """A device operation's event is named by its whole HLO text,
    ``%name = shape opcode(operands), attributes``. Returns the short name
    and the category: the opcode, and for a custom call its target
    (``custom-call tpu_custom_call`` is a Mosaic kernel). Matching goes by
    these and never by the operands, which name other operations."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    target = _TARGET.search(rest) if opcode == "custom-call" else None
    return head.lstrip("%"), (f"{opcode} {target.group(1)}" if target
                              else opcode)


def _events(line, parse: bool = False) -> List[Event]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        name, category = parse_op(ev.name) if parse else (ev.name, "")
        out.append(Event(name, start, start + int(ev.duration_ns), category))
    return out


def load(path: str, span_prefix: str, window_span: str) -> Reduced:
    """Read one ``.xplane.pb``. Of the host's events it keeps those whose
    name starts with ``span_prefix`` (the benchmark's own spans), without
    the prefix. The window is the span named ``window_span``; without one
    it is everything the devices did."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    host: List[Event] = []
    window: Optional[Interval] = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line, parse=True)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            devices[int(m.group(1))] = DeviceTrace(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(span_prefix):
                        continue
                    start = int(ev.start_ns)
                    span = Event(ev.name[len(span_prefix):], start,
                                 start + int(ev.duration_ns))
                    if span.name == window_span:
                        window = window or (span.start, span.end)
                    else:
                        host.append(span)
    if window is None:
        every = [e for d in devices.values() for e in d.ops]
        window = ((min(e.start for e in every), max(e.end for e in every))
                  if every else (0, 0))
    return Reduced(window, devices, host)


def describe(path: str, top: int = 25) -> str:
    """What is in a trace, for a reader who has not seen one: planes, lines,
    counts and the most frequent event names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            total: Dict[str, Tuple[int, float]] = {}
            for ev in events:
                n, s = total.get(ev.name, (0, 0.0))
                total[ev.name] = (n + 1, s + ev.duration_ns * NS)
            for name, (n, s) in sorted(total.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {n:7d} x {s:10.6f} s  {name[:110]}")
            if events:
                ev = events[0]
                out.append(f"    first event start {ev.start_ns} ns; stats "
                           f"{[(k, str(v)[:40]) for k, v in ev.stats][:12]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
