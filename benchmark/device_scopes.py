"""Where a step's device time goes by layer: a reader of the scopes the
model enters on the device (``ray_tpu/observability/metric_names.py``,
``DEVICE_SCOPES``).

``jax.named_scope`` lands in an operation's name stack; the compiler keeps
that as the operation's ``op_name`` and the TPU's profiler writes it to
the ``.xplane.pb`` as ``tf_op``, beside XLA's own ``hlo_category``,
``flops``, ``bytes_accessed`` and ``source``. They are stats of the
event's *metadata* record (``XEventMetadata.stats``), which
``jax.profiler.ProfileData`` does not give: its ``event.stats`` yields the
stats stored on the event alone (offset, duration, a multiplier). So
``read_trace`` decodes the file's protobuf wire format itself and joins
each ``XLA Ops`` event to its record by ``metadata_id``, never by the HLO
text: two programs of one run number their fusions alike.

A reader returns ``None`` where the trace names no scope (a program from
before the scopes, a CPU run with no device plane) and the harness leaves
the metric out. The arithmetic works on plain ``Op`` tuples, so a plane
the tests encode themselves checks it on the CPU; ``read_trace`` is the
only part that touches a file.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import struct
import sys
import tempfile
import time
from typing import (Any, Dict, FrozenSet, Iterator, List, NamedTuple,
                    Optional, Sequence, Tuple)

from benchmark.program_spans import WINDOW_EVENT
from benchmark.reducers import Context
from benchmark.trace_reduce import (DEVICE_PLANE, NS, OPS_LINE, Interval,
                                    leaves)

UNSCOPED = "unscoped"                # ``params.scope`` of the class None
# the scopes a note shows inside their parent's row
INNER = {"attn": ("core",), "moe": ("router", "experts")}
NOTE_HEAD = "device time by scope"
# JAX leaves an operation's metadata out of the compile cache's key, so a
# cache filled by a program from before the scopes hands their twin its
# own executables, old names and all
STALE_NOTE = ("device_scopes: the program declares scopes and the trace "
              "names none: its executables came from a compile cache "
              "filled before the scopes (metadata is not in the key)")


class OpRecord(NamedTuple):
    """What the trace says of one operation of a program, once: the stats
    of its ``XEventMetadata``."""
    name: str                 # the operation's HLO text
    tf_op: str                # the jaxpr's name stack; "" for compiler-made
    category: str             # hlo_category
    flops: int                # XLA's own count; 0 for a Mosaic call
    bytes_accessed: int
    source: str               # file:line of the Python that made it


class Op(NamedTuple):
    start: int                # nanoseconds on the trace's clock
    end: int
    record: OpRecord

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * NS


# -- the wire format -------------------------------------------------------
# tensorflow/tsl/profiler/protobuf/xplane.proto, by field number


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: a varint's
    number, a fixed field's bytes, a length-delimited field's bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} is not in an xplane")
        yield key >> 3, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One ``XStat``: its name and its value, a ``ref_value`` resolved to
    the string it names."""
    name, value = "", None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, "")
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = v.decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _record(buf: bytes, stat_names: Dict[int, str]) -> OpRecord:
    name, stats = "", {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = v.decode("utf-8", "replace")
        elif number == 5:
            key, value = _stat(v, stat_names)
            stats[key] = value
    return OpRecord(name, str(stats.get("tf_op") or ""),
                    str(stats.get("hlo_category") or ""),
                    int(stats.get("flops") or 0),
                    int(stats.get("bytes_accessed") or 0),
                    str(stats.get("source") or ""))


def _line_head(buf: bytes) -> Tuple[str, int]:
    """A line's name and the nanosecond its events' offsets count from."""
    name, timestamp_ns = "", 0
    for number, _, v in _fields(buf):
        if number == 2:
            name = v.decode("utf-8", "replace")
        elif number == 3:
            timestamp_ns = _signed(v)
    return name, timestamp_ns


def _line_events(buf: bytes, timestamp_ns: int
                 ) -> Iterator[Tuple[int, int, int]]:
    """``(metadata_id, start, end)`` of a line's events in whole
    nanoseconds, rounded as ``trace_reduce.load`` rounds what
    ``ProfileData`` gives it. The one loop that sees every event of a
    trace, so it reads an event's three varints in place."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        if key & 7 != 2:                    # a line's own scalar fields
            if key & 7 == 0:
                _, at = _varint(buf, at)
            else:
                at += 8 if key & 7 == 1 else 4
            continue
        size, at = _varint(buf, at)
        stop = at + size
        if key >> 3 != 4:                   # its names
            at = stop
            continue
        metadata_id = offset_ps = duration_ps = 0
        while at < stop:
            tag = buf[at]                   # an event's fields are below 16
            at += 1
            if tag & 7 == 0:
                value, at = _varint(buf, at)
                if tag == 8:
                    metadata_id = value
                elif tag == 16:
                    offset_ps = value
                elif tag == 24:
                    duration_ps = value
            elif tag & 7 == 2:              # its own stats: not read here
                size, at = _varint(buf, at)
                at += size
            else:
                at += 8 if tag & 7 == 1 else 4
        start = int(timestamp_ns + offset_ps / 1000.0)
        yield metadata_id, start, start + int(duration_ps / 1000.0)


def _plane(buf: bytes):
    name, lines, metadata, stat_names = "", [], {}, {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = v.decode("utf-8", "replace")
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            metadata[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (s.decode("utf-8", "replace")
                 for n, _, s in _fields(value) if n == 2), "")
    return name, lines, metadata, stat_names


def _planes(path: str) -> Iterator[Tuple[str, bytes]]:
    """The name and the bytes of every plane of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = f.read()
    for number, _, plane in _fields(space):
        if number == 1:
            # a plane's name comes before its lines; peek at it alone
            yield next((v.decode("utf-8", "replace")
                        for n, _, v in _fields(plane) if n == 2), ""), plane


@functools.lru_cache(maxsize=None)
def trace_window(path: str) -> Optional[Interval]:
    """The benchmark's window alone (the first ``bench.window`` event of
    the host's planes, as ``trace_reduce.load`` takes it): no operation is
    decoded, so looking at another run's file costs little."""
    for name, plane in _planes(path):
        if name.startswith("/host:"):
            window = _window(plane)
            if window is not None:
                return window
    return None


@functools.lru_cache(maxsize=None)
def read_trace(path: str) -> Tuple[Optional[Interval], Tuple[Op, ...]]:
    """One ``.xplane.pb``: the benchmark's window and the ``XLA Ops``
    events of the lowest-numbered device, each joined to its metadata
    record."""
    first: Optional[Tuple[int, bytes]] = None
    for name, plane in _planes(path):
        device = DEVICE_PLANE.match(name)
        if device and (first is None or int(device.group(1)) < first[0]):
            first = (int(device.group(1)), plane)
    return trace_window(path), (tuple(_ops(first[1])) if first else ())


def _window(plane: bytes) -> Optional[Interval]:
    _, lines, metadata, _ = _plane(plane)
    ids = {key for key, value in metadata.items()
           if any(n == 2 and v == WINDOW_EVENT.encode()
                  for n, _, v in _fields(value))}
    if not ids:
        return None
    for line in lines:
        for metadata_id, start, end in _line_events(line,
                                                    _line_head(line)[1]):
            if metadata_id in ids:
                return (start, end)
    return None


def _ops(plane: bytes) -> Iterator[Op]:
    _, lines, metadata, stat_names = _plane(plane)
    records: Dict[int, OpRecord] = {}
    for line in lines:
        name, timestamp_ns = _line_head(line)
        if name != OPS_LINE:
            continue
        for metadata_id, start, end in _line_events(line, timestamp_ns):
            record = records.get(metadata_id)
            if record is None:
                record = records[metadata_id] = _record(
                    metadata.get(metadata_id, b""), stat_names)
            yield Op(start, end, record)


def read_ops(path: str) -> Tuple[Op, ...]:
    """The first device's ``XLA Ops`` events of one ``.xplane.pb``."""
    return read_trace(path)[1]


# -- the run's trace -------------------------------------------------------


def find_trace(window: Interval) -> Optional[str]:
    """The path of the run whose window this is, found as
    ``program_spans.find_trace`` finds it: among the harness's trace
    directories, which still stand while the readers run, the file whose
    ``bench.window`` event equals the reduced trace's window to the
    nanosecond."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "plugins",
                           "profile", "*", "*.xplane.pb")
    for path in glob.glob(pattern):
        try:
            if trace_window(path) == window:
                return path
        except (OSError, ValueError, IndexError, struct.error):
            continue        # another run's file, half written or gone
    return None


def program_scopes() -> FrozenSet[str]:
    """The scopes the program says it enters; none for a program from
    before them."""
    from ray_tpu.observability import metric_names
    return frozenset(getattr(metric_names, "DEVICE_SCOPES", ()))


# -- from operations to classes --------------------------------------------

_SEPARATORS = re.compile(r"[/():]")


@functools.lru_cache(maxsize=None)
def scope_path(tf_op: str, scopes: FrozenSet[str]) -> Tuple[str, ...]:
    """The scopes on an operation's name stack, outermost first: the path
    split on ``/``, ``(`` and ``)`` (a backward operation's stack sits
    inside ``transpose(jvp(...))``), the tokens that are scopes kept.
    Remembered: a trace's operations share a few hundred ``tf_op``s."""
    return tuple(t for t in _SEPARATORS.split(tf_op) if t in scopes)


def scope_of(tf_op: str, scopes: FrozenSet[str]) -> Optional[str]:
    """An operation's class: the first token of its path that is a scope,
    ``None`` where there is none. A fusion takes the class of the one
    operation XLA kept as its name (the matmul of a convolution fusion,
    the root of a loop fusion), so what it fused across a scope's edge is
    counted with that operation: the method's known blur."""
    path = scope_path(tf_op, scopes)
    return path[0] if path else None


def window_leaves(ops: Sequence[Op], window: Interval) -> List[Op]:
    """The leaf operations that lie wholly inside the window: a ``while``
    spans its body's operations on the same line and carries no ``tf_op``;
    only the body's did the work."""
    lo, hi = window
    return leaves([op for op in ops if op.start >= lo and op.end <= hi])


class Row(NamedTuple):
    seconds: float = 0.0
    flops: int = 0
    bytes_accessed: int = 0
    count: int = 0

    def plus(self, op: Op) -> "Row":
        return Row(self.seconds + op.seconds, self.flops + op.record.flops,
                   self.bytes_accessed + op.record.bytes_accessed,
                   self.count + 1)


NO_TF_OP, NO_SCOPE = "no tf_op", "tf_op names no scope"


def by_class(ops: Sequence[Op], scopes: FrozenSet[str]
             ) -> Dict[Tuple[str, ...], Row]:
    """Leaf operations summed by class. Keys: ``(scope,)`` for a class,
    ``(scope, inner)`` for the part of it under one of ``INNER``'s scopes,
    ``(UNSCOPED,)`` for the class ``None`` and ``(UNSCOPED, kind)`` for its
    two kinds: an operation the compiler made (no ``tf_op`` at all) and
    one whose ``tf_op`` names no scope (an annotation still missing)."""
    rows: Dict[Tuple[str, ...], Row] = {}

    def add(key: Tuple[str, ...], op: Op) -> None:
        rows[key] = rows.get(key, Row()).plus(op)

    for op in ops:
        path = scope_path(op.record.tf_op, scopes)
        if not path:
            add((UNSCOPED,), op)
            add((UNSCOPED, NO_SCOPE if op.record.tf_op else NO_TF_OP), op)
            continue
        add(path[:1], op)
        inner = next((t for t in path[1:] if t in INNER.get(path[0], ())),
                     None)
        if inner:
            add((path[0], inner), op)
    return rows


def table(ops: Sequence[Op], scopes: FrozenSet[str], top: int = 5) -> str:
    """The classes of ``by_class`` as lines of text: seconds, share of the
    leaves' time, XLA's own ``flops`` and ``bytes_accessed`` over the time
    (a Mosaic call counts 0 of either, so a class that holds one reads
    low: notes, no roofline), then the largest unscoped operations."""
    rows = by_class(ops, scopes)
    total = sum(op.seconds for op in ops)
    out = [f"{NOTE_HEAD} ({len(ops)} leaf operations, {total:.6f} s):",
           f"  {'class':<28}{'ops':>8}{'seconds':>12}{'share %':>9}"
           f"{'TFLOP/s':>9}{'GB/s':>8}"]
    order = sorted((k for k in rows if len(k) == 1 and k[0] != UNSCOPED),
                   key=lambda k: -rows[k].seconds) + [(UNSCOPED,)]
    for head in order:
        for key in [head] + sorted(k for k in rows
                                   if len(k) == 2 and k[0] == head[0]):
            row = rows.get(key, Row())
            label = key[0] if len(key) == 1 else "  " + key[1]
            rate = 1.0 / row.seconds if row.seconds else 0.0
            out.append(
                f"  {label:<28}{row.count:>8}{row.seconds:>12.6f}"
                f"{100.0 * row.seconds / total if total else 0.0:>9.2f}"
                f"{row.flops * rate / 1e12:>9.1f}"
                f"{row.bytes_accessed * rate / 1e9:>8.1f}")
    summed: Dict[OpRecord, Row] = {}
    for op in ops:
        if scope_of(op.record.tf_op, scopes) is None:
            summed[op.record] = summed.get(op.record, Row()).plus(op)
    out.append(f"  the {top} largest unscoped operations:")
    for record, row in sorted(summed.items(),
                              key=lambda kv: -kv[1].seconds)[:top]:
        out.append(f"    {row.seconds:.6f} s x{row.count} "
                   f"{record.name.split(' = ')[0]} ({record.category}) "
                   f"tf_op={record.tf_op or '-'} "
                   f"source={record.source or '-'}")
    return "\n".join(out)


# -- the reader ------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _run_leaves(window: Interval) -> Optional[Tuple[Op, ...]]:
    path = find_trace(window)
    if path is None:
        return None
    return tuple(window_leaves(read_trace(path)[1], window))


@functools.lru_cache(maxsize=None)
def _run_rows(window: Interval, scopes: FrozenSet[str]
              ) -> Dict[Tuple[str, ...], Row]:
    """The run's classes, summed once however many metrics read them."""
    return by_class(_run_leaves(window) or (), scopes)


def scope_share_pct(ctx: Context, p: Dict[str, Any]) -> Optional[float]:
    """Over the leaf operations of the first device inside the window: the
    device time of the class ``p["scope"]`` (``scope_of``; ``"unscoped"``
    is the class ``None``) over the device time of all leaves, in per
    cent. ``None`` where no leaf names a scope at all: a program from
    before the scopes, or a trace without ``tf_op``. The first call of a
    run leaves the whole table as a note."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    began = time.perf_counter()
    window, scopes = tuple(ctx.trace.window), program_scopes()
    rows = _run_rows(window, scopes)
    if not any(key != (UNSCOPED,) for key in rows if len(key) == 1):
        if scopes and rows and STALE_NOTE not in ctx.notes:
            ctx.notes.append(STALE_NOTE)
        return None
    if not any(note.startswith(NOTE_HEAD) for note in ctx.notes):
        ctx.notes.append(table(_run_leaves(window), scopes))
        ctx.notes.append(f"device_scopes: decoded and summed in "
                         f"{time.perf_counter() - began:.2f} s")
    total = sum(row.seconds for key, row in rows.items() if len(key) == 1)
    mine = rows.get((p["scope"],), Row()).seconds
    return 100.0 * mine / total if total else None


if __name__ == "__main__":
    _window_of, _ops_of = read_trace(sys.argv[1])
    if _window_of is None:      # no benchmark's window: all the device did
        _window_of = (min(op.start for op in _ops_of),
                      max(op.end for op in _ops_of))
    print(table(window_leaves(_ops_of, _window_of), program_scopes()))
