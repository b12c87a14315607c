"""Low-overhead periodic stack sampler (pure-Python, per process).

A daemon thread wakes at ``perf_sampler_hz`` and walks
``sys._current_frames()``, folding each thread's stack into a
``file:func;file:func;...`` string (root first) and bumping its count.
Cost per tick is a few frame-pointer chases per live thread — at the
default ~19 Hz that is well under the 2% overhead budget enforced by
``bench_micro.py``'s ``sampler_overhead_pct`` row.

Trace tagging: when :data:`TAGGING` is on, ``observability.span`` pushes
the active trace id into a per-thread stack here on enter and pops on
exit; a sample that lands while a thread is inside a span is attributed
to that trace.  The hooks are two dict operations and only run when a
sampler wants them, so tracing's own overhead budget is unaffected.

Profiles are cumulative since :func:`start` (or the last
:func:`reset`).  Windowed profiles — ``/api/profile?seconds=N`` — are
computed by the dashboard head as the difference of two cumulative
snapshots, which keeps this module free of timers and the wire protocol
free of new fields.

Holds: the tick also measures its own lateness.  A tick that wakes
:data:`HOLD_S` or more after it was due *is* a hold of this process, seen
from inside it, by the thread that already knows what every other thread
is doing.  Each tick reads a few clocks (:class:`_HostClocks`) and keeps
them from the tick before, so that a hold can be put down to a cause
(:func:`classify_hold`); a hold goes to the two sinks the process already
has: a ``host.hold`` span (the ring under ``tracing_enabled``, a
``ray_tpu.host.hold`` annotation in a profiler session) and the counters
``host_holds_total{cause}`` / ``host_hold_seconds_total{cause}``.  Nothing
of it runs on any other thread, and nothing at all where the sampler is
off.
"""

from __future__ import annotations

import gc
import os
import resource
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from ray_tpu._private.config import _config
from ray_tpu.observability.metric_names import (HOST_HOLD_SECONDS,
                                                 HOST_HOLDS)

# Flipped by start()/stop(); observability.span consults it before
# touching the trace-stack map so span cost stays flat when no sampler
# is running.
TAGGING: bool = False

# tid -> stack of active trace ids for that thread.  Mutated only by the
# owning thread (span enter/exit), read by the sampler thread; every
# operation is a single dict/list op under the GIL.
_trace_stacks: Dict[int, List[str]] = {}


def note_span_enter(trace_id: str) -> None:
    _trace_stacks.setdefault(threading.get_ident(), []).append(trace_id)  # raylint: allow(data-race) single dict/list op under the GIL (see module note); the sampler reads a best-effort snapshot


def note_span_exit() -> None:
    tid = threading.get_ident()
    stack = _trace_stacks.get(tid)
    if stack:
        stack.pop()
        if not stack:
            _trace_stacks.pop(tid, None)  # raylint: allow(data-race) single dict op under the GIL (see module note); the sampler reads a best-effort snapshot


_MAX_DEPTH = 64

# A tick that wakes this late or later is a hold.  PR 36's watcher read
# skips of 0.10-0.13 s as the common ones; at the default 19 Hz a tick
# cannot tell less than one interval (52.6 ms).
HOLD_S = 0.05
# The frames of the holder's stack a hold carries, leaf-most.
_HOLDER_FRAMES = 8

CAUSES = ("throttled", "gc", "gil", "runqueue", "off_cpu")


def classify_hold(held_us: int, cpu_us: int, run_delay_us: int,
                  throttled_us: int, gc_full: int) -> str:
    """What a hold of ``held_us`` is put down to: the first of these that
    covers HALF the hold, in THIS order (a source the platform lacks reads
    -1 and covers nothing):

    ``throttled``  the cgroup's quota kept every thread off the cores for
                   ``throttled_us``; first, because a throttled process is
                   neither runnable nor on a core whatever else it did;
    ``gc``         the process was on a core for half the hold (``cpu_us``,
                   the whole process's CPU time since the tick before) and
                   a full collection ended inside it (``gc_full`` > 0);
    ``gil``        on a core with no full collection, so another thread kept
                   the interpreter: the hold's ``holder`` names it;
    ``runqueue``   the sampler's thread was runnable and no core took it
                   (``run_delay_us``); after the two above, because a thread
                   that waits for the interpreter is not runnable;
    ``off_cpu``    none of them: page faults (``majflt`` says), a stopped
                   process, the hypervisor.
    """
    half = held_us / 2.0
    if throttled_us >= half:
        return "throttled"
    if cpu_us >= half:
        return "gc" if gc_full > 0 else "gil"
    if run_delay_us >= half:
        return "runqueue"
    return "off_cpu"


class _Reading(NamedTuple):
    """What one tick read of the clocks; -1 where the platform has none."""
    cpu_s: float            # time.process_time(): the whole process
    run_delay_ns: int       # this thread runnable and waiting for a core
    throttled_us: int       # the cgroup's throttled time
    gc_full: int            # full collections so far
    majflt: int
    nivcsw: int
    thread_cpu_s: Dict[int, float]  # thread ident -> its CPU clock


def _cpu_stat_paths() -> List[str]:
    """Where this process's cgroup keeps ``cpu.stat``: the v1 ``cpu``
    controller's directory, then the unified hierarchy's."""
    v1: List[str] = []
    v2: List[str] = []
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, controllers, path = line.rstrip("\n").split(":", 2)
                path = path.strip("/")
                if "cpu" in controllers.split(","):
                    v1.append(os.path.join("/sys/fs/cgroup", controllers,
                                           path, "cpu.stat"))
                elif not controllers:
                    v2.append(os.path.join("/sys/fs/cgroup", path,
                                           "cpu.stat"))
    except (OSError, ValueError):
        pass
    return v1 + v2 + ["/sys/fs/cgroup/cpu/cpu.stat",
                      "/sys/fs/cgroup/cpu.stat"]


def _thread_cpu_clock(native_id: int) -> int:
    """The CPU clock of the thread with that kernel id: what
    ``time.pthread_getcpuclockid`` returns for it (the kernel's
    ``(~tid << 3) | CPUCLOCK_PERTHREAD_MASK | CPUCLOCK_SCHED``), worked
    out from the id itself.  ``pthread_getcpuclockid`` reads the thread's
    descriptor, which is freed memory once a detached thread has exited,
    and a thread can exit between ``sys._current_frames()`` and the call;
    ``clock_gettime`` on the clock of a thread that is gone fails with
    ``EINVAL`` and touches nothing."""
    return (~native_id << 3) | 6


# ``cpu.stat``'s throttled time: the field's name and what brings it to
# microseconds (v2 counts ``throttled_usec``, v1 ``throttled_time`` in
# nanoseconds).
_THROTTLED_FIELDS = ((b"throttled_usec", 1), (b"throttled_time", 1000))


class _HostClocks:
    """The sources a tick reads, opened once on the sampler's own thread
    (``/proc/thread-self`` is the opener's) and read with ``os.pread``.  A
    source the platform lacks reads -1 and is never an error."""

    def __init__(self):
        self._schedstat = self._open(["/proc/thread-self/schedstat"],
                                     None)
        self._cpu_stat = self._open(_cpu_stat_paths(), b"throttled_")

    @staticmethod
    def _open(paths: List[str], needs: Optional[bytes]) -> int:
        for path in paths:
            try:
                fd = os.open(path, os.O_RDONLY | os.O_CLOEXEC)
            except OSError:
                continue
            try:
                if needs is None or needs in os.pread(fd, 4096, 0):
                    return fd
            except OSError:
                pass
            os.close(fd)
        return -1

    @property
    def fds(self) -> List[int]:
        return [fd for fd in (self._schedstat, self._cpu_stat) if fd >= 0]

    def close(self) -> None:
        for fd in self.fds:
            os.close(fd)
        self._schedstat = self._cpu_stat = -1  # raylint: allow(data-race) the sampler's thread alone opens, reads and closes them; another thread only ever peeks at `fds`

    def _run_delay_ns(self) -> int:
        if self._schedstat < 0:
            return -1
        try:
            return int(os.pread(self._schedstat, 128, 0).split()[1])
        except (OSError, IndexError, ValueError):
            return -1

    def _throttled_us(self) -> int:
        if self._cpu_stat < 0:
            return -1
        try:
            fields = os.pread(self._cpu_stat, 4096, 0).split()
            for name, per_us in _THROTTLED_FIELDS:
                if name in fields:
                    return int(fields[fields.index(name) + 1]) // per_us
        except (OSError, IndexError, ValueError):
            pass
        return -1

    def read(self, tids) -> _Reading:
        """One tick's readings; ``tids`` are the threads whose CPU clocks
        are wanted (idents, as ``sys._current_frames()`` keys them)."""
        usage = resource.getrusage(resource.RUSAGE_SELF)
        thread_cpu: Dict[int, float] = {}
        for th in threading.enumerate():
            if th.ident in tids and th.native_id:
                try:
                    thread_cpu[th.ident] = time.clock_gettime(
                        _thread_cpu_clock(th.native_id))
                except OSError:  # it exited since the frames were taken
                    pass
        return _Reading(time.process_time(), self._run_delay_ns(),
                        self._throttled_us(),
                        gc.get_stats()[2]["collections"],
                        usage.ru_majflt, usage.ru_nivcsw, thread_cpu)


def _since(now: int, before: int, scale: float = 1.0) -> int:
    """``now - before`` of a source that reads -1 where there is none."""
    return -1 if now < 0 or before < 0 else int((now - before) * scale)


class _HoldCounters:
    """The two counters, one of each in the process's registry."""
    _lock = threading.Lock()
    _made: Optional["_HoldCounters"] = None

    def __init__(self):
        from ray_tpu.util.metrics import Counter
        self.holds = Counter(
            HOST_HOLDS, "Sampler ticks that woke HOLD_S late or later: "
            "holds of this process, by cause", tag_keys=("cause",))
        self.seconds = Counter(
            HOST_HOLD_SECONDS, "Seconds this process was held, by cause",
            tag_keys=("cause",))

    @classmethod
    def get(cls) -> "_HoldCounters":
        with cls._lock:
            if cls._made is None:
                cls._made = cls()
            return cls._made


def _fold(frame) -> List[str]:
    """A thread's stack as ``file:func`` parts, root first."""
    parts: List[str] = []
    depth = 0
    while frame is not None and depth < _MAX_DEPTH:
        code = frame.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{code.co_name}")
        frame = frame.f_back
        depth += 1
    parts.reverse()
    return parts


class StackSampler:
    """One sampling thread; counts keyed (folded stack, trace id)."""

    def __init__(self, hz: float):
        self.hz = float(hz)
        self._counts: Dict[Tuple[str, str], int] = {}  # raylint: guarded-by(self._lock)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started_s = 0.0
        self._ticks = 0  # raylint: guarded-by(self._lock)
        # the sampler's thread opens and closes them
        self._clocks: Optional[_HostClocks] = None

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "StackSampler":
        if self._thread is not None:
            return self
        self._started_s = time.time()
        self._thread = threading.Thread(
            target=self._run, name="perf-sampler", daemon=True)
        self._thread.start()
        global TAGGING
        TAGGING = True
        return self

    def stop(self) -> None:
        global TAGGING
        TAGGING = False
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None

    # -- sampling loop ---------------------------------------------------

    def _run(self) -> None:
        interval = 1.0 / max(self.hz, 0.1)
        me = threading.get_ident()
        clocks = self._clocks = _HostClocks()
        try:
            before = clocks.read(set(sys._current_frames()))
            due = time.monotonic() + interval
            while not self._stop.wait(interval):
                late = time.monotonic() - due
                stacks = self._sample_once(me)
                reading = clocks.read(stacks)
                if late >= HOLD_S:
                    self._record_hold(late, before, reading, stacks)
                before = reading
                due = time.monotonic() + interval
        finally:
            clocks.close()

    def _sample_once(self, skip_tid: int) -> Dict[int, List[str]]:
        """One walk over every other thread's stack, counted; returns the
        stacks by thread, folded into parts."""
        frames = sys._current_frames()
        stacks = {tid: _fold(frame) for tid, frame in frames.items()
                  if tid != skip_tid}
        del frames
        rows: List[Tuple[str, str]] = []
        for tid, parts in stacks.items():
            stack = _trace_stacks.get(tid)
            rows.append((";".join(parts), stack[-1] if stack else ""))
        with self._lock:
            self._ticks += 1
            for key in rows:
                self._counts[key] = self._counts.get(key, 0) + 1
        return stacks

    def _record_hold(self, late_s: float, before: _Reading, now: _Reading,
                     stacks: Dict[int, List[str]]) -> None:
        """This tick woke ``late_s`` after it was due: what the clocks say
        of the time since the tick before, the thread that spent most CPU
        in it, and the cause, to both sinks."""
        held_us = int(late_s * 1e6)
        cpu_us = int((now.cpu_s - before.cpu_s) * 1e6)
        run_delay_us = _since(now.run_delay_ns, before.run_delay_ns, 1e-3)
        throttled_us = _since(now.throttled_us, before.throttled_us)
        gc_full = now.gc_full - before.gc_full
        cause = classify_hold(held_us, cpu_us, run_delay_us, throttled_us,
                              gc_full)
        spent = {tid: cpu - before.thread_cpu_s.get(tid, 0.0)
                 for tid, cpu in now.thread_cpu_s.items()}
        busiest = max(spent, key=spent.get, default=None)
        holder = ""
        if busiest is not None and spent[busiest] >= late_s / 10.0:
            holder = ";".join(stacks[busiest][-_HOLDER_FRAMES:])
        counters = _HoldCounters.get()
        counters.holds.inc(tags={"cause": cause})
        counters.seconds.inc(late_s, tags={"cause": cause})
        # Opened and closed at the wake: the hold began on no thread's
        # clock but the kernel's, so it is a number on the span that ends
        # it, and the held interval is [start - held_us, start].
        from ray_tpu import observability
        with observability.span(
                "host.hold", cat="host", held_us=held_us, cpu_us=cpu_us,
                run_delay_us=run_delay_us, throttled_us=throttled_us,
                gc_full=gc_full, majflt=now.majflt - before.majflt,
                nivcsw=now.nivcsw - before.nivcsw, threads=len(stacks),
                holder=holder, cause=cause):
            pass

    # -- read side -------------------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            samples = [{"stack": k[0], "trace": k[1], "count": c}
                       for k, c in sorted(self._counts.items())]
            ticks = self._ticks
        return {
            "hz": self.hz,
            "ticks": ticks,
            "since_s": self._started_s,
            "duration_s": (time.time() - self._started_s
                           if self._started_s else 0.0),
            "samples": samples,
        }

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._ticks = 0
            self._started_s = time.time()


# -- profile post-processing (also used head-side on federated dicts) --------


def diff_profiles(newer: Dict[str, object],
                  older: Dict[str, object]) -> Dict[str, object]:
    """``newer - older`` per (stack, trace) key: the samples that landed
    in the window between two cumulative snapshots."""
    base: Dict[Tuple[str, str], int] = {
        (str(s["stack"]), str(s.get("trace", ""))): int(s["count"])
        for s in older.get("samples", [])}  # type: ignore[union-attr]
    out = []
    for s in newer.get("samples", []):  # type: ignore[union-attr]
        key = (str(s["stack"]), str(s.get("trace", "")))
        delta = int(s["count"]) - base.get(key, 0)
        if delta > 0:
            out.append({"stack": key[0], "trace": key[1], "count": delta})
    return {
        "hz": newer.get("hz"),
        "ticks": int(newer.get("ticks", 0)) - int(older.get("ticks", 0)),
        "duration_s": (float(newer.get("duration_s", 0.0))
                       - float(older.get("duration_s", 0.0))),
        "samples": out,
    }


def merge_profiles(parts: List[Dict[str, object]]) -> Dict[str, object]:
    """Sum same-keyed samples across processes/hosts."""
    counts: Dict[Tuple[str, str], int] = {}
    ticks = 0
    for p in parts:
        ticks += int(p.get("ticks", 0))
        for s in p.get("samples", []):  # type: ignore[union-attr]
            key = (str(s["stack"]), str(s.get("trace", "")))
            counts[key] = counts.get(key, 0) + int(s["count"])
    return {"ticks": ticks,
            "samples": [{"stack": k[0], "trace": k[1], "count": c}
                        for k, c in sorted(counts.items())]}


def collapsed(profile: Dict[str, object]) -> str:
    """Brendan-Gregg collapsed-stack text (``stack count`` per line),
    trace tags folded together — feed straight to flamegraph.pl."""
    agg: Dict[str, int] = {}
    for s in profile.get("samples", []):  # type: ignore[union-attr]
        agg[str(s["stack"])] = agg.get(str(s["stack"]), 0) + int(s["count"])
    return "\n".join(f"{stack} {c}" for stack, c in sorted(agg.items()))


def pprof_json(profile: Dict[str, object]) -> Dict[str, object]:
    """pprof-shaped JSON: sample_type header + location-list samples."""
    samples = []
    for s in profile.get("samples", []):  # type: ignore[union-attr]
        row: Dict[str, object] = {
            "location": str(s["stack"]).split(";"),
            "value": [int(s["count"])],
        }
        if s.get("trace"):
            row["trace_id"] = s["trace"]
        samples.append(row)
    return {"sample_type": [{"type": "samples", "unit": "count"}],
            "period": (1.0 / float(profile["hz"])
                       if profile.get("hz") else None),
            "samples": samples}


# -- process-wide singleton --------------------------------------------------

_sampler: Optional[StackSampler] = None
_sampler_lock = threading.Lock()


def start(hz: Optional[float] = None) -> Optional[StackSampler]:
    """Start (or return) the process sampler.  ``hz`` defaults to the
    ``perf_sampler_hz`` knob; <= 0 disables and returns None."""
    global _sampler
    if hz is None:
        hz = float(_config.get("perf_sampler_hz"))
    if hz <= 0:
        return None
    with _sampler_lock:
        if _sampler is None:
            _sampler = StackSampler(hz).start()  # raylint: allow(data-race) get_sampler's unlocked peek is a GIL-atomic read of the singleton
        return _sampler


def stop() -> None:
    global _sampler
    with _sampler_lock:
        s = _sampler
        _sampler = None  # raylint: allow(data-race) get_sampler's unlocked peek is a GIL-atomic read of the singleton
    if s is not None:
        s.stop()


def get_sampler() -> Optional[StackSampler]:
    return _sampler


def profile_snapshot() -> Optional[Dict[str, object]]:
    """The running sampler's cumulative profile, or None."""
    s = _sampler
    return s.snapshot() if s is not None else None
