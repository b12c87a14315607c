"""End-to-end distributed tracing: W3C-style context + span recording.

A trace is a ``trace_id`` minted at an entry point (task submit, serve
request, checkpoint save) plus a tree of spans, each ``(span_id,
parent_span_id)``.  The context travels three ways:

- **TaskSpec** — ``trace_id``/``parent_span_id`` fields, so a task's
  worker-side execute span joins the submit-side trace (``runtime.py``).
- **RPC envelope** — ``Envelope.trace`` carries ``"trace_id:span_id"``;
  the server adopts it around handler dispatch (``_private/rpc.py``).
- **RTF5 frame index** — an optional trailing blob in the frame index
  (``_private/framing.py``) stamps serialized objects with the trace
  that produced them, so a striped fetch can attribute the bytes it
  moved.  Absent trace keeps frames byte-identical to the pre-trace
  format (checkpoint chunk dedup depends on this).

A span has two sinks.  With ``tracing_enabled`` it lands in the
process-local :class:`~ray_tpu._private.profiling.Profiler` ring; the
dashboard head federates every host's ring into one merged
chrome://tracing timeline (``/api/timeline``, ``/api/trace?id=X``).  While
a ``jax.profiler`` session is on it is also entered as a
``jax.profiler.TraceAnnotation`` named ``ray_tpu.<name>`` on the same
thread, with the same ids and attributes, so it lies in the session's
``.xplane.pb`` on the device's clock (the ring's clock is ``time.time()``,
which the device trace does not share).  The session itself is the switch:
there is no flag for it, and JAX is looked up in ``sys.modules`` on use, so
a process that never imported JAX never does so here.

Cost model mirrors :mod:`ray_tpu.chaos`: a module-level ``ENABLED`` bool
and one ``TraceAnnotation.is_enabled()`` call are all the hot paths touch
when neither sink is on (guarded by ``bench_micro.py``'s
``trace_overhead_pct`` gate).  ``enable()`` flips the bool and installs the
chaos observer so injected faults appear as instant events inside the
traces they perturb.
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler
from ray_tpu.observability import sampler as _sampler

# Fast-path switch: hot paths check this module bool and nothing else
# when tracing is off (same pattern as chaos.ENABLED).
ENABLED: bool = bool(_config.get("tracing_enabled"))

# chrome-tracing process label for spans recorded in this process;
# daemons relabel to "node:<hex8>" at startup so the merged timeline
# separates hosts.
_pid_label: str = "driver"

_ctx_var: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = \
    contextvars.ContextVar("ray_tpu_obs_ctx", default=None)

# Fallback context sources (the runtime registers one that reads its
# per-task thread-local / async ContextVar), consulted when no explicit
# span context is active.  Registration instead of an import keeps
# observability import-light and cycle-free (runtime imports us).
_providers: list = []

Context = Tuple[str, str]  # (trace_id, span_id)

ANNOTATION_PREFIX = "ray_tpu."  # a span's name in the profiler's trace
# The profiler's trace stores an annotation's attribute as a number wherever
# it parses as one (an id of digits alone loses its leading zeros,
# ``12e45...`` comes back as ``inf``).  So that sink, and only that one,
# writes an id behind a letter; whoever reads the trace strips it.
ANNOTATION_ID_PREFIX = "t"

_annotation = None  # jax.profiler.TraceAnnotation, once JAX is imported


def session_on() -> bool:
    """Whether a ``jax.profiler`` session is recording in this process.
    False wherever JAX was never imported: importing it here would cost a
    process that does not need it seconds of start-up."""
    global _annotation
    if _annotation is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return False
        _annotation = profiler.TraceAnnotation  # raylint: allow(data-race) idempotent publish of one class object
    return _annotation.is_enabled()


def live() -> bool:
    """Whether a span opened now would be recorded anywhere: the ring
    (``tracing_enabled``) or a profiler session.  What the propagation
    path asks before it mints or carries a trace context."""
    return ENABLED or session_on()


def register_context_provider(fn: Callable[[], Optional[Context]]) -> None:
    if fn not in _providers:
        _providers.append(fn)  # raylint: allow(data-race) providers registered during process bootstrap; iteration sees a GIL-atomic list snapshot


def set_process_label(label: str) -> None:
    global _pid_label
    _pid_label = label  # raylint: allow(data-race) process label set once at bootstrap; plain string store is GIL-atomic


def process_label() -> str:
    return _pid_label


def enable() -> None:
    """Turn tracing on (also flips the config knob so child runtimes and
    ``Profiler.enabled`` agree) and hook chaos instant events."""
    global ENABLED
    _config.set("tracing_enabled", True)
    ENABLED = True
    from ray_tpu import chaos
    chaos.set_observer(_chaos_observer)


def disable() -> None:
    global ENABLED
    _config.set("tracing_enabled", False)
    ENABLED = False
    from ray_tpu import chaos
    chaos.set_observer(None)


def mint_id() -> str:
    """A fresh 64-bit hex id (trace or span)."""
    return os.urandom(8).hex()


def current() -> Optional[Context]:
    """The active (trace_id, span_id), from the innermost enclosing
    ``span(...)`` or, failing that, a registered provider (task ctx)."""
    ctx = _ctx_var.get()
    if ctx is not None:
        return ctx
    for fn in _providers:
        got = fn()
        if got:
            return got
    return None


def current_trace_id() -> str:
    """The active trace id, or ``""``. Cheap enough for log records."""
    if not live():
        return ""
    ctx = current()
    return ctx[0] if ctx else ""


def set_current(trace_id: str, span_id: str):
    """Explicitly adopt a context; returns a token for :func:`reset`."""
    return _ctx_var.set((trace_id, span_id))


def reset(token) -> None:
    _ctx_var.reset(token)


# -- wire helpers -----------------------------------------------------------

def wire_context() -> str:
    """The active context encoded for the wire (``"trace_id:span_id"``),
    or ``""`` when no sink is on / no context is active."""
    if not live():
        return ""
    ctx = current()
    return f"{ctx[0]}:{ctx[1]}" if ctx else ""


def parse_wire(ctx_str: str) -> Optional[Context]:
    if not ctx_str:
        return None
    trace_id, sep, span_id = ctx_str.partition(":")
    if not sep or not trace_id:
        return None
    return (trace_id, span_id)


def adopt_wire(ctx_str: str):
    """Adopt a wire-encoded context for the current execution context.
    Returns a reset token, or ``None`` when ``ctx_str`` is empty/bad."""
    ctx = parse_wire(ctx_str)
    if ctx is None:
        return None
    return _ctx_var.set(ctx)


# -- span recording ---------------------------------------------------------

# "," "=" and "#" delimit an annotation's attributes in the profiler's trace
_UNSAFE = str.maketrans(",=#", ";:~")


class span:
    """Record a timed span parented under the active context (or under
    ``parent``, a ``(trace_id, span_id)`` taken on another thread).

    Context-manager only (raylint R14 enforces this outside the
    observability package): the span closes on every exit path, and the
    context var is always reset.  Near-free when no sink is on —
    ``__enter__``/``__exit__`` return after one bool check and one
    ``is_enabled()`` call.  A wait that begins on one thread and ends on
    another cannot be a span: record it as a number on the span that ends
    it (``mailbox_wait_us``, ``oldest_wait_us``).
    """

    __slots__ = ("name", "ring_name", "cat", "args", "pid", "parent", "_t0",
                 "_ids", "_token", "_tagged", "_ring", "_ann")

    # whether stack samples landing on the span's thread are attributed to
    # its trace (observability/sampler.py)
    _tags_samples = True

    def __init__(self, name: str, cat: str = "obs",
                 pid: Optional[str] = None,
                 parent: Optional[Context] = None, **args: Any):
        self.name = name
        self.ring_name = name  # the ring may know the span by another name
        self.cat = cat
        self.args = args
        self.pid = pid
        self.parent = parent
        self._t0 = None
        self._token = None
        self._tagged = False
        self._ring = False
        self._ann = None

    def _ring_on(self) -> bool:
        return ENABLED

    def __enter__(self) -> "span":
        session = session_on()
        ring = self._ring_on()
        if not (ring or session):
            return self
        parent = self.parent or current()
        if parent is None:
            trace_id, parent_span = mint_id(), ""
        else:
            trace_id, parent_span = parent
        span_id = mint_id()
        self._ids = (trace_id, span_id, parent_span)
        self._token = _ctx_var.set((trace_id, span_id))
        if self._tags_samples and _sampler.TAGGING:
            # stack-sampler attribution: samples landing on this thread
            # while the span is open are tagged with its trace id
            _sampler.note_span_enter(trace_id)
            self._tagged = True
        self._ring = ring
        if session:
            # raylint: allow(span-leak) delegated CM: our __exit__ closes it
            self._ann = _annotation(
                ANNOTATION_PREFIX + self.name,
                trace_id=ANNOTATION_ID_PREFIX + trace_id,
                span_id=ANNOTATION_ID_PREFIX + span_id,
                parent_span_id=ANNOTATION_ID_PREFIX + parent_span,
                **_annotation_safe(self.args))
            self._ann.__enter__()
        self._t0 = time.time()
        return self

    @property
    def live(self) -> bool:
        """Whether a sink took the span when it was entered.  Attributes
        that cost something to compute are computed under this test and
        handed to :meth:`set`, so that they cost nothing with no sink."""
        return self._t0 is not None

    @property
    def trace_id(self) -> str:
        return self._ids[0] if self._t0 is not None else ""

    @property
    def span_id(self) -> str:
        return self._ids[1] if self._t0 is not None else ""

    def set(self, **attrs: Any) -> None:
        """Attributes known only once the work is under way (the replica a
        request was routed to, the bytes of a reply); both sinks get them.
        Nothing to do on a span that is not live."""
        if self._t0 is None:
            return
        self.args.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_annotation_safe(attrs))

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._t0 is None:  # no sink was on at __enter__
            return
        try:
            if self._ring:
                dur = time.time() - self._t0
                trace_id, span_id, parent_span = self._ids
                args = dict(self.args)
                args.update(trace_id=trace_id, span_id=span_id,
                            parent_span_id=parent_span)
                if exc_type is not None:
                    args["error"] = exc_type.__name__
                get_profiler().record(self.ring_name, self.cat,
                                      pid=self.pid or _pid_label,
                                      start_s=self._t0, dur_s=dur, args=args)
        finally:
            if self._ann is not None:
                self._ann.__exit__(exc_type, exc, tb)
                self._ann = None
            if self._tagged:
                _sampler.note_span_exit()
                self._tagged = False
            _ctx_var.reset(self._token)
            self._t0 = None


def _annotation_safe(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v.translate(_UNSAFE) if isinstance(v, str) else v
            for k, v in attrs.items()}


class task_span(span):
    """The runtime's span round one task or actor call.  In the profiler's
    trace it is ``ray_tpu.<name>`` (``task.execute``, ``actor.call``) like
    any other span.  In the ring it keeps what the runtime has always
    recorded there, whenever ``profiling_enabled`` is on and whether or not
    ``tracing_enabled`` is: the function's own name, under ``task`` /
    ``actor_task``, on the node's row of the timeline.  Stack samples are
    not attributed to it, as they were not to the record it replaces: an
    actor's thread may sit in one call for the actor's whole life."""

    __slots__ = ()
    _tags_samples = False

    def __init__(self, name: str, ring_name: str, cat: str, pid: str,
                 parent: Optional[Context]):
        # attributes come by set(), once the span is known to be live
        super().__init__(name, cat, pid, parent)
        self.ring_name = ring_name

    def _ring_on(self) -> bool:
        return get_profiler().enabled


def instant(name: str, cat: str = "obs", pid: Optional[str] = None,
            **args: Any) -> None:
    """Record a point-in-time event tagged with the active context."""
    if not ENABLED:
        return
    ctx = current()
    if ctx:
        args.setdefault("trace_id", ctx[0])
        args.setdefault("parent_span_id", ctx[1])
    get_profiler().instant(name, cat, pid=pid or _pid_label, args=args)


def _chaos_observer(point: str, labels: Dict[str, Any], action: str) -> None:
    """Installed into ray_tpu.chaos by enable(): every fired fault becomes
    an instant event carrying the fault spec, interleaved with the spans
    it perturbed."""
    args = {"action": action}
    for k, v in labels.items():
        args[k] = str(v)
    instant(f"chaos:{point}", cat="chaos", **args)


# -- trace querying ---------------------------------------------------------

def spans_for_trace(trace_id: str, events=None) -> list:
    """Filter chrome events down to one trace (spans whose args carry the
    trace_id, plus its instant events)."""
    if events is None:
        events = get_profiler().chrome_trace()
    return [e for e in events
            if (e.get("args") or {}).get("trace_id") == trace_id]
