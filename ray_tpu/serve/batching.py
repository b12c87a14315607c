"""Adaptive request batching: ``@serve.batch`` and a batched replica.

Parity with ``python/ray/serve/batching.py`` (``@serve.batch``): concurrent
calls to the wrapped method are grouped into one invocation receiving a
list of inputs and returning a list of outputs; each caller gets its own
element back.  A replica of a batched deployment
(``DeploymentConfig.batched``) does the same to its ``__call__`` requests.
Both build one ``_Batcher``, the only owner of a queue of requests and a
flusher thread in ``ray_tpu/serve``: the decorator one per decorated
function or bound instance, handing it the function; the replica one,
handing it its callable, its latency budget and its sensors.

The state machine.  Callers are admitted into a queue (each caller's
thread parks on its slot, so a replica's ``max_concurrent_queries`` still
bounds admission); a dedicated flusher thread coalesces queued requests
into pad-to-bucket batches and invokes the callable once per batch with a
LIST of requests.  ``pad_batch_to`` is a sorted tuple of bucket sizes: the
list is padded (by repeating the last element) up to the next bucket so
one jitted forward sees only ``len(buckets)`` static shapes and never
recompiles per batch size; padded outputs are dropped before delivery.
Batch size adapts to observed queue depth, capped so the EWMA-predicted
batch time stays inside the latency budget its owner gives (a replica's
``target_latency_ms`` falling back to the ``serve_target_latency_ms`` knob;
with none, ``max_batch_size`` is the cap).  Which of the queued requests
share a call is cut by what the call will be padded to (``cut_by_size``):
the oldest request and the queued requests of like size (``len()`` of a
sequence, observed), so a short prompt neither pays for nor waits out a
long neighbour's rows; with equal sizes that is arrival order.  When the
batcher stops waiting for more: ``batch_wait_timeout_s`` is the LONGEST the
oldest queued request may be held, and within it the flusher cuts as soon
as the batch is full to the cap, an earlier cut passed the request over (it
has had its linger and gets no second one), or no neighbour is due in time
to be worth the wait.  The last is worked out from what the batcher sees of
its own traffic: an EWMA of the gaps between admissions (taken in
``submit``) against the per-item call estimate (``_HOLD_GAP_SHARE``); a
batcher that has not yet seen two admissions and one call holds for the
configured bound, so a first burst batches as it always did.  Which reason
fired is ``cut`` on the ``serve.batch.linger`` span (``full``, ``waited``,
``passed``, ``not_due``) and is counted in ``counts()``.  Requests that age
past ``serve_queue_deadline_ms`` in the queue — the wait for the calls cut
before theirs included — are shed with :class:`ServeOverloadedError`
instead of executing; the proxy maps that to 503 + Retry-After.  A failed
batch isolates per item: singleton batches get their own error raw; larger
batches re-run members alone once (``serve_batch_retry_singletons``) or
receive a batch-level :class:`BatchExecutionError` naming the batch size
and request ids.

The rules of the padded batch are pure functions: ``next_bucket`` /
``pad_items`` (how many rows a batch is padded to) and ``item_size`` /
``cut_by_size`` (which queued requests share a batch, by the rectangle of
rows x largest size they would be padded to).
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from collections.abc import Mapping
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ray_tpu import chaos, observability
from ray_tpu._private.config import _config
from ray_tpu.exceptions import BatchExecutionError, ServeOverloadedError
from ray_tpu.observability.metric_names import (REPLICA_BATCH_CUTS,
                                                 REPLICA_BATCH_CUTS_NOT_DUE,
                                                 REPLICA_BATCH_PADDED_SUM,
                                                 REPLICA_BATCH_SIZE_SUM)

# Process-unique ids stamped on each batched request so batch-level
# failures (``BatchExecutionError``) can name their members.
_request_counter = itertools.count()

# EWMA weight for the per-item execution-time estimate that sizes batches
# and the queue_est_ms backpressure signal, and for the gaps between
# admissions (local smoothing; the autoscaler's cross-tick smoothing uses
# serve_autoscale_ewma_alpha).
_EWMA_ALPHA = 0.3

# A queued request is held for a neighbour only while the gap between
# admissions is expected to be under this share of what its call would
# take.  Holding never shortens the held request's own latency; it can only
# pay for the next one.  With a call of t and a neighbour due after a gap g:
# run now, and the two wait t and 2t - g (the neighbour sits out the first
# call), 3t - g together; hold, and they wait g + t and t, g + 2t together,
# if the shared call costs what one does (a lone request is padded to the
# first row bucket, so a neighbour of like size rides free).  Holding wins
# only if g < t / 2; where a call grows with its rows it never does.
_HOLD_GAP_SHARE = 0.5


def next_bucket(n: int, buckets: Optional[Tuple[int, ...]]) -> int:
    """Smallest bucket >= n (the largest bucket when n overflows them);
    n itself when no buckets are configured."""
    if not buckets:
        return n
    return next((b for b in buckets if b >= n), buckets[-1])


def pad_items(items: List[Any], buckets: Optional[Tuple[int, ...]]
              ) -> List[Any]:
    """Pad ``items`` (repeating the last element) up to the next bucket so
    a jitted forward only ever sees ``len(buckets)`` static batch shapes."""
    target = next_bucket(len(items), buckets)
    if target > len(items):
        return items + [items[-1]] * (target - len(items))
    return items


def item_size(item: Any) -> int:
    """What a request weighs in a padded batch, observed and not
    configured: ``len(item)`` of a sized sequence (a list or tuple of token
    ids, a string, bytes, an array); 1 for a mapping, a scalar or anything
    else, so that a deployment of such requests sees every size equal."""
    if isinstance(item, Mapping):
        return 1
    try:
        return max(1, len(item))
    except TypeError:
        return 1


def cut_by_size(sizes: Sequence[int], cap: int,
                buckets: Optional[Tuple[int, ...]]) -> List[int]:
    """Which queued requests share the next call: their places in the
    queue (``sizes`` is in arrival order), ascending, at most ``cap``.

    A batch is padded to a rectangle of ``next_bucket(n, buckets)`` rows by
    its largest member, so requests go together by the rectangle they
    make.  Sizes are read to the power of two (the batcher does not know
    the deployment's own length buckets, and 100 and 120 tokens are alike
    to any).  The candidates are the runs of neighbouring size classes
    round the oldest request's class, each with every queued member of its
    classes (the oldest ``cap`` of them); the one whose real sizes fill
    most of their rectangle wins, ties to the larger batch.  The oldest is
    in every candidate, so each cut retires the head of the queue and no
    request starves; sizes of one class (a deployment of scalars, of
    dicts, of equal prompts) give ``queue[:cap]`` to the letter."""
    classes = [(size - 1).bit_length() for size in sizes]
    present = sorted(set(classes))
    home = present.index(classes[0])
    best, best_key = [0], (0.0, 0)
    for lo in present[:home + 1]:
        for hi in present[home:]:
            take = [i for i, c in enumerate(classes) if lo <= c <= hi][:cap]
            rows = max(len(take), next_bucket(len(take), buckets))
            filled = sum(sizes[i] for i in take) / (
                rows * max(sizes[i] for i in take))
            if (filled, len(take)) > best_key:
                best, best_key = take, (filled, len(take))
    return best


class _ItemEstimate:
    """What one request's share of a call takes, in ms: an EWMA of
    ``ms / n`` a call, seeded by the first sample (0.0 until then).  It
    sizes batches, says whether a neighbour is worth waiting for, and is a
    replica's ``ewma_item_ms``.  Whoever makes the calls feeds it: a
    batcher its batches, a replica its direct calls too, into the one it
    hands its batcher."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ms = 0.0  # raylint: guarded-by(self._lock)

    def observe(self, ms: float, n: int) -> None:
        per_item = ms / max(n, 1)
        with self._lock:
            prev = self._ms
            self._ms = (per_item if prev == 0.0 else
                        prev + _EWMA_ALPHA * (per_item - prev))

    def ms(self) -> float:
        with self._lock:
            return self._ms


class _Request:
    """One queued request, parked until its batch has run."""

    __slots__ = ("item", "size", "passed", "event", "value", "error",
                 "request_id", "t_enqueue", "trace", "t_cut", "t_done",
                 "batch", "n", "padded_n", "size_max", "retried", "shed")

    def __init__(self, item):
        self.item = item
        self.size = item_size(item)
        # a cut took others and left this one queued: only the flusher
        # thread writes and reads it
        self.passed = False
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None
        self.request_id = next(_request_counter)
        self.t_enqueue = time.monotonic()
        # the submitting thread's (trace_id, span_id): the flusher's
        # spans for the batch this request heads join its trace
        self.trace = (observability.current() if observability.live()
                      else None)
        # What became of it, for the caller's ``serve.replica.wait`` span.
        # The flusher writes these before ``event.set()`` and the caller
        # reads them after ``event.wait()``: the Event orders the two.
        self.t_cut = self.t_done = self.t_enqueue  # cut into a batch; call ended
        self.batch = 0          # the ordinal ``serve.batch.execute`` carries
        self.n = self.padded_n = self.size_max = 0
        self.retried = False    # its batch failed and it was run again alone
        self.shed = False       # it aged out of the queue and was never run


def _sorted_buckets(pad: Optional[Sequence[int]]
                    ) -> Optional[Tuple[int, ...]]:
    return tuple(sorted(int(b) for b in pad)) if pad else None


def _no_sensor(*_sample) -> None:
    """What a batcher that was handed no sensor observes with."""


class _Batcher:
    """A queue of requests and the flusher thread that batches them (the
    module docstring has the state machine: admit -> linger while a
    neighbour is due -> shed-expired -> cut by size -> pad-to-bucket call
    -> per-item deliver).

    ``call`` takes the padded list and returns a list no shorter than the
    requests in it.  ``name`` is what ran a failed batch, for
    :class:`BatchExecutionError`; ``thread_name`` names the flusher.  The
    rest is what only a replica has: ``estimate``, the per-item estimate
    its direct calls feed too (a batcher handed none keeps its own);
    ``budget_ms``, the latency budget the cap is held inside (none, or 0:
    the cap is ``max_batch_size``); its two sensors,
    ``observe_queue_wait(ms)`` and ``observe_execute(ms, n)``; and
    ``chaos_labels``, which make a batch a ``serve.replica.execute`` chaos
    point."""

    def __init__(self, call: Callable[[List[Any]], Sequence[Any]],
                 name: str, thread_name: str, *, max_batch_size: int,
                 batch_wait_timeout_s: float,
                 pad_batch_to: Optional[Sequence[int]] = None,
                 estimate: Optional[_ItemEstimate] = None,
                 budget_ms: Callable[[], float] = lambda: 0.0,
                 observe_queue_wait: Callable[[float], None] = _no_sensor,
                 observe_execute: Callable[[float, int], None] = _no_sensor,
                 chaos_labels: Optional[Mapping[str, str]] = None):
        self._invoke = call
        self._name = name
        self._thread_name = thread_name
        self._estimate = estimate if estimate is not None else _ItemEstimate()
        self._budget_ms = budget_ms
        self._observe_queue_wait = observe_queue_wait
        self._observe_execute = observe_execute
        self._chaos_labels = chaos_labels
        # the batch shape is retune()-able live (autopilot serve policy),
        # so the flush loop reads it under the same lock as the queue
        # raylint: guarded-by(self._lock)
        self._max = max(1, int(max_batch_size))
        # raylint: guarded-by(self._lock)
        self._wait_s = float(batch_wait_timeout_s)
        # raylint: guarded-by(self._lock)
        self._buckets = _sorted_buckets(pad_batch_to)
        self._lock = threading.Lock()
        self._queue: List[_Request] = []  # raylint: guarded-by(self._lock)
        self._wakeup = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._batches = 0  # batches run; only the flusher thread counts
        # real sizes, and the padded rectangles they were run in, summed
        # over every batch: their quotient is the fill
        self._size_sum = 0  # raylint: guarded-by(self._lock)
        self._padded_sum = 0  # raylint: guarded-by(self._lock)
        # when the next request is due: the last admission and an EWMA of
        # the gaps between admissions (None until two have been seen)
        # raylint: guarded-by(self._lock)
        self._t_admit: Optional[float] = None
        # raylint: guarded-by(self._lock)
        self._gap_ewma_s: Optional[float] = None
        # cuts made, and those made because no neighbour was due
        self._cuts = 0  # raylint: guarded-by(self._lock)
        self._cuts_not_due = 0  # raylint: guarded-by(self._lock)

    def depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def counts(self) -> dict:
        """What ``get_metrics()`` carries of the batches and the cuts."""
        with self._lock:
            return {REPLICA_BATCH_SIZE_SUM: self._size_sum,
                    REPLICA_BATCH_PADDED_SUM: self._padded_sum,
                    REPLICA_BATCH_CUTS: self._cuts,
                    REPLICA_BATCH_CUTS_NOT_DUE: self._cuts_not_due}

    def retune(self, cfg: dict) -> None:
        """Live-update the batch shape (autopilot serve policy): the
        next flush cycle reads the new linger/cap; requests already
        parked keep their slots — nothing is dropped on a retune."""
        with self._lock:
            if "max_batch_size" in cfg:
                self._max = max(1, int(cfg["max_batch_size"]))
            if "batch_wait_timeout_s" in cfg:
                self._wait_s = max(0.0, float(cfg["batch_wait_timeout_s"]))
            if "pad_batch_to" in cfg:
                self._buckets = _sorted_buckets(cfg["pad_batch_to"])
        self._wakeup.set()

    def submit(self, item) -> Any:
        slot = _Request(item)
        # The caller's own wait, on its own thread and so in its own trace
        # (inside ``actor.call`` on a replica), opened before the request
        # is queued, so that the batch that serves it lies inside it.  The
        # cut and the call happen on the flusher's thread: they cannot be
        # spans here, so they are numbers on the span that ends the wait,
        # which the flusher wrote on the request.
        with observability.span("serve.replica.wait", cat="serve") as wait:
            self._admit(slot)
            slot.event.wait()
            if wait.live:
                wait.set(by="batch",
                         queue_wait_us=int((slot.t_cut - slot.t_enqueue)
                                           * 1e6),
                         call_us=int((slot.t_done - slot.t_cut) * 1e6),
                         batch=slot.batch, n=slot.n, padded_n=slot.padded_n,
                         size=slot.size, size_max=slot.size_max,
                         retried=int(slot.retried), shed=int(slot.shed))
        if slot.error is not None:
            raise slot.error
        return slot.value

    def _admit(self, slot: _Request) -> None:
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._flush_loop, daemon=True,
                    name=self._thread_name)
                self._thread.start()
            if self._t_admit is not None:
                # two callers' threads may get here out of order
                gap = max(0.0, slot.t_enqueue - self._t_admit)
                prev = self._gap_ewma_s
                self._gap_ewma_s = (gap if prev is None else
                                    prev + _EWMA_ALPHA * (gap - prev))
            self._t_admit = slot.t_enqueue
            self._queue.append(slot)
        self._wakeup.set()

    def shutdown(self) -> None:
        self._stop = True
        self._wakeup.set()

    def _effective_max(self, item_ms: float) -> int:
        """Latency-guarded batch-size cap: never form a batch whose
        EWMA-predicted execution time (items × per-item estimate) would
        blow the latency budget."""
        with self._lock:
            want = self._max
        budget = self._budget_ms()
        if budget > 0 and item_ms > 0:
            want = min(want, max(1, int(budget / item_ms)))
        return max(1, want)

    def _flush_loop(self) -> None:
        while True:
            self._wakeup.wait()
            if self._stop:
                return
            with self._lock:
                if not self._queue:
                    self._wakeup.clear()
                    continue
                head = self._queue[0].trace
            # From the wake-up with requests in hand to the batch cut:
            # what the batching policy spends on waiting for neighbours.
            with observability.span("serve.batch.linger", cat="serve",
                                    parent=head) as linger:
                batch, expired, deadline_ms = self._cut_batch(linger)
            for s in expired:
                s.t_cut = s.t_done = time.monotonic()
                s.shed = True
                wait_ms = (s.t_cut - s.t_enqueue) * 1e3
                self._observe_queue_wait(wait_ms)
                s.error = ServeOverloadedError(
                    f"request {s.request_id} aged {wait_ms:.0f}ms in the "
                    f"queue of {self._thread_name} "
                    f"(serve_queue_deadline_ms={deadline_ms:.0f})",
                    retry_after_s=max(deadline_ms / 1e3, 0.1))
                s.event.set()
            if batch:
                self._run_batch(batch)

    def _cut_batch(self, linger: observability.span):
        """Linger, then cut: ``(the batch, the requests that aged out, the
        deadline they aged past)``.  Only this thread takes requests off
        the queue, so it is not empty here."""
        # what the oldest request's call would take alone: a call ends on
        # this thread, so the estimate stands still while the cut waits
        call_ms = self._estimate.ms()
        cap = self._effective_max(call_ms)
        # Linger window anchored on the OLDEST queued request.  Four
        # reasons to cut: the batch is full (to the adaptive cap); the
        # oldest request has waited batch_wait_timeout_s, the longest it
        # may be held; an earlier cut passed it over (it has had its
        # linger; the device is idle); or no neighbour is due in time to
        # be worth the wait (_HOLD_GAP_SHARE).  Until the batcher has seen
        # two admissions and one call there is no estimate, and the
        # configured linger holds.
        while True:
            with self._lock:
                depth = len(self._queue)
                oldest = self._queue[0]
                wait_s = self._wait_s
                gap_s = self._gap_ewma_s
            waited = time.monotonic() - oldest.t_enqueue
            not_due = (gap_s is not None and call_ms > 0
                       and gap_s * 1e3 > _HOLD_GAP_SHARE * call_ms)
            cut = ("full" if depth >= cap else
                   "waited" if waited >= wait_s else
                   "passed" if oldest.passed else
                   "not_due" if not_due else None)
            if cut:
                break
            time.sleep(min(0.0005, max(wait_s / 10.0, 1e-4)))
        if linger.live:
            linger.set(depth=depth, cap=cap, cut=cut,
                       oldest_wait_us=int(waited * 1e6),
                       gap_est_us=-1 if gap_s is None else int(gap_s * 1e6),
                       call_est_us=int(call_ms * 1e3) if call_ms > 0 else -1)
        deadline_ms = float(_config.get("serve_queue_deadline_ms"))
        expired: List[_Request] = []
        with self._lock:
            if deadline_ms > 0:
                now = time.monotonic()
                live: List[_Request] = []
                for s in self._queue:
                    if (now - s.t_enqueue) * 1e3 > deadline_ms:
                        expired.append(s)
                    else:
                        live.append(s)
                self._queue = live
            taken = cut_by_size([s.size for s in self._queue], cap,
                                self._buckets) if self._queue else []
            batch = [self._queue[i] for i in taken]
            for i in reversed(taken):
                del self._queue[i]
            for s in self._queue:
                s.passed = True
            if not self._queue:
                self._wakeup.clear()
            self._cuts += 1
            if cut == "not_due":
                self._cuts_not_due += 1
        return batch, expired, deadline_ms

    def _call(self, items: List[Any]) -> List[Any]:
        n = len(items)
        with self._lock:
            buckets = self._buckets
        results = list(self._invoke(pad_items(items, buckets)))[:n]
        if len(results) != n:
            raise ValueError(
                f"batched function {self._name} returned {len(results)} "
                f"results for {n} inputs")
        return results

    def _run_batch(self, batch: List[_Request]) -> None:
        self._batches += 1
        size_sum = sum(s.size for s in batch)
        size_max = max(s.size for s in batch)
        with self._lock:
            padded_n = next_bucket(len(batch), self._buckets)
            self._size_sum += size_sum
            self._padded_sum += padded_n * size_max
        # Pad, call, read back and deliver: device idle under this span
        # and outside serve.batch.call's device work is the batcher's own
        # host time.
        for s in batch:
            s.batch, s.n = self._batches, len(batch)
            s.padded_n, s.size_max = padded_n, size_max
        with observability.span("serve.batch.execute", cat="serve",
                                parent=batch[0].trace) as execute:
            if execute.live:
                execute.set(n=len(batch), padded_n=padded_n,
                            size_sum=size_sum, size_max=size_max,
                            batch=self._batches)
            self._execute(batch)

    def _observe_call(self, t_start: float, n: int) -> float:
        """One call covering ``n`` requests has ended, well or badly: the
        estimate gets ``ms / n`` (the amortized cost that sizes future
        batches), the sensor the call's whole time.  Returns when it
        ended."""
        t_end = time.monotonic()
        ms = (t_end - t_start) * 1e3
        self._estimate.observe(ms, n)
        self._observe_execute(ms, n)
        return t_end

    def _execute(self, batch: List[_Request]) -> None:
        t_start = time.monotonic()
        for s in batch:
            s.t_cut = t_start
            self._observe_queue_wait((t_start - s.t_enqueue) * 1e3)
        n = len(batch)
        try:
            if chaos.ENABLED and self._chaos_labels is not None:
                chaos.inject("serve.replica.execute", **self._chaos_labels)
            results = self._call([s.item for s in batch])
            t_done = self._observe_call(t_start, n)
            for s, v in zip(batch, results):
                s.value = v
                s.t_done = t_done
                s.event.set()
            return
        except BaseException as e:
            error = e
        t_done = self._observe_call(t_start, n)
        # Per-item error isolation.  A singleton's error is unambiguously
        # its own and is delivered raw.  Larger batches re-run members
        # alone once, so a poisoned request fails alone and innocent
        # batchmates still get answers, or (with retry off) get a
        # batch-level tag carrying the batch size and request ids, so
        # callers can tell "my request was bad" from "I was collateral".
        if n == 1:
            batch[0].error = error
            batch[0].t_done = t_done
            batch[0].event.set()
            return
        if _config.get("serve_batch_retry_singletons"):
            for s in batch:
                t1 = time.monotonic()
                try:
                    s.value = self._call([s.item])[0]
                except BaseException as single_err:
                    s.error = single_err
                s.retried = True
                s.t_done = self._observe_call(t1, 1)
                s.event.set()
            return
        tagged = BatchExecutionError(
            self._name, n, [s.request_id for s in batch], error)
        for s in batch:
            s.error = tagged
            s.t_done = t_done
            s.event.set()


def batch(_fn: Optional[Callable] = None, *, max_batch_size: int = 10,
          batch_wait_timeout_s: float = 0.01,
          pad_batch_to: Optional[Sequence[int]] = None):
    """Decorator converting ``f(self, item)`` call sites into batched
    ``f(self, [items])`` execution.  The wrapped function must accept a
    list and return a list of equal length."""

    def wrap(fn: Callable):
        queue_attr = f"__batch_queue_{fn.__name__}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if kwargs:
                raise ValueError("@serve.batch methods take one positional "
                                 "request argument")
            if len(args) == 2:  # bound method: (self, item)
                holder, item = args
            elif len(args) == 1:  # plain function: (item,)
                holder, item = wrapper, args[0]
            else:
                raise ValueError("@serve.batch methods take exactly one "
                                 "request argument")
            queue = getattr(holder, queue_attr, None)
            if queue is None:
                queue = _Batcher(
                    fn if holder is wrapper else functools.partial(fn, holder),
                    fn.__name__, f"serve-batch-{fn.__name__}",
                    max_batch_size=max_batch_size,
                    batch_wait_timeout_s=batch_wait_timeout_s,
                    pad_batch_to=pad_batch_to)
                setattr(holder, queue_attr, queue)
            return queue.submit(item)

        wrapper._is_serve_batch = True
        return wrapper

    if _fn is not None:
        return wrap(_fn)
    return wrap
