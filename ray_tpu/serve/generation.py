"""Autoregressive generation on a replica: a queue, a fixed number of slots
and one thread that admits and steps.

A deployment asks for it with ``serve.deployment(generation_slots=S)``. Its
callable is then a *slot model* and not a function of requests: the replica
builds a ``GenerationEngine`` over it as it builds a ``_Batcher`` over a
batched callable, and every ``__call__`` request is ``engine.submit``'s. A
request is ``{"prompt": [token ids], "max_new_tokens": n}`` and its reply
``{"tokens": [...], "logits": [...]}``: ``n`` tokens chosen greedily, each
with its logit (no end token stops an answer early).

The slot model holds the weights and the ``S`` sequences' state on the
device and has three methods (``ray_tpu.models.generation.TransformerGenerator``
is the one over ``models.transformer``'s ``prefill`` and ``decode_step``; this
module knows no model):

``admit(prompt, slot) -> (handle, bucket)``
    Prefill the prompt alone, write what it keeps into ``slot`` and make its
    first token the slot's next input. Dispatches and returns: ``handle``
    reads back as the first token and its logit (scalars or arrays of one).
``step(active) -> handle``
    One token for every slot (``active`` [S] bool: the occupied ones), each
    slot's new token becoming its next input *on the device*. Dispatches and
    returns: ``handle`` reads back as the S tokens and their logits.
``read(handle) -> (tokens, logits)``
    Wait for a handle's arrays and bring them to the host.

Two more are optional: ``check(prompt, n)`` raises for a request the model
cannot hold, ``live_rows(lengths)`` says how many K/V rows sequences of
these lengths hold and ``read_rows(lengths)`` how many a step reads for them
(a step's span carries them as ``live_rows`` and ``read_rows``).

The engine's loop: while a slot is free and a request waits, admit it; then
one step over all slots; then read the step *before* (the host reads a
step's tokens while the next runs); an answer whose last token was read is
handed to its caller. An answer's length is known when it is admitted, so
its slot is free again as soon as its last step is dispatched, a step
before its tokens are read. Callers park on their request as
``_Batcher.submit`` parks them. No paging, prefix sharing, chunked prefill,
streaming, sampling or preemption.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu import observability
from ray_tpu.observability.metric_names import (GENERATE_ADMITTED,
                                                 GENERATE_SLOTS_OCCUPIED,
                                                 GENERATE_STEPS,
                                                 GENERATE_TOKENS)
from ray_tpu.serve.batching import _no_sensor
from ray_tpu.util.metrics import Counter, Gauge

_TAGS = ("deployment",)


class _Metrics:
    """The engine's four metrics, one of each in the process's registry."""
    _lock = threading.Lock()
    _made: Optional["_Metrics"] = None

    def __init__(self):
        self.tokens = Counter(GENERATE_TOKENS,
                              "Tokens the generation engine ran, by phase",
                              tag_keys=(*_TAGS, "phase"))
        self.steps = Counter(GENERATE_STEPS, "Decode steps dispatched",
                             tag_keys=_TAGS)
        self.admitted = Counter(GENERATE_ADMITTED,
                                "Requests prefilled into a slot",
                                tag_keys=_TAGS)
        self.occupied = Gauge(GENERATE_SLOTS_OCCUPIED,
                              "Slots that took the last decode step",
                              tag_keys=_TAGS)

    @classmethod
    def get(cls) -> "_Metrics":
        with cls._lock:
            if cls._made is None:
                cls._made = cls()
            return cls._made


class _Generation:
    """One request, parked until its answer is whole."""

    __slots__ = ("prompt", "n", "tokens", "logits", "event", "value", "error",
                 "t_enqueue", "t_admit", "trace", "slot", "bucket")

    def __init__(self, prompt: List[int], n: int):
        self.prompt = prompt
        self.n = n
        self.tokens: List[int] = []
        self.logits: List[float] = []
        self.event = threading.Event()
        self.value: Optional[Dict[str, Any]] = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.monotonic()
        self.t_admit = 0.0
        self.trace = (observability.current() if observability.live()
                      else None)
        # where the engine put it, for the caller's ``serve.replica.wait``
        # span: the engine's thread writes them before ``event.set()``
        self.slot = self.bucket = -1


def parse_request(item: Any) -> Tuple[List[int], int]:
    """``(prompt, max_new_tokens)`` of a request, or ``ValueError``."""
    try:
        prompt = [int(t) for t in item["prompt"]]
        n = int(item["max_new_tokens"])
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(
            'a generation request is {"prompt": [token ids], '
            f'"max_new_tokens": n}}: {e!r}') from e
    if not prompt or n < 1:
        raise ValueError("a generation request has a prompt of at least one "
                         "token and max_new_tokens of at least 1")
    return prompt, n


class GenerationEngine:
    """The queue, the slots and the thread (the module docstring has the
    loop). ``model`` is the slot model; ``check(prompt, n)`` raises for a
    request the model cannot hold (the caller gets the error, no slot is
    spent); the two sensors are the replica's, fed a request's wait for its
    slot and its time in one (``observe_execute(ms, 1)``)."""

    def __init__(self, model: Any, name: str, thread_name: str, *,
                 observe_queue_wait: Callable[[float], None] = _no_sensor,
                 observe_execute: Callable[[float, int], None] = _no_sensor):
        self._model = model
        self._name = name
        self._thread_name = thread_name
        self._observe_queue_wait = observe_queue_wait
        self._observe_execute = observe_execute
        self.slots = int(model.slots)
        self._lock = threading.Lock()
        self._queue: Deque[_Generation] = collections.deque()  # raylint: guarded-by(self._lock)
        self._wakeup = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        # the engine's thread alone reads and writes what follows
        self._free: List[int] = list(range(self.slots))[::-1]
        # slot -> (its request, decode steps it still takes)
        self._running: Dict[int, List[Any]] = {}
        # handles dispatched and not read yet, oldest first: (handle, what
        # to do with its tokens once read)
        self._unread: Deque[Tuple[Any, Callable]] = collections.deque()
        # admitted and not answered yet (a request whose last step is
        # dispatched is in no slot any more, and its tokens are unread)
        self._admitted: Dict[int, _Generation] = {}
        self._metrics = _Metrics.get()
        self._tags = {"deployment": name}
        # raylint: guarded-by(self._lock)
        self._counts = {"generate_admitted": 0, "generate_steps": 0,
                        "generate_prefill_tokens": 0,
                        "generate_decode_tokens": 0, "generate_replies": 0}
        self._occupied = 0  # raylint: guarded-by(self._lock)

    # -- the callers' side ------------------------------------------------

    def submit(self, item: Any) -> Dict[str, Any]:
        prompt, n = parse_request(item)
        check = getattr(self._model, "check", None)
        if check is not None:
            check(prompt, n)
        request = _Generation(prompt, n)
        # the caller's own wait, in its own trace, as ``_Batcher.submit``'s
        with observability.span("serve.replica.wait", cat="serve") as wait:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True,
                        name=self._thread_name)
                    self._thread.start()
                self._queue.append(request)
            self._wakeup.set()
            request.event.wait()
            if wait.live:
                wait.set(by="generate",
                         waited_us=int((request.t_admit - request.t_enqueue)
                                       * 1e6) if request.t_admit else -1,
                         slot=request.slot, len=len(prompt),
                         bucket=request.bucket, steps=n - 1,
                         n_new=len(request.tokens))
        if request.error is not None:
            raise request.error
        return request.value

    def depth(self) -> int:
        """Requests waiting for a slot."""
        with self._lock:
            return len(self._queue)

    def counts(self) -> Dict[str, int]:
        """What ``get_metrics()`` carries of the engine."""
        with self._lock:
            return {**self._counts, "generate_slots": self.slots,
                    "generate_slots_occupied": self._occupied}

    def shutdown(self) -> None:
        self._stop = True
        self._wakeup.set()

    # -- the engine's thread ----------------------------------------------

    def _count(self, **add: int) -> None:
        with self._lock:
            for key, n in add.items():
                self._counts[key] += n

    def _loop(self) -> None:
        while True:
            if not self._running and not self._unread:
                self._wakeup.wait()
            if self._stop:
                self._fail_all(RuntimeError(
                    f"the generation engine of {self._name} was shut down"))
                return
            try:
                self._admit()
                if self._running:
                    self._step()
                    # the step before this one, while this one runs
                    while len(self._unread) > 1:
                        self._read_one()
                else:
                    while self._unread:
                        self._read_one()
            except BaseException as e:  # noqa: BLE001 - every caller is told
                self._fail_all(e)
            with self._lock:
                if (not self._queue and not self._running
                        and not self._unread):
                    self._wakeup.clear()

    def _admit(self) -> None:
        while self._free:
            with self._lock:
                if not self._queue:
                    return
                request = self._queue.popleft()
            slot = self._free.pop()
            self._admitted[id(request)] = request
            request.t_admit = time.monotonic()
            request.slot = slot
            waited = request.t_admit - request.t_enqueue
            self._observe_queue_wait(waited * 1e3)
            with observability.span("serve.generate.prefill", cat="serve",
                                    parent=request.trace,
                                    len=len(request.prompt), slot=slot,
                                    waited_us=int(waited * 1e6)) as sp:
                handle, bucket = self._model.admit(request.prompt, slot)
                request.bucket = int(bucket)
                if sp.live:
                    sp.set(bucket=request.bucket)
            self._metrics.admitted.inc(tags=self._tags)
            self._metrics.tokens.inc(len(request.prompt),
                                     tags={**self._tags, "phase": "prefill"})
            self._count(generate_admitted=1,
                        generate_prefill_tokens=len(request.prompt))
            self._unread.append(
                (handle, lambda tokens, logits, r=request: self._take(
                    r, int(np.ravel(tokens)[0]), float(np.ravel(logits)[0]))))
            if request.n > 1:
                self._running[slot] = [request, request.n - 1]
            else:
                self._free.append(slot)

    def _step(self) -> None:
        active = np.zeros((self.slots,), bool)
        active[list(self._running)] = True
        # each taker with the steps it has left, this one among them
        takers = [(slot, request, left)
                  for slot, (request, left) in self._running.items()]
        finished = 0
        for slot, entry in list(self._running.items()):
            entry[1] -= 1
            if entry[1] == 0:       # its last step: the slot is free again
                del self._running[slot]
                self._free.append(slot)
                finished += 1
        with observability.span("serve.generate.step", cat="serve",
                                active=len(takers), finished=finished) as sp:
            handle = self._model.step(active)
            if sp.live:
                # what each holds with the token this step takes in: its
                # prompt, the steps it has taken and one
                held = [len(r.prompt) + r.n - left for _, r, left in takers]
                for name in ("live_rows", "read_rows"):
                    rows = getattr(self._model, name, None)
                    if rows is not None:
                        sp.set(**{name: int(rows(held))})
        self._metrics.steps.inc(tags=self._tags)
        self._metrics.tokens.inc(len(takers),
                                 tags={**self._tags, "phase": "decode"})
        self._metrics.occupied.set(len(takers), tags=self._tags)
        self._count(generate_steps=1, generate_decode_tokens=len(takers))
        with self._lock:
            self._occupied = len(takers)

        def deliver(tokens, logits):
            for slot, request, _ in takers:
                self._take(request, int(tokens[slot]), float(logits[slot]))

        self._unread.append((handle, deliver))

    def _read_one(self) -> None:
        handle, deliver = self._unread.popleft()
        deliver(*self._model.read(handle))

    def _take(self, request: _Generation, token: int, logit: float) -> None:
        request.tokens.append(token)
        request.logits.append(logit)
        if len(request.tokens) < request.n:
            return
        with observability.span("serve.generate.reply", cat="serve",
                                parent=request.trace, n_new=request.n):
            request.value = {"tokens": request.tokens,
                             "logits": request.logits}
            self._observe_execute(
                (time.monotonic() - request.t_admit) * 1e3, 1)
            self._count(generate_replies=1)
            del self._admitted[id(request)]
            request.event.set()

    def _fail_all(self, error: BaseException) -> None:
        """Every request the engine holds is answered with ``error``; the
        slots are all free again (their state is whatever it is: an insert
        overwrites it)."""
        with self._lock:
            waiting = list(self._queue)
            self._queue.clear()
            self._occupied = 0
        waiting.extend(self._admitted.values())
        self._admitted.clear()
        self._running.clear()
        self._unread.clear()
        self._free = list(range(self.slots))[::-1]
        for request in waiting:
            request.error = error
            request.event.set()
