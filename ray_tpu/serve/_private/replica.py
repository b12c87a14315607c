"""Replica actor: hosts one copy of a deployment's user callable.

Parity with ``python/ray/serve/_private/replica.py``: runs the user class
(or function), counts ongoing requests for autoscaling/backpressure,
supports ``reconfigure(user_config)`` in place, health checks, and
graceful drain before shutdown.

TPU note: a replica is where compiled inference lives — the user callable
typically closes over a ``jax.jit``'d function.  Replicas stay alive across
requests precisely so XLA compilation caches stay warm; a rolling update
replaces replicas one at a time so the app never serves with a cold cache
on every replica at once.

A batched deployment (``DeploymentConfig.batched``): ``__call__`` requests
go through the request batcher of ``serve/batching.py`` (its docstring has
the state machine), which this replica builds and hands its callable
(``_invoke_batch``), its latency budget, its two sensors and its per-item
call estimate; the callable then takes a LIST of requests and returns a
list of equal length.

A generating deployment (``DeploymentConfig.generation_slots``): the callable
is a slot model and ``__call__`` requests go through the generation engine of
``serve/generation.py``, which this replica builds as it builds the batcher
and hands its two sensors: ``queue_wait`` is then a request's wait for a
slot, ``execute`` its time in one, and the per-item estimate that time over
the slots (what a waiting request waits for the one ahead of it).

Every request — batched or direct — feeds two replica-local
:class:`~ray_tpu.observability.perf.PerfHistogram` instances
(``queue_wait`` and ``execute``).  Their raw bucket counts ride
``get_metrics()`` to the controller, which diffs them per tick, federates
across replicas with ``perf.merge_counts``, and publishes per-replica
execute p95 to routers / feeds the EWMA-smoothed SLO autoscaler.
"""

from __future__ import annotations

import asyncio
import inspect
import threading
import time
from typing import Any, List, Optional

from ray_tpu import chaos, observability
from ray_tpu._private.config import _config
from ray_tpu.observability import perf
from ray_tpu.observability.metric_names import (REPLICA_BATCH_CUTS,
                                                 REPLICA_BATCH_CUTS_NOT_DUE,
                                                 REPLICA_BATCH_PADDED_SUM,
                                                 REPLICA_BATCH_SIZE_SUM,
                                                 REPLICA_INIT_GAUGE)
from ray_tpu.serve.batching import _Batcher, _ItemEstimate
from ray_tpu.serve.config import batched
from ray_tpu.serve.generation import GenerationEngine


def _load_checkpoint(checkpoint: Any) -> Any:
    """Resolve a deployment checkpoint to the restored pytree. Accepts a
    CheckpointRef or its dict form (DeploymentConfig rides through
    dataclasses.asdict on deploy)."""
    from ray_tpu.checkpoint import CheckpointRef
    if isinstance(checkpoint, dict) and "root" in checkpoint:
        checkpoint = CheckpointRef(**checkpoint)
    if isinstance(checkpoint, CheckpointRef):
        return checkpoint.load()
    return checkpoint


def _resolve_arg_refs(args):
    """Resolve ObjectRef request arguments to their values.  The proxy
    puts large raw ingress bodies into the object plane and ships a ref
    (the bytes ride the striped transport pool); ``handle_request``'s
    own args tuple is nested inside the actor-call args, so the
    runtime's top-level ref resolution does not reach it — resolve here,
    on the replica's host, where the fetch is local-or-striped."""
    from ray_tpu.object_ref import ObjectRef
    if not any(isinstance(a, ObjectRef) for a in args):
        return args
    import ray_tpu
    return tuple(ray_tpu.get(a) if isinstance(a, ObjectRef) else a
                 for a in args)


# what get_metrics() carries of a replica that does not batch
_NO_BATCHES = dict.fromkeys((REPLICA_BATCH_SIZE_SUM, REPLICA_BATCH_PADDED_SUM,
                             REPLICA_BATCH_CUTS, REPLICA_BATCH_CUTS_NOT_DUE),
                            0)

_init_gauge = None


def _record_init_seconds(deployment: str, replica: str, seconds: float):
    """Of a deployment's start-up, the share that is the user's own
    constructor (weights, warm-up compiles) and not the controller's.
    A gauge in the process's registry, so it outlives the replica and is
    there for whoever reads after ``serve.shutdown()``."""
    global _init_gauge
    if _init_gauge is None:
        from ray_tpu.util.metrics import Gauge
        _init_gauge = Gauge(  # raylint: allow(data-race) idempotent: the registry keeps one metric per name
            REPLICA_INIT_GAUGE,
            "Seconds a replica's user constructor took",
            tag_keys=("deployment", "replica"))
    # raylint: allow(metrics-cardinality) one series per replica, bounded by the deployments' replica counts
    _init_gauge.set(seconds, tags={"deployment": deployment,
                                   "replica": replica})


class Replica:
    def __init__(self, deployment_name: str, replica_tag: str,
                 func_or_class, init_args, init_kwargs,
                 user_config: Optional[Any] = None,
                 checkpoint: Optional[Any] = None,
                 batch_config: Optional[dict] = None):
        self.deployment_name = deployment_name
        self.replica_tag = replica_tag
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        self._draining = False
        self._is_function = inspect.isfunction(func_or_class)
        if checkpoint is not None:
            if self._is_function:
                # Only class replicas have an __init__ to receive the
                # restored tree; silently dropping the checkpoint would
                # serve uninitialized weights.
                raise ValueError(
                    f"deployment {deployment_name!r}: checkpoint= requires "
                    "a class deployment (the restored pytree is injected "
                    "as the checkpoint= init kwarg); a function replica "
                    "has nowhere to receive it")
            # Cold start from an engine manifest: the weights pytree loads
            # from the content-addressed store HERE, on the replica — the
            # controller only ever shipped the (root, manifest) pointer.
            init_kwargs = dict(init_kwargs or {})
            init_kwargs["checkpoint"] = _load_checkpoint(checkpoint)
        if self._is_function:
            self._callable = func_or_class
        else:
            t_init = time.monotonic()
            self._callable = func_or_class(*init_args, **(init_kwargs or {}))
            _record_init_seconds(deployment_name, replica_tag,
                                 time.monotonic() - t_init)
        # Replica-local latency sensors (always on — they are the
        # router/autoscaler inputs, not optional observability).
        self._hist_queue_wait = perf.PerfHistogram("queue_wait")
        self._hist_execute = perf.PerfHistogram("execute")
        # what one request's share of a call takes: the direct calls feed
        # it here, the batcher its batches
        self._estimate = _ItemEstimate()
        self._batch_cfg = dict(batch_config) if batch_config else None
        self._batcher = self._build_batcher()
        self._engine = self._build_engine()
        if user_config is not None:
            self.reconfigure(user_config)

    def _build_engine(self) -> Optional[GenerationEngine]:
        if not (self._batch_cfg or {}).get("generation_slots"):
            return None
        engine = GenerationEngine(
            self._callable, self.deployment_name,
            f"serve-replica-generate-{self.replica_tag}",
            observe_queue_wait=self._observe_queue_wait,
            observe_execute=self._observe_generation)
        if engine.slots != self._batch_cfg["generation_slots"]:
            raise ValueError(
                f"deployment {self.deployment_name!r}: generation_slots="
                f"{self._batch_cfg['generation_slots']}, its slot model "
                f"holds {engine.slots}")
        return engine

    def _observe_generation(self, ms: float, n: int) -> None:
        """One answer left its slot after ``ms``: the slots turn over one
        request every ``ms / slots``, which is what the one queued behind it
        waits for."""
        self._observe_execute(ms, n)
        self._estimate.observe(ms, self._engine.slots)

    def _build_batcher(self) -> Optional[_Batcher]:
        cfg = self._batch_cfg or {}
        if cfg.get("generation_slots") or not batched(cfg.get("max_batch_size", 1), cfg.get("pad_batch_to")):
            return None
        return _Batcher(
            self._invoke_batch,
            getattr(self._callable, "__name__", self.deployment_name),
            f"serve-replica-batch-{self.replica_tag}",
            max_batch_size=cfg.get("max_batch_size", 1),
            batch_wait_timeout_s=cfg.get("batch_wait_timeout_s", 0.005),
            pad_batch_to=cfg.get("pad_batch_to"),
            estimate=self._estimate, budget_ms=self._batch_budget_ms,
            observe_queue_wait=self._observe_queue_wait,
            observe_execute=self._observe_execute,
            chaos_labels={"deployment": self.deployment_name,
                          "replica": self.replica_tag})

    def _batch_budget_ms(self) -> float:
        cfg = self._batch_cfg or {}
        target = float(cfg.get("target_latency_ms") or 0.0)
        if target > 0:
            return target
        return float(_config.get("serve_target_latency_ms"))

    def _invoke_batch(self, items: List[Any]):
        # Function deployments and class __call__ share the contract:
        # take a LIST of requests, return a list of equal length.  An
        # async callable is run to completion here — the flusher thread
        # has no event loop of its own, and the result must be a list.
        with observability.span("serve.batch.call", cat="serve"):
            result = self._callable(items)
            if inspect.iscoroutine(result):
                result = asyncio.run(result)
        return result

    def _observe_queue_wait(self, ms: float) -> None:
        self._hist_queue_wait.observe(ms)
        if perf.ENABLED:
            perf.observe("serve.queue_wait", ms)

    def _observe_execute(self, ms: float, n: int) -> None:
        """Record one execution covering ``n`` requests: each member
        experienced the whole batch's wall time, so the execute histogram
        gets ``n`` samples of ``ms``."""
        for _ in range(n):
            self._hist_execute.observe(ms)

    def reconfigure(self, user_config: Any) -> None:
        if not self._is_function:
            reconfigure = getattr(self._callable, "reconfigure", None)
            if reconfigure is not None:
                reconfigure(user_config)

    def set_batch_config(self, cfg: dict) -> None:
        """Merge a batch-config delta into the live batcher (the
        controller's ``retune_deployment_batch`` fan-out target)."""
        merged = dict(self._batch_cfg or {})
        merged.update(cfg or {})
        self._batch_cfg = merged
        batcher = self._batcher
        if batcher is not None:
            batcher.retune(merged)
        else:
            self._batcher = self._build_batcher()

    def handle_request(self, method_name: str, args, kwargs) -> Any:
        with self._lock:
            if self._draining:
                raise RuntimeError(
                    f"Replica {self.replica_tag} is draining")
            self._ongoing += 1
            self._total += 1
        try:
            args = _resolve_arg_refs(args)
            if (self._engine is not None and method_name == "__call__"
                    and len(args) == 1 and not kwargs):
                # the caller's actor thread parks on its request
                return self._engine.submit(args[0])
            batcher = self._batcher
            if (batcher is not None and method_name == "__call__"
                    and len(args) == 1 and not kwargs):
                # The caller's actor thread parks on its slot; queue wait
                # and execute are recorded by the flusher per batch.
                return batcher.submit(args[0])
            t0 = time.monotonic()
            try:
                if chaos.ENABLED:
                    chaos.inject("serve.replica.execute",
                                 deployment=self.deployment_name,
                                 replica=self.replica_tag)
                if self._is_function:
                    return self._callable(*args, **kwargs)
                if method_name == "__call__":
                    return self._callable(*args, **kwargs)
                return getattr(self._callable, method_name)(*args, **kwargs)
            finally:
                ms = (time.monotonic() - t0) * 1e3
                self._observe_queue_wait(0.0)
                self._estimate.observe(ms, 1)
                self._observe_execute(ms, 1)
                if perf.ENABLED:
                    perf.observe("serve.replica_exec", ms)
        finally:
            with self._lock:
                self._ongoing -= 1

    def get_metrics(self) -> dict:
        qw_counts, qw_sum = self._hist_queue_wait.merged()
        ex_counts, ex_sum = self._hist_execute.merged()
        batcher = self._batcher
        engine = self._engine
        queue = engine if engine is not None else batcher
        depth = queue.depth() if queue is not None else 0
        with self._lock:
            ongoing = self._ongoing
            total = self._total
        # Estimated time-to-drain of work already admitted here: the
        # router's shed signal and a tiebreaker for scoring.
        pending = depth if queue is not None else ongoing
        ewma = self._estimate.ms()
        return {"replica_tag": self.replica_tag,
                "num_ongoing_requests": ongoing,
                "num_total_requests": total,
                "queue_depth": depth,
                "queue_est_ms": pending * ewma,
                "ewma_item_ms": ewma,
                # real request sizes over the padded rectangles they ran
                # in (the batches' fill) and the cuts, of them those made
                # because no neighbour was due: readable without a trace
                **(batcher.counts() if batcher is not None else _NO_BATCHES),
                # the generation engine's counts, where there is one
                **(engine.counts() if engine is not None else {}),
                "perf": {
                    "bounds": list(perf.bucket_bounds()),
                    "queue_wait": {"counts": qw_counts, "sum_ms": qw_sum},
                    "execute": {"counts": ex_counts, "sum_ms": ex_sum},
                }}

    def check_health(self) -> bool:
        checker = None if self._is_function else getattr(
            self._callable, "check_health", None)
        if checker is not None:
            checker()
        return True

    def prepare_for_shutdown(self, timeout_s: float = 20.0) -> bool:
        """Stop accepting requests and wait for in-flight ones to drain."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + timeout_s
        drained = False
        while time.monotonic() < deadline:
            with self._lock:
                if self._ongoing == 0:
                    drained = True
                    break
            time.sleep(0.01)
        if self._batcher is not None:
            self._batcher.shutdown()
        if self._engine is not None:
            self._engine.shutdown()
        return drained

    # A node drain snapshots hosted actors with cloudpickle. The locks, the
    # batcher (thread/event) and the histogram shards (thread-locals) are
    # not picklable and the drain-time flags must not survive migration —
    # a replica restored on a healthy node serves again immediately with
    # fresh sensors and a fresh batcher rebuilt from _batch_cfg.
    def __getstate__(self):
        with self._lock:
            st = self.__dict__.copy()
        st.pop("_lock", None)
        st.pop("_batcher", None)
        st.pop("_engine", None)
        st.pop("_estimate", None)
        st.pop("_hist_queue_wait", None)
        st.pop("_hist_execute", None)
        st["_draining"] = False
        st["_ongoing"] = 0
        return st

    def __setstate__(self, st):
        self.__dict__.update(st)
        self._lock = threading.Lock()
        self._hist_queue_wait = perf.PerfHistogram("queue_wait")
        self._hist_execute = perf.PerfHistogram("execute")
        self._estimate = _ItemEstimate()
        self._batcher = self._build_batcher()
        self._engine = self._build_engine()
