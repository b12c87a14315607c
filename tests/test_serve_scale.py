"""Interactive-scale serving tests: replica-side continuous batching,
latency-aware routing, SLO autoscaling, and overload shedding.

Covers the serving plane end to end — pad-to-bucket recompile avoidance,
per-item error isolation inside a batch, queue-deadline shedding (the
"never hangs" contract), the power-of-two-choices router, the
scale-from-target autoscaler fix, and two deterministic chaos drills
(routing away from a chaos-delayed replica; the SLO autoscaler tripping
under injected latency within a bounded number of ticks).
"""

import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import chaos, serve
from ray_tpu._private.backoff import BreakerBoard
from ray_tpu._private.config import _config
from ray_tpu.serve._private.router import Router


@pytest.fixture
def serve_instance(ray_start_regular):
    serve.start()
    yield
    serve.shutdown()


def _burst(handle, values, timeout=60):
    """Fire all values concurrently through the handle; returns a list of
    results or the exception each caller got."""
    out = [None] * len(values)
    barrier = threading.Barrier(len(values))

    def call(i, v):
        barrier.wait()
        try:
            out[i] = handle.remote(v).result(timeout=timeout)
        except BaseException as e:  # noqa: BLE001 - tests inspect errors
            out[i] = e

    threads = [threading.Thread(target=call, args=(i, v))
               for i, v in enumerate(values)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    return out


class _Driver:
    """Closed-loop load: n threads calling the handle back to back."""

    def __init__(self, handle, n_threads=4):
        self._h = handle
        self._stop = threading.Event()
        self.errors = []
        self._threads = [threading.Thread(target=self._loop, daemon=True)
                         for _ in range(n_threads)]

    def _loop(self):
        i = 0
        while not self._stop.is_set():
            try:
                self._h.remote(i).result(timeout=30)
            except Exception as e:  # noqa: BLE001 - drills tolerate sheds
                self.errors.append(e)
            i += 1

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=30)


# -- continuous batching: pad-to-bucket recompile avoidance ----------------

_TRACE_SHAPES = []


@jax.jit
def _bucketed_fwd(xs):
    # Python side effects run only while jax TRACES (i.e. compiles) — the
    # list records one entry per distinct input shape.
    _TRACE_SHAPES.append(xs.shape)
    return xs * 2.0


@serve.deployment(max_batch_size=8, batch_wait_timeout_s=0.05,
                  pad_batch_to=(2, 4, 8))
class Bucketed:
    def __call__(self, items):
        xs = jnp.asarray([float(v) for v in items], dtype=jnp.float32)
        return [float(v) for v in _bucketed_fwd(xs)]


def test_pad_to_bucket_limits_recompiles(serve_instance):
    """Every batch the replica forms is padded to a configured bucket, so
    the jitted forward compiles at most len(buckets) times no matter how
    request-count varies burst to burst."""
    del _TRACE_SHAPES[:]
    h = serve.run(Bucketed.bind(), name="bucketed", route_prefix=None)
    for values in ([1, 2, 3], [5, 6], [1, 2, 3, 4, 5, 6], [9],
                   [1, 2, 3, 4, 5, 6, 7, 8]):
        results = _burst(h, values)
        assert results == [2 * v for v in values]
    assert len(_TRACE_SHAPES) >= 1
    assert set(_TRACE_SHAPES) <= {(2,), (4,), (8,)}, _TRACE_SHAPES
    # jit caches per shape: one trace per bucket, never per batch size.
    assert len(_TRACE_SHAPES) <= 3, _TRACE_SHAPES


# -- per-item error isolation ----------------------------------------------

@serve.deployment(max_batch_size=4, batch_wait_timeout_s=0.2)
class Picky:
    def __call__(self, items):
        if any(v == "poison" for v in items):
            raise ValueError("poisoned batch")
        return [v + "!" for v in items]


def test_batch_error_isolated_per_item(serve_instance):
    """A poisoned request fails alone (singleton re-run); its innocent
    batchmates still get their answers."""
    assert _config.get("serve_batch_retry_singletons")
    h = serve.run(Picky.bind(), name="picky", route_prefix=None)
    a, poison, b = _burst(h, ["a", "poison", "b"])
    assert a == "a!"
    assert b == "b!"
    # The poisoned caller gets its OWN error (the singleton re-run's
    # ValueError, riding the usual TaskError wrapper) — not a batch-level
    # tag, and the innocents above were not collateral.
    assert isinstance(poison, Exception)
    assert not isinstance(poison, serve.BatchExecutionError)
    assert "poisoned batch" in str(poison)


def test_batch_execution_error_tags_batch():
    """With singleton retry off, a failed multi-item batch delivers a
    BatchExecutionError naming the batch size and every member request id
    — callers can tell "my request was bad" from "I was collateral"."""

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.25)
    def explode(items):
        raise RuntimeError("boom")

    old = _config.get("serve_batch_retry_singletons")
    _config.set("serve_batch_retry_singletons", False)
    try:
        errs = [None] * 3
        barrier = threading.Barrier(3)

        def call(i):
            barrier.wait()
            try:
                explode(i)
            except BaseException as e:  # noqa: BLE001
                errs[i] = e

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert all(isinstance(e, serve.BatchExecutionError) for e in errs)
        tag = errs[0]
        assert tag.batch_size == 3
        assert len(tag.request_ids) == 3
        assert isinstance(tag.cause, RuntimeError)
        assert "batch of 3" in str(tag)
    finally:
        _config.set("serve_batch_retry_singletons", old)

    # A singleton batch gets its own error RAW — no batch-level wrapper.
    with pytest.raises(RuntimeError, match="boom"):
        explode("solo")


# -- queue-deadline shedding -----------------------------------------------

@serve.deployment(max_batch_size=2, batch_wait_timeout_s=0.005)
class Sluggish:
    def __call__(self, items):
        time.sleep(0.08)
        return list(items)


def test_queue_deadline_sheds_not_hangs(serve_instance):
    """Flooding a slow replica: requests that age past
    serve_queue_deadline_ms are shed with ServeOverloadedError (carrying a
    Retry-After hint); every caller returns promptly — nobody hangs."""
    old = _config.get("serve_queue_deadline_ms")
    _config.set("serve_queue_deadline_ms", 150.0)
    try:
        h = serve.run(Sluggish.bind(), name="sluggish", route_prefix=None)
        t0 = time.monotonic()
        results = _burst(h, [[i] for i in range(16)], timeout=30)
        elapsed = time.monotonic() - t0
    finally:
        _config.set("serve_queue_deadline_ms", old)
    assert elapsed < 20.0
    ok = [r for r in results if isinstance(r, list)]
    shed = [r for r in results if isinstance(r, serve.ServeOverloadedError)]
    assert len(ok) + len(shed) == 16, results
    assert ok, results
    assert shed, results
    assert all(e.retry_after_s > 0 for e in shed)


# -- router: power-of-two-choices scoring + shedding (unit) ----------------

def _bare_router(tags, p95=None, queue_est=None, target=0.0,
                 max_concurrent=100):
    r = object.__new__(Router)
    r._deployment_name = "unit"
    r._lock = threading.Condition()
    r._replicas = [f"replica:{t}" for t in tags]
    r._tags = list(tags)
    r._max_concurrent = max_concurrent
    r._in_flight = {}
    r._p95_ms = dict(p95 or {})
    r._queue_est_ms = dict(queue_est or {})
    r._target_latency_ms = target
    r._breakers = BreakerBoard()
    return r


def test_router_prefers_low_latency_replica():
    router = _bare_router(["slow", "fast"],
                          p95={"slow": 50.0, "fast": 1.0})
    for _ in range(20):
        _, tag = router._pick(timeout=1)
        assert tag == "fast"
        router._release(tag)
    # Load still matters: pile in-flight onto the fast replica until its
    # score crosses the slow one's, and the pick flips.
    router._in_flight["fast"] = 99
    _, tag = router._pick(timeout=1)
    assert tag == "slow"


def test_router_breaker_removes_replica():
    router = _bare_router(["a", "b"])
    for _ in range(int(_config.get("circuit_failure_threshold"))):
        router._breakers.record_failure("a")
    for _ in range(10):
        _, tag = router._pick(timeout=1)
        assert tag == "b"
        router._release(tag)


def test_router_sheds_when_all_over_budget():
    router = _bare_router(["a", "b"],
                          queue_est={"a": 500.0, "b": 300.0},
                          target=100.0)
    with pytest.raises(serve.ServeOverloadedError) as info:
        router._pick(timeout=1)
    assert info.value.retry_after_s > 0


def test_router_pick_is_bounded():
    """No replicas and a timeout: the pick raises instead of hanging."""
    router = _bare_router([])
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        router._pick(timeout=0.3)
    assert time.monotonic() - t0 < 5.0


# -- autoscaler: scale from target, not live count (unit) ------------------

def test_autoscale_scales_from_target_not_live():
    """While a scale-up is in flight the live count lags the target;
    desired must be computed from the target or every tick over-requests
    again (overshoot/oscillation)."""
    from ray_tpu.serve._private.deployment_state import (DeploymentState,
                                                         ReplicaInfo)
    from ray_tpu.serve.controller import ServeController

    ctrl = object.__new__(ServeController)
    ctrl._autoscale_state = {}
    ctrl._lock = threading.RLock()   # _autoscale decides under it
    state = DeploymentState("scaling")
    state.config = serve.DeploymentConfig(
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=20,
            target_num_ongoing_requests_per_replica=1.0,
            upscale_delay_s=0.0, downscale_delay_s=3600.0,
            smoothing_factor=2.0))
    # Scale-up in progress: 4 replicas requested, only 1 live yet.
    state.target_replicas = 4
    state.replicas = [ReplicaInfo("scaling#0", None, "v1")]
    metrics = {"total_ongoing": 8.0, "replicas": {}, "p95_ms": 0.0}

    ServeController._autoscale(ctrl, state, metrics)
    # From target=4: error=2 -> desired = 4*(1+2*(2-1)) = 12.  The old
    # live-count policy computed 1*(1+2*(8-1)) = 15 (overshoot).
    assert state.target_replicas == 12

    # Re-running with the same demand while replicas are STILL starting
    # must not keep inflating the target.
    for _ in range(3):
        ServeController._autoscale(ctrl, state, metrics)
    assert state.target_replicas == 12


def test_long_poll_notify_if_changed_dedups():
    from ray_tpu.serve._private.long_poll import LongPollHost
    host = LongPollHost()
    assert host.notify_if_changed("k", {"a": 1}) is True
    snap = dict(host._snapshot_ids)
    assert host.notify_if_changed("k", {"a": 1}) is False
    assert host._snapshot_ids == snap  # no listener wakeup for a no-op
    assert host.notify_if_changed("k", {"a": 2}) is True


# -- chaos drill: routing away from a delayed replica ----------------------

@serve.deployment(num_replicas=2)
class Steady:
    def __call__(self, x):
        return x


def _replica_totals(handles):
    metrics = [ray_tpu.get(h.get_metrics.remote(), timeout=10)
               for h in handles]
    return {m["replica_tag"]: m["num_total_requests"] for m in metrics}


def test_chaos_delay_shifts_routing_to_healthy_replica(serve_instance):
    """A deterministic 50ms chaos delay on one of two replicas: the
    router's latency-aware scoring moves >= 90% of traffic to the healthy
    one once its published execute p95 reflects the injury."""
    controller = serve.start()
    h = serve.run(Steady.options(name="reroute").bind(), route_prefix=None)
    info = ray_tpu.get(controller.get_replica_handles.remote("reroute"))
    tags, handles = info["tags"], info["handles"]
    assert len(tags) == 2
    slow_tag, healthy_tag = tags[0], tags[1]
    chaos.configure(
        20260805, f"serve.replica.execute[replica={slow_tag}]@1+=delay(0.05)")
    driver = _Driver(h, n_threads=4).start()
    try:
        # Learning phase: wait (bounded) until the router has seen the
        # slow replica's published p95 via long-poll membership.
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            router = h._router
            if router is not None and \
                    router._p95_ms.get(slow_tag, 0) >= 10:
                break
            time.sleep(0.1)
        else:
            pytest.fail("router never learned the slow replica's p95")
        before = _replica_totals(handles)
        time.sleep(2.0)
        after = _replica_totals(handles)
    finally:
        driver.stop()
        chaos.clear()
    healthy_delta = after[healthy_tag] - before[healthy_tag]
    slow_delta = after[slow_tag] - before[slow_tag]
    total = healthy_delta + slow_delta
    assert total > 50, (before, after)
    assert healthy_delta / total >= 0.9, (before, after)


# -- chaos drill: SLO autoscaler trips under injected latency --------------

@serve.deployment
class SlightlySteady:
    def __call__(self, x):
        return x


def test_chaos_delay_trips_slo_autoscaler(serve_instance):
    """Injected 30ms latency against a 10ms SLO: the EWMA-smoothed p95
    sensor crosses the target and the autoscaler scales up within a
    bounded number of autoscale_tick() calls — and never past
    max_replicas (hysteresis/clamp contract)."""
    controller = serve.start()
    dep = SlightlySteady.options(
        name="slo_dep",
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3, upscale_delay_s=0.0,
            downscale_delay_s=3600.0, smoothing_factor=1.0,
            target_latency_ms=10.0))
    h = serve.run(dep.bind(), name="slo", route_prefix=None)
    chaos.configure(
        20260805, "serve.replica.execute[deployment=slo_dep]@1+=delay(0.03)")
    driver = _Driver(h, n_threads=2).start()
    scaled = False
    try:
        for _ in range(50):
            ray_tpu.get(controller.autoscale_tick.remote(), timeout=30)
            target = serve.status()["slo_dep"]["target_replicas"]
            assert target <= 3
            if target >= 2:
                scaled = True
                break
            time.sleep(0.05)
    finally:
        driver.stop()
        chaos.clear()
    assert scaled, "SLO autoscaler never scaled up within 50 ticks"


# -- HTTP: overload presents as 503 + Retry-After --------------------------

@serve.deployment(max_batch_size=2, batch_wait_timeout_s=0.005,
                  max_concurrent_queries=32)
class VerySlow:
    def __call__(self, items):
        time.sleep(0.3)
        return list(items)


def test_proxy_maps_shed_to_503_retry_after(serve_instance):
    """Saturating a slow deployment over HTTP: shed requests come back as
    a prompt 503 with a Retry-After header — overload is never a hang."""
    old = _config.get("serve_queue_deadline_ms")
    _config.set("serve_queue_deadline_ms", 120.0)
    try:
        serve.run(VerySlow.bind(), name="shed", route_prefix="/shed")
        base = serve.start_http_proxy()
        out = []
        barrier = threading.Barrier(8)

        def post(i):
            barrier.wait()
            req = urllib.request.Request(
                f"{base}/shed", data=str(i).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=20) as resp:
                    out.append((resp.status, None))
            except urllib.error.HTTPError as e:
                out.append((e.code, e.headers.get("Retry-After")))

        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(8)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        elapsed = time.monotonic() - t0
    finally:
        _config.set("serve_queue_deadline_ms", old)
    assert len(out) == 8, out
    assert elapsed < 25.0
    codes = {code for code, _ in out}
    assert codes <= {200, 503}, out
    assert 200 in codes, out
    retry_afters = [ra for code, ra in out if code == 503]
    assert retry_afters, out
    assert any(ra is not None and int(ra) >= 1 for ra in retry_afters), out
