"""Serve layer tests.

Models the reference's ``python/ray/serve/tests/``: deploy/call/handle,
rolling reconfigure, replica failure recovery, autoscaling, batching,
HTTP ingress, and deployment graphs.
"""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance(ray_start_regular):
    serve.start()
    yield
    serve.shutdown()


@serve.deployment
class Echo:
    def __call__(self, x):
        return {"echo": x}

    def shout(self, x):
        return str(x).upper()


def test_deploy_and_call(serve_instance):
    h = serve.run(Echo.bind(), route_prefix="/echo")
    assert h.remote(42).result(timeout=30) == {"echo": 42}
    assert h.shout.remote("hi").result(timeout=30) == "HI"


@serve.deployment
def double(x):
    return 2 * x


def test_function_deployment(serve_instance):
    h = serve.run(double.bind())
    assert h.remote(21).result(timeout=30) == 42


def test_function_deployment_rejects_checkpoint():
    """checkpoint= injects the restored tree as an __init__ kwarg, which a
    function deployment has nowhere to receive — declaring one must fail
    loudly instead of silently serving without the weights."""
    from ray_tpu.serve._private.replica import Replica

    with pytest.raises(ValueError, match="class"):
        @serve.deployment(checkpoint={"root": "/tmp/ckpt"})
        def with_ckpt(x):
            return x

    # the replica guards too (config-dict deploy paths bypass the decorator)
    with pytest.raises(ValueError, match="checkpoint"):
        Replica("d", "d#1", double.func_or_class, (), {},
                checkpoint={"root": "/tmp/ckpt"})


def test_num_replicas_and_status(serve_instance):
    h = serve.run(Echo.options(name="echo3", num_replicas=3).bind(),
                  route_prefix="/e3")
    assert h.remote(1).result(timeout=30) == {"echo": 1}
    st = serve.status()
    assert st["echo3"]["running_replicas"] == 3


@serve.deployment
class Configurable:
    def __init__(self):
        self.threshold = 0

    def reconfigure(self, config):
        self.threshold = config["threshold"]

    def __call__(self, x):
        return x > self.threshold


def test_user_config_reconfigure(serve_instance):
    h = serve.run(
        Configurable.options(user_config={"threshold": 5}).bind())
    assert h.remote(10).result(timeout=30) is True
    assert h.remote(3).result(timeout=30) is False
    # Redeploy with only user_config changed: in-place reconfigure.
    serve.run(Configurable.options(user_config={"threshold": 50}).bind())
    assert h.remote(10).result(timeout=30) is False


def test_replica_failure_recovery(serve_instance):
    h = serve.run(Echo.options(name="fragile", num_replicas=2,
                               health_check_period_s=0.2).bind())
    assert h.remote(0).result(timeout=30) == {"echo": 0}
    controller = serve._get_controller() if hasattr(serve, "_get_controller") \
        else serve.api._get_controller()
    info = ray_tpu.get(controller.get_replica_handles.remote("fragile"))
    ray_tpu.kill(info["handles"][0])
    # Controller reconcile replaces the dead replica.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        ray_tpu.get(controller.autoscale_tick.remote())
        st = ray_tpu.get(controller.list_deployments.remote())["fragile"]
        if st["running_replicas"] == 2:
            break
        time.sleep(0.1)
    # Requests still succeed.
    for i in range(8):
        assert h.remote(i).result(timeout=30) == {"echo": i}


@serve.deployment
class Slow:
    def __call__(self, x):
        time.sleep(0.3)
        return x


def test_autoscaling_up(serve_instance):
    serve.run(Slow.options(
        name="auto",
        autoscaling_config={"min_replicas": 1, "max_replicas": 3,
                            "target_num_ongoing_requests_per_replica": 1.0,
                            "upscale_delay_s": 0.0},
    ).bind())
    h = serve.get_deployment_handle("auto")
    controller = serve.api._get_controller()
    responses = [h.remote(i) for i in range(6)]

    def tick():
        for _ in range(20):
            ray_tpu.get(controller.autoscale_tick.remote())
            time.sleep(0.05)
    t = threading.Thread(target=tick)
    t.start()
    results = [r.result(timeout=60) for r in responses]
    t.join()
    assert sorted(results) == list(range(6))
    st = serve.status()["auto"]
    assert st["target_replicas"] > 1


class _BatchModel:
    def __init__(self):
        self.batch_sizes = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def predict(self, items):
        self.batch_sizes.append(len(items))
        return [i * 10 for i in items]


def test_batching_groups_requests(ray_start_regular):
    model = _BatchModel()
    results = [None] * 8
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, model.predict(i)))
        for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [i * 10 for i in range(8)]
    assert max(model.batch_sizes) > 1  # actually batched


def test_batching_pad_to_bucket(ray_start_regular):
    seen = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1,
                 pad_batch_to=(4, 8))
    def predict(items):
        seen.append(len(items))
        return [x + 1 for x in items]

    results = [None] * 3
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, predict(i)))
        for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [1, 2, 3]
    assert all(s in (4, 8) for s in seen)  # padded to a bucket


def test_batching_error_propagates(ray_start_regular):
    @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.05)
    def bad(items):
        raise ValueError("nope")

    with pytest.raises(ValueError):
        bad(1)


def test_one_class_owns_a_request_queue_and_its_flusher():
    """``@serve.batch`` and a batched replica run one state machine: the
    two modules define one class with a ``_flush_loop``, and a decorated
    function's queue is one of it."""
    import inspect

    from ray_tpu.serve import batching
    from ray_tpu.serve._private import replica

    flushers = {cls for module in (batching, replica)
                for cls in vars(module).values()
                if inspect.isclass(cls) and cls.__module__ == module.__name__
                and hasattr(cls, "_flush_loop")}
    assert len(flushers) == 1, flushers

    @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.0)
    def double(items):
        return [2 * x for x in items]

    assert double(21) == 42
    queue = getattr(double, "__batch_queue_double")
    assert type(queue) in flushers
    queue.shutdown()


def test_http_proxy(serve_instance):
    serve.run(Echo.options(name="http_echo").bind(), route_prefix="/api")
    url = serve.start_http_proxy()
    req = urllib.request.Request(
        f"{url}/api", data=json.dumps({"k": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert json.loads(resp.read()) == {"echo": {"k": 1}}
    # Unknown route -> 404
    try:
        urllib.request.urlopen(f"{url}/nope-xyzzy", timeout=30)
        assert False
    except urllib.error.HTTPError as e:
        assert e.code in (404, 500)


@serve.deployment
class Preprocessor:
    def __call__(self, x):
        return x + 1


@serve.deployment
class Pipeline:
    def __init__(self, pre):
        self.pre = pre

    def __call__(self, x):
        pre_out = self.pre.remote(x).result(timeout=30)
        return pre_out * 100


def test_deployment_graph_composition(serve_instance):
    h = serve.run(Pipeline.bind(Preprocessor.bind()))
    assert h.remote(4).result(timeout=60) == 500


def test_delete_deployment(serve_instance):
    serve.run(Echo.options(name="todelete").bind(), route_prefix="/td")
    assert "todelete" in serve.status()
    serve.delete("todelete")
    assert "todelete" not in serve.status()


@serve.deployment(name="versioned")
class V1:
    def __call__(self, x):
        return "v1"


@serve.deployment(name="versioned")
class V2:
    def __call__(self, x):
        return "v2"


def test_rolling_update_on_code_change(serve_instance):
    h = serve.run(V1.bind(), route_prefix="/v")
    assert h.remote(0).result(timeout=30) == "v1"
    serve.run(V2.bind(), route_prefix="/v")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if h.remote(0).result(timeout=30) == "v2":
            break
        time.sleep(0.1)
    assert h.remote(0).result(timeout=30) == "v2"


def test_http_proxy_health_routes_and_streaming(serve_instance):
    """Proxy-level features (reference http_proxy.py parity): /-/healthz,
    /-/routes, chunked streaming of list results, 404 body shape."""
    @serve.deployment
    class Lister:
        def __call__(self, n):
            return list(range(n or 3))

    serve.run(Lister.options(name="lister").bind(), route_prefix="/list")
    url = serve.start_http_proxy()
    with urllib.request.urlopen(f"{url}/-/healthz", timeout=30) as r:
        assert json.loads(r.read())["status"] == "ok"
    with urllib.request.urlopen(f"{url}/-/routes", timeout=30) as r:
        routes = json.loads(r.read())
    assert routes.get("/list") == "lister"
    # streaming: each element arrives as its own chunk line
    req = urllib.request.Request(
        f"{url}/list", data=json.dumps(4).encode(),
        headers={"Content-Type": "application/json", "X-Serve-Stream": "1"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.headers.get("Transfer-Encoding") == "chunked"
        lines = [json.loads(x) for x in r.read().split(b"\n") if x]
    assert lines == [0, 1, 2, 3]
    # non-streamed default still one JSON body
    req = urllib.request.Request(
        f"{url}/list", data=json.dumps(2).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.loads(r.read()) == [0, 1]


def test_http_proxy_concurrency_limit(serve_instance):
    """Over-limit requests are rejected 503 immediately (ingress
    backpressure), not queued behind blocked handlers."""
    import threading
    import time as _time

    @serve.deployment
    class Slow:
        def __call__(self, x):
            _time.sleep(2.0)
            return "done"

    serve.run(Slow.options(name="slowd").bind(), route_prefix="/slow")
    from ray_tpu.serve import api as serve_api
    from ray_tpu.serve._private.http_proxy import HTTPProxy
    proxy = HTTPProxy(serve_api._get_controller(),
                      max_concurrent_requests=1)
    url = proxy.address()
    results = {}

    def call(key):
        req = urllib.request.Request(
            f"{url}/slow", data=b"1",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                results[key] = ("ok", json.loads(r.read()))
        except urllib.error.HTTPError as e:
            results[key] = ("http", e.code, e.headers.get("Retry-After"))

    t1 = threading.Thread(target=call, args=("a",))
    t1.start()
    _time.sleep(0.5)  # first request is now holding the one slot
    call("b")
    t1.join(timeout=30)
    assert results["a"] == ("ok", "done"), results
    assert results["b"][0] == "http" and results["b"][1] == 503, results
    assert results["b"][2] == "1"  # Retry-After
    proxy.shutdown()
