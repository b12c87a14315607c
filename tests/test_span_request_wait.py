"""A request's own wait on its replica, in its own trace: the
``serve.replica.wait`` span (``serve/batching.py`` ``_Batcher.submit``), the
join of a batch's members to the call that served them, and the proxy's
``accept_wait_us``. The ring (``tracing_enabled``) is the sink here; the
generation engine's twin is in ``tests/test_serve_generation.py``.
"""

import json
import statistics
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import observability, serve
from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler
from ray_tpu.observability import metric_names

CALLERS = 8


def _ring(name):
    return [e for e in get_profiler().chrome_trace() if e["name"] == name]


def _at_once(call, n=CALLERS):
    """``call(i)`` from ``n`` threads released together; their results."""
    out, gate = [None] * n, threading.Barrier(n)

    def one(i):
        gate.wait(timeout=30)
        out[i] = call(i)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return out


@pytest.fixture
def ring_on():
    profiling = _config.get("profiling_enabled")
    _config.set("profiling_enabled", True)
    get_profiler().clear()
    observability.enable()
    yield
    observability.disable()
    _config.set("profiling_enabled", profiling)
    get_profiler().clear()


def _check_waits_against_executes(waits, executes):
    """Every wait names, by ``batch``, one execute span that lay inside it,
    with that span's ``n``; its two numbers add up to its duration."""
    assert len(waits) == CALLERS
    by_batch = {}
    for e in executes:
        by_batch.setdefault(e["args"]["batch"], []).append(e)
    slack = []
    for w in waits:
        a = w["args"]
        assert a["by"] == "batch" and a["retried"] == 0 and a["shed"] == 0
        execute, = by_batch[a["batch"]]
        assert w["ts"] <= execute["ts"]
        assert execute["ts"] + execute["dur"] <= w["ts"] + w["dur"] + 1
        assert a["n"] == execute["args"]["n"] >= 1
        assert a["padded_n"] == execute["args"]["padded_n"] >= a["n"]
        assert a["size_max"] == execute["args"]["size_max"] >= a["size"] >= 1
        assert a["queue_wait_us"] >= 0 and a["call_us"] >= 0
        # what is left is the caller's thread waking after the event, less
        # what passed between the request's own stamp and the span's (the
        # machine's other workers can hold a thread between the two)
        slack.append(w["dur"] - a["queue_wait_us"] - a["call_us"])
    # (on a quiet machine the median is a few hundred microseconds)
    assert all(-50_000 < s < 250_000 for s in slack), slack
    assert abs(statistics.median(slack)) < 25_000, slack
    # members of one batch carry one ordinal, and the ordinals of the
    # batches that ran are the executes'
    assert sorted({w["args"]["batch"] for w in waits}) == sorted(by_batch)
    assert sum(e["args"]["n"] for e in executes) == CALLERS


def test_every_request_through_the_proxy_has_one_wait_that_names_its_batch(
        ring_on):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8.0, ignore_reinit_error=True)
    serve.start()

    @serve.deployment(name="lengths", max_batch_size=4,
                      batch_wait_timeout_s=0.05, pad_batch_to=(2, 4),
                      max_concurrent_queries=16)
    class Lengths:
        def __call__(self, items):
            time.sleep(0.01)
            return [len(x) for x in items]

    try:
        serve.run(Lengths.bind(), route_prefix="/len")
        url = serve.start_http_proxy() + "/len"

        def post(i):
            req = urllib.request.Request(
                url, data=json.dumps([0] * (i + 1)).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        assert post(0) == 1          # the router and the flusher exist
        time.sleep(0.2)
        get_profiler().clear()
        assert _at_once(post) == list(range(1, CALLERS + 1))
        time.sleep(0.3)              # the handlers close their spans
        requests = _ring("serve.request")
        waits = _ring("serve.replica.wait")
        calls = [e for e in _ring("Replica.handle_request")]
        executes = _ring("serve.batch.execute")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert len(requests) == CALLERS
    # one wait, one actor call and one route in every request's trace
    for name, found in (("serve.replica.wait", waits),
                        ("actor.call", calls)):
        assert sorted(e["args"]["trace_id"] for e in found) == sorted(
            r["args"]["trace_id"] for r in requests), name
    # the wait lies inside its request's actor call, on the actor's thread
    call_of = {c["args"]["trace_id"]: c for c in calls}
    for w in waits:
        call = call_of[w["args"]["trace_id"]]
        assert w["args"]["parent_span_id"] == call["args"]["span_id"]
        assert w["tid"] == call["tid"]
    _check_waits_against_executes(waits, executes)
    # each connection is new, so the accept's stamp is there for everyone
    assert all(0 <= r["args"]["accept_wait_us"] < 5_000_000
               for r in requests)
    assert "serve.replica.wait" in metric_names.SPANS


def test_a_kept_alive_connections_later_requests_carry_no_accept_wait(
        ring_on):
    import http.client
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8.0, ignore_reinit_error=True)
    serve.start()

    @serve.deployment(name="echo")
    def echo(x):
        return x

    try:
        serve.run(echo.bind(), route_prefix="/echo")
        base = serve.start_http_proxy()
        host, port = base[len("http://"):].split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        get_profiler().clear()
        for i in range(3):
            conn.request("POST", "/echo", body=json.dumps(i),
                         headers={"Content-Type": "application/json"})
            assert json.loads(conn.getresponse().read()) == i
        conn.close()
        time.sleep(0.2)
        stamps = [r["args"]["accept_wait_us"]
                  for r in sorted(_ring("serve.request"),
                                  key=lambda e: e["ts"])]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert len(stamps) == 3 and stamps[0] >= 0 and stamps[1:] == [-1, -1]


def test_a_serve_batch_functions_callers_have_the_same_wait(ring_on):
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05,
                 pad_batch_to=(2, 4))
    def lengths(items):
        time.sleep(0.01)
        return [len(x) for x in items]

    assert lengths([0]) == 1
    get_profiler().clear()
    assert _at_once(lambda i: lengths([0] * (i + 1))) == list(
        range(1, CALLERS + 1))
    _check_waits_against_executes(_ring("serve.replica.wait"),
                                  _ring("serve.batch.execute"))


def test_a_request_that_ran_again_alone_or_aged_out_says_so(ring_on,
                                                            monkeypatch):
    from ray_tpu.exceptions import ServeOverloadedError

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def picky(items):
        if len(items) > 1 and "bad" in items:
            raise ValueError("a poisoned batch")
        if items == ["bad"]:
            raise ValueError("a poisoned request")
        return [x.upper() for x in items]

    def call(i):
        try:
            return picky("bad" if i == 0 else f"ok{i}")
        except ValueError as e:
            return str(e)

    out = _at_once(call, n=3)
    assert out[0] == "a poisoned request" and out[1:] == ["OK1", "OK2"]
    retried = [w["args"] for w in _ring("serve.replica.wait")]
    assert len(retried) == 3
    together = [a for a in retried if a["n"] > 1]
    assert together and all(a["retried"] == 1 for a in together)
    assert all(a["call_us"] >= 0 and a["shed"] == 0 for a in retried)

    # aged out: a deadline shorter than the call ahead of it
    get_profiler().clear()
    deadline = _config.get("serve_queue_deadline_ms")
    _config.set("serve_queue_deadline_ms", 30.0)
    try:
        @serve.batch(max_batch_size=1, batch_wait_timeout_s=0.0)
        def slow(items):
            time.sleep(0.15)
            return items

        def call_slow(i):
            try:
                return slow(i)
            except ServeOverloadedError:
                return "shed"

        out = _at_once(call_slow, n=3)
    finally:
        _config.set("serve_queue_deadline_ms", deadline)
    assert "shed" in out
    shed = [w["args"] for w in _ring("serve.replica.wait")
            if w["args"]["shed"]]
    assert len(shed) == out.count("shed")
    assert all(a["call_us"] == 0 and a["queue_wait_us"] >= 30_000
               and a["n"] == 0 for a in shed)
