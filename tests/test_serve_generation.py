"""The generation engine through ``serve.run`` on the CPU: a tiny
Granite-shaped model behind ``serve.deployment(generation_slots=...)``."""

import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_tiny as tiny
import ray_tpu
from ray_tpu import observability, serve
from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler
from ray_tpu.models import transformer
from ray_tpu.models.generation import TransformerGenerator
from ray_tpu.serve.generation import GenerationEngine, parse_request
from ray_tpu.util import metrics

SLOTS, CACHE, BUCKETS = 3, 48, (8, 16, 32)
NAME = "tiny-granite"


class TinyGranite(TransformerGenerator):
    """The deployment's class: the slot model over the tiny tree."""

    def __init__(self):
        super().__init__(tiny.config(), tiny.params(), slots=SLOTS,
                         cache_len=CACHE, length_buckets=BUCKETS)
        self.warm_up()


@functools.lru_cache(maxsize=None)
def _forward():
    """The whole forward over one padded row, compiled once (positions to the
    right change nothing before them)."""
    cfg, params = tiny.config(), tiny.params()
    return jax.jit(lambda row: transformer.apply(params, row[None], cfg)[0])


def greedy(prompt, n):
    """A plain greedy loop: the whole forward again for every token."""
    tokens, logits = list(prompt), []
    for _ in range(n):
        row = np.zeros((CACHE,), np.int32)
        row[:len(tokens)] = tokens
        last = _forward()(jnp.asarray(row))[len(tokens) - 1]
        tokens.append(int(jnp.argmax(last)))
        logits.append(float(jnp.max(last)))
    return tokens[len(prompt):], logits


def _requests(count):
    rng = np.random.default_rng(5)
    return [{"prompt": rng.integers(0, 64, int(rng.integers(3, 30))).tolist(),
             "max_new_tokens": int(rng.integers(1, 7))}
            for _ in range(count)]


@contextlib.contextmanager
def spans_on():
    profiling = _config.get("profiling_enabled")
    _config.set("profiling_enabled", True)
    get_profiler().clear()
    observability.enable()
    try:
        yield
    finally:
        observability.disable()
        _config.set("profiling_enabled", profiling)
        get_profiler().clear()


def _spans(name):
    return [e["args"] for e in get_profiler().chrome_trace()
            if e["name"] == name]


@pytest.fixture(scope="module")
def served():
    """One deployment for the module: eight requests from eight threads at
    once (more than the slots), their replies, the engine's spans and the
    replica's metrics."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start()
    try:
        deployment = serve.deployment(name=NAME, generation_slots=SLOTS)(
            TinyGranite)
        handle = serve.run(deployment.bind(), name=NAME, route_prefix="/gen")
        requests = _requests(8)
        replies = [None] * len(requests)

        def call(i):
            replies[i] = handle.remote(requests[i]).result(timeout=120)

        with spans_on():
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(len(requests))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            spans = {name: _spans(name) for name in (
                "serve.generate.prefill", "serve.generate.step",
                "serve.generate.reply", "serve.replica.wait")}
        info = ray_tpu.get(
            serve.api._get_controller().get_replica_handles.remote(NAME))
        replica_metrics = ray_tpu.get(info["handles"][0].get_metrics.remote())
        bad = []
        for item in ({"prompt": [1] * 40, "max_new_tokens": 2},
                     {"prompt": [1] * 30, "max_new_tokens": 30},
                     {"prompt": [], "max_new_tokens": 2}, [1, 2, 3]):
            try:
                handle.remote(item).result(timeout=60)
                bad.append(None)
            except Exception as e:  # noqa: BLE001 - what the caller is told
                bad.append(str(e))
        after = handle.remote(requests[0]).result(timeout=120)
        yield {"requests": requests, "replies": replies, "spans": spans,
               "metrics": replica_metrics, "bad": bad, "after": after}
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_more_requests_than_slots_are_all_answered_with_their_tokens(served):
    for request, reply in zip(served["requests"], served["replies"]):
        tokens, logits = greedy(request["prompt"], request["max_new_tokens"])
        assert reply["tokens"] == tokens
        np.testing.assert_allclose(reply["logits"], logits, rtol=1e-4,
                                   atol=1e-6)


def test_the_engine_leaves_its_spans(served):
    requests, spans = served["requests"], served["spans"]
    prefill = spans["serve.generate.prefill"]
    assert sorted(s["len"] for s in prefill) == sorted(
        len(r["prompt"]) for r in requests)
    assert all(s["bucket"] == next(b for b in BUCKETS if b >= s["len"])
               and 0 <= s["slot"] < SLOTS and s["waited_us"] >= 0
               for s in prefill)
    steps = spans["serve.generate.step"]
    # a request's first token is its prefill's; every other one a step's
    assert sum(s["active"] for s in steps) == sum(
        r["max_new_tokens"] - 1 for r in requests)
    assert all(1 <= s["active"] <= SLOTS for s in steps)
    # the K/V rows the step's sequences hold and the rows its attention
    # reads: without the kernels every row of the one attention layer's cache
    assert all(s["active"] <= s["live_rows"] <= s["read_rows"]
               == SLOTS * CACHE for s in steps)
    assert sum(s["finished"] for s in steps) == sum(
        r["max_new_tokens"] > 1 for r in requests)
    assert sorted(s["n_new"] for s in spans["serve.generate.reply"]) \
        == sorted(r["max_new_tokens"] for r in requests)


def test_a_callers_wait_carries_what_the_engine_did_in_its_own_trace(served):
    """Every caller's ``serve.replica.wait`` is in the trace its prefill
    joined, and says ``by="generate"`` with the prefill's own numbers."""
    requests, spans = served["requests"], served["spans"]
    waits = spans["serve.replica.wait"]
    assert len(waits) == len(requests)
    prefill = {s["trace_id"]: s for s in spans["serve.generate.prefill"]}
    replies = {s["trace_id"]: s for s in spans["serve.generate.reply"]}
    assert len(prefill) == len(requests)      # one trace a request
    for w in waits:
        mine = prefill[w["trace_id"]]
        assert w["by"] == "generate"
        assert w["waited_us"] == mine["waited_us"]
        assert (w["slot"], w["len"], w["bucket"]) == (
            mine["slot"], mine["len"], mine["bucket"])
        assert w["n_new"] == replies[w["trace_id"]]["n_new"]
        assert w["steps"] == w["n_new"] - 1
    assert sorted(w["len"] for w in waits) == sorted(
        len(r["prompt"]) for r in requests)


def test_get_metrics_carries_the_engines_counts(served):
    m, requests = served["metrics"], served["requests"]
    assert m["generate_slots"] == SLOTS
    assert m["generate_admitted"] == m["generate_replies"] == len(requests)
    assert m["generate_prefill_tokens"] == sum(len(r["prompt"])
                                               for r in requests)
    assert m["generate_decode_tokens"] == sum(r["max_new_tokens"] - 1
                                              for r in requests)
    assert m["generate_steps"] >= max(r["max_new_tokens"] - 1
                                      for r in requests)
    assert m["num_ongoing_requests"] == 0 and m["queue_depth"] == 0
    assert m["num_total_requests"] == len(requests)
    # a wait for a slot and a time in one for every request
    assert sum(m["perf"]["queue_wait"]["counts"]) == len(requests)
    assert sum(m["perf"]["execute"]["counts"]) == len(requests)
    assert m["ewma_item_ms"] > 0


def test_the_counters_are_in_the_registry(served):
    from ray_tpu.observability.metric_names import (GENERATE_ADMITTED,
                                                     GENERATE_SLOTS_OCCUPIED,
                                                     GENERATE_STEPS,
                                                     GENERATE_TOKENS)
    text = metrics.generate_prometheus_text()
    for name in (GENERATE_ADMITTED, GENERATE_SLOTS_OCCUPIED, GENERATE_STEPS,
                 GENERATE_TOKENS):
        assert name in text
    assert 'phase="prefill"' in text and 'phase="decode"' in text


def test_a_request_the_slots_cannot_hold_is_refused_and_costs_no_slot(served):
    too_long, no_room, empty, bare = served["bad"]
    assert "longer than the last bucket" in too_long
    assert "do not fit a slot" in no_room
    assert "at least one token" in empty
    assert "a generation request is" in bare
    assert served["after"] == served["replies"][0]


def test_a_deployment_that_generates_is_a_class_that_does_not_batch():
    with pytest.raises(ValueError, match="takes a class"):
        serve.deployment(generation_slots=2)(lambda x: x)
    with pytest.raises(ValueError, match="takes a class"):
        serve.deployment(generation_slots=2, max_batch_size=4)(TinyGranite)
    with pytest.raises(ValueError, match="max_concurrent_queries"):
        serve.deployment(generation_slots=8, max_concurrent_queries=4)(
            TinyGranite)
    with pytest.raises(ValueError, match="a generation request is"):
        parse_request({"prompt": [1]})


class _Scripted:
    """A slot model that counts: slot s's token at step t is 100 s + t."""
    slots = 2

    def __init__(self):
        self.steps, self.fail, self.fail_at = 0, False, None
        self.gate = threading.Event()
        self.gate.set()

    def admit(self, prompt, slot):
        self.gate.wait(30)
        return (np.int32(prompt[0]), np.float32(0.5)), len(prompt)

    def step(self, active):
        if self.fail or self.steps == self.fail_at:
            self.fail_at = None
            raise RuntimeError("the device is gone")
        self.steps += 1
        return (np.arange(self.slots) * 100 + self.steps,
                np.full(self.slots, float(self.steps)))

    def read(self, handle):
        return handle


def test_the_engine_alone_frees_a_slot_at_its_last_step_and_fails_whole():
    model = _Scripted()
    engine = GenerationEngine(model, "scripted", "scripted-engine")
    out = {}

    def call(i, n):
        try:
            out[i] = engine.submit({"prompt": [i], "max_new_tokens": n})
        except RuntimeError as e:
            out[i] = str(e)

    threads = [threading.Thread(target=call, args=(i, n))
               for i, n in enumerate((3, 1, 2, 4))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert [len(out[i]["tokens"]) for i in range(4)] == [3, 1, 2, 4]
    assert [out[i]["tokens"][0] for i in range(4)] == [0, 1, 2, 3]
    assert engine.counts()["generate_replies"] == 4
    # a failure reaches a request whose last step is out and unread too
    model.fail = True
    call(9, 3)
    assert out[9] == "the device is gone"
    threads = [threading.Thread(target=call, args=(i, n))
               for i, n in ((20, 2), (21, 5))]
    model.fail = False
    model.fail_at = model.steps + 1
    model.gate.clear()      # both are admitted before the first step
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30
    while engine.depth() != 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    model.gate.set()
    for t in threads:
        t.join(30)
    assert out[20] == out[21] == "the device is gone"
    model.fail = False
    call(10, 2)
    assert len(out[10]["tokens"]) == 2
    engine.shutdown()


def test_the_proxys_socket_holds_a_burst_of_callers_until_they_are_accepted():
    """64 callers that connect before the proxy's thread accepts one (a
    closed loop's start) are all in the kernel's queue: at ``socketserver``'s
    default of 5 the seventh's SYN is dropped and sent again a second later,
    then 3, 7 and 15 s later."""
    import socket
    from http.server import BaseHTTPRequestHandler

    from ray_tpu.serve._private.http_proxy import _Server
    server = _Server(("127.0.0.1", 0), BaseHTTPRequestHandler)  # none accepts
    callers = []
    try:
        for _ in range(64):
            callers.append(socket.create_connection(server.server_address,
                                                    timeout=0.5))
    finally:
        for caller in callers:
            caller.close()
        server.server_close()
    assert len(callers) == 64
