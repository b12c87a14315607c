"""One span primitive, two sinks: the profiler ring (``tracing_enabled``)
and, while a ``jax.profiler`` session records, the session's own trace.

A CPU profiler session in a temporary directory stands in for the chip's:
the annotations land on the host's plane of the ``.xplane.pb`` the same
way, and ``jax.profiler.ProfileData`` reads them back.
"""

import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import pytest

import ray_tpu
from ray_tpu import observability, serve
from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler
from ray_tpu.observability import metric_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IDS = ("trace_id", "span_id", "parent_span_id")


@pytest.fixture(autouse=True)
def _switches_restored():
    profiling = _config.get("profiling_enabled")
    yield
    observability.disable()
    _config.set("profiling_enabled", profiling)
    get_profiler().clear()


class Session:
    """A CPU profiler session; after it, ``spans`` holds every host event
    named ``ray_tpu.*`` as ``(name, thread, attributes)``."""

    def __init__(self, tmp_path):
        self.dir, self.spans = str(tmp_path), []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        thread = 0
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                thread += 1
                for ev in line.events:
                    if ev.name.startswith(observability.ANNOTATION_PREFIX):
                        self.spans.append((ev.name[len("ray_tpu."):], thread,
                                           _attributes(ev.stats)))

    def named(self, name, **where):
        return [(n, t, a) for n, t, a in self.spans if n == name
                and all(a.get(k) == v for k, v in where.items())]


def _attributes(stats):
    """An annotation's attributes with its ids as the ring has them: this
    sink writes an id behind a letter, so that the trace keeps it text."""
    attrs = dict(stats)
    for key in IDS:
        assert attrs[key].startswith(observability.ANNOTATION_ID_PREFIX)
        attrs[key] = attrs[key][1:]
    return attrs


def _ring(name):
    return [e for e in get_profiler().chrome_trace() if e["name"] == name]


# -- the primitive ----------------------------------------------------------


@pytest.mark.parametrize("tracing", [False, True], ids=["ring-off", "ring-on"])
@pytest.mark.parametrize("session", [False, True],
                         ids=["no-session", "session"])
def test_each_sink_gets_the_span_exactly_when_it_is_on(tmp_path, tracing,
                                                       session):
    _config.set("profiling_enabled", True)
    get_profiler().clear()
    if tracing:
        observability.enable()
    assert not observability.session_on()
    trace = Session(tmp_path)
    if session:
        with trace:
            assert observability.session_on() and observability.live()
            with observability.span("unit.work", cat="test", n=3) as s:
                ids = (s.trace_id, s.span_id)
    else:
        assert observability.live() == tracing
        with observability.span("unit.work", cat="test", n=3) as s:
            ids = (s.trace_id, s.span_id)
    in_trace, in_ring = trace.named("unit.work"), _ring("unit.work")
    assert len(in_trace) == (1 if session else 0)
    assert len(in_ring) == (1 if tracing else 0)
    assert all(ids) == (tracing or session)   # ids are minted only when live
    for args in [a for _, _, a in in_trace] + [e["args"] for e in in_ring]:
        # both sinks carry the ids the span itself reported, and its
        # attributes
        assert (args["trace_id"], args["span_id"]) == ids
        # (the trace drops an attribute whose value is empty)
        assert args.get("parent_span_id", "") == "" and args["n"] == 3


def test_a_span_nests_under_the_open_one_in_both_sinks(tmp_path):
    _config.set("profiling_enabled", True)
    observability.enable()
    with Session(tmp_path) as trace:
        with observability.span("unit.outer") as outer:
            with observability.span("unit.inner") as inner:
                assert inner.trace_id == outer.trace_id
            inner_ids = (outer.trace_id, outer.span_id)
    (_, _, a), = trace.named("unit.inner")
    ring, = _ring("unit.inner")
    assert (a["trace_id"], a["parent_span_id"]) == inner_ids
    assert {k: a[k] for k in IDS} == {k: ring["args"][k] for k in IDS}


def test_set_adds_attributes_known_later_to_both_sinks(tmp_path):
    _config.set("profiling_enabled", True)
    observability.enable()
    with Session(tmp_path) as trace:
        with observability.span("unit.reply", route="/m") as s:
            s.set(bytes=17, replica="d#0")
    (_, _, a), = trace.named("unit.reply")
    assert a["bytes"] == 17 and a["route"] == "/m"
    # "," "=" and "#" delimit attributes in the profiler's trace
    assert a["replica"] == "d~0"
    assert _ring("unit.reply")[0]["args"]["replica"] == "d#0"
    idle = observability.span("unit.off")
    observability.disable()
    with idle:
        idle.set(bytes=1)              # nothing to do on a span not live
    assert idle.args == {}


def test_parent_carries_a_context_to_another_thread(tmp_path):
    with Session(tmp_path) as trace:
        with observability.span("unit.submit") as s:
            ctx = observability.current()

            def flusher():
                with observability.span("unit.flush", parent=ctx):
                    pass
            t = threading.Thread(target=flusher)
            t.start()
            t.join()
            want = (s.trace_id, s.span_id)
    (_, t_submit, _), = trace.named("unit.submit")
    (_, t_flush, a), = trace.named("unit.flush")
    assert (a["trace_id"], a["parent_span_id"]) == want
    assert t_flush != t_submit


@pytest.mark.parametrize("tricky", ["0012345678901234", "12e4567890123456",
                                    "1234567890123456"])
def test_an_id_that_looks_like_a_number_comes_back_as_it_went_in(
        tmp_path, monkeypatch, tricky):
    """The trace stores an attribute as a number wherever it parses as one
    (digits alone lose their leading zeros, ``12e45...`` reads as inf), so
    the annotation writes an id behind a letter; the id itself stays the
    plain hex every other plane carries."""
    assert re.fullmatch(r"[0-9a-f]{16}", observability.mint_id())
    monkeypatch.setattr(observability, "mint_id", lambda: tricky)
    observability.enable()
    with Session(tmp_path) as trace:
        with observability.span("unit.outer"):
            with observability.span("unit.inner") as s:
                assert (s.trace_id, s.span_id) == (tricky, tricky)
    (_, _, a), = trace.named("unit.inner")
    assert [a[k] for k in IDS] == [tricky] * 3
    assert [_ring("unit.inner")[0]["args"][k] for k in IDS] == [tricky] * 3


def test_a_span_attributes_stack_samples_and_a_runtime_span_does_not():
    """``span`` tags its thread for the stack sampler while it is open; the
    runtime's task and actor spans never did (an actor's thread may sit in
    one call for the actor's life), and do not now."""
    from ray_tpu.observability import sampler
    _config.set("profiling_enabled", True)
    observability.enable()
    sampler.start(hz=50.0)
    try:
        me = threading.get_ident()
        with observability.task_span("task.execute", "f", "task", "node:x",
                                     None) as t:
            assert t.live and me not in sampler._trace_stacks
            with observability.span("unit.tagged") as s:
                assert sampler._trace_stacks[me] == [s.trace_id]
            assert me not in sampler._trace_stacks
    finally:
        sampler.stop()


def test_importing_observability_does_not_import_jax():
    code = (
        "import sys\n"
        "from ray_tpu import observability\n"
        "assert 'jax' not in sys.modules, 'import pulled jax in'\n"
        "assert not observability.session_on() and not observability.live()\n"
        "with observability.span('x') as s:\n"
        "    assert s.trace_id == ''\n"
        "assert 'jax' not in sys.modules, 'a span pulled jax in'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": REPO})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def _literal_spans():
    found = set()
    for path in glob.glob(os.path.join(REPO, "ray_tpu", "**", "*.py"),
                          recursive=True):
        if os.sep + "devtools" + os.sep in path:
            continue
        with open(path) as f:
            found.update(re.findall(
                r"observability\.(?:task_)?span\(\s*\"([a-z_.]+)\"", f.read()))
    return found


def test_every_literal_span_name_is_declared():
    used = _literal_spans()
    assert {"serve.request", "serve.batch.linger", "task.execute",
            "actor.call"} <= used
    assert used <= metric_names.SPANS, used - metric_names.SPANS


# -- the runtime's spans ----------------------------------------------------


def test_a_task_span_keeps_its_ring_name_and_gains_an_annotation(tmp_path):
    """profiling_enabled alone (tracing off) keeps the ring as it was: the
    function's name under ``task``. A session adds ``task.execute``."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=2, ignore_reinit_error=True)
    try:
        ray_tpu.set_profiling_enabled(True)
        get_profiler().clear()

        @ray_tpu.remote(num_tpus=2)
        def granted():
            return len(ray_tpu.get_runtime_context().get_tpu_devices())

        with Session(tmp_path) as trace:
            assert ray_tpu.get(granted.remote()) == 2
        (_, _, a), = trace.named("task.execute")
        assert a["function"].endswith("granted")
        assert a["sched_wait_us"] >= 0
        assert a["devices"] == "0/1"     # R8: a grant is the first n
        ring, = [e for e in get_profiler().chrome_trace()
                 if e["name"].endswith("granted")]
        assert ring["cat"] == "task" and ring["pid"].startswith("node:")
        assert ring["dur"] > 0
        assert ({k: ring["args"][k] for k in IDS}
                == {k: a.get(k, "") for k in IDS})
        assert not _ring("task.execute")
    finally:
        ray_tpu.shutdown()


def test_a_task_is_completed_whatever_its_bookkeeping_raises(
        ray_start_regular, monkeypatch):
    """Restoring the worker thread's context, the completion hooks and the
    scheduler's kick come after the span and run even where the span's
    block raises (here: the TASK_DONE event)."""
    from ray_tpu._private import worker
    from ray_tpu._private.runtime import task_context
    rt = worker.try_global_runtime()
    seen, emit, fire = {}, rt.emit_event, rt._fire_completion

    def failing_emit(kind, **kw):
        if kind == "TASK_DONE" and kw.get("task", "").endswith("unlucky"):
            raise RuntimeError("event sink down")
        return emit(kind, **kw)

    def fired(spec):
        fire(spec)
        if spec.function_name.endswith("unlucky"):
            seen["ctx_task"] = task_context.task_id
            seen["fired"] = True

    monkeypatch.setattr(rt, "emit_event", failing_emit)
    monkeypatch.setattr(rt, "_fire_completion", fired)

    @ray_tpu.remote
    def unlucky():
        return 7

    ref = unlucky.remote()
    assert ray_tpu.get(ref) == 7
    deadline = time.monotonic() + 10
    while "fired" not in seen and time.monotonic() < deadline:
        time.sleep(0.01)
    assert seen.get("fired"), "completion never fired"
    assert seen["ctx_task"] != ref.task_id()   # the context was restored


@pytest.mark.parametrize("kind", ["sync", "async"])
def test_an_actor_call_reports_its_mailbox_wait(tmp_path, ray_start_regular,
                                                kind):
    ray_tpu.set_profiling_enabled(True)

    @ray_tpu.remote
    class Sync:
        def work(self, s):
            time.sleep(s)
            return s

    @ray_tpu.remote
    class Async:
        async def work(self, s):
            time.sleep(s)
            return s

    actor = (Sync if kind == "sync" else Async).remote()
    ray_tpu.get(actor.work.remote(0.0))
    get_profiler().clear()
    with Session(tmp_path) as trace:
        # the second call waits in the mailbox while the first one runs
        refs = [actor.work.remote(0.05), actor.work.remote(0.0)]
        ray_tpu.get(refs)
    calls = trace.named("actor.call", method="work")
    assert len(calls) == 2
    waits = sorted(a["mailbox_wait_us"] for _, _, a in calls)
    assert 0 <= waits[0] < 40_000 <= waits[1] < 5_000_000
    ring = [e for e in get_profiler().chrome_trace()
            if e["name"] == f"{kind.capitalize()}.work"]
    assert len(ring) == 2 and all(e["cat"] == "actor_task" for e in ring)


def test_actor_creation_records_the_granted_devices(tmp_path):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=1, ignore_reinit_error=True)
    try:
        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def devices(self):
                return len(ray_tpu.get_runtime_context().get_tpu_devices())

        with Session(tmp_path) as trace:
            assert ray_tpu.get(Holder.remote().devices.remote()) == 1
        (_, _, a), = trace.named("actor.init")
        assert str(a["devices"]) == "0"    # one id reads back as a number
    finally:
        ray_tpu.shutdown()


# -- one request through proxy, router and a batched replica ----------------

TABLE_B = ("serve.request", "serve.route", "serve.await_replica",
           "serve.reply", "actor.call", "serve.batch.linger",
           "serve.batch.execute", "serve.batch.call")


@pytest.fixture(scope="module")
def one_request(tmp_path_factory):
    """One HTTP request under a session, tracing_enabled off: the spans of
    the request and the always-on gauge after ``serve.shutdown()``."""
    from ray_tpu.util import metrics
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8.0, ignore_reinit_error=True)
    serve.start()

    @serve.deployment(name="doubler", max_batch_size=4,
                      batch_wait_timeout_s=0.02, pad_batch_to=(2, 4))
    class Doubler:
        def __init__(self):
            time.sleep(0.05)

        def __call__(self, items):
            return [2 * x for x in items]

    try:
        serve.run(Doubler.bind(), route_prefix="/double")
        url = serve.start_http_proxy() + "/double"

        def post(x):
            req = urllib.request.Request(
                url, data=json.dumps(x).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())

        assert post(1) == 2            # the router and the flusher exist
        with Session(tmp_path_factory.mktemp("session")) as trace:
            assert post(21) == 42
            # the reply is written inside serve.reply inside serve.request:
            # the handler's thread closes them after the client has its
            # answer, and a span still open when the session stops is lost
            time.sleep(0.2)
        serve.shutdown()
        gauges = [s for f in metrics.snapshot()
                  if f["name"] == metric_names.REPLICA_INIT_GAUGE
                  for s in f["samples"]]
    finally:
        ray_tpu.shutdown()
    request, = trace.named("serve.request")
    return {"trace": trace, "trace_id": request[2]["trace_id"],
            "gauges": gauges}


@pytest.mark.parametrize("name", TABLE_B)
def test_the_request_yields_every_span_once_in_one_trace(one_request, name):
    where = {"method": "handle_request"} if name == "actor.call" else {}
    mine = [s for s in one_request["trace"].named(name, **where)
            if s[2]["trace_id"] == one_request["trace_id"]]
    assert len(mine) == 1, one_request["trace"].spans
    assert name in metric_names.SPANS


def test_the_requests_spans_cross_three_threads_with_their_numbers(
        one_request):
    trace, tid = one_request["trace"], one_request["trace_id"]

    def one(name, **where):
        (_, thread, attrs), = [s for s in trace.named(name, **where)
                               if s[2]["trace_id"] == tid]
        return thread, attrs

    handler, request = one("serve.request")
    actor, call = one("actor.call", method="handle_request")
    flusher, linger = one("serve.batch.linger")
    assert len({handler, actor, flusher}) == 3
    for name in ("serve.route", "serve.await_replica", "serve.reply"):
        thread, attrs = one(name)
        assert thread == handler
        assert attrs["parent_span_id"] == request["span_id"]
    assert one("serve.batch.execute")[0] == flusher
    assert one("serve.batch.call")[0] == flusher
    assert request["route"] == "/double"
    route = one("serve.route")[1]
    assert route["replica"].startswith("doubler") and route["in_flight"] == 1
    assert call["parent_span_id"] == route["span_id"]
    assert 0 <= call["mailbox_wait_us"] < 1_000_000
    assert linger["parent_span_id"] == call["span_id"]
    assert linger["depth"] == 1 and linger["cap"] == 4
    # the replica's second request: the first came 20 ms and more before
    # it and a call takes microseconds, so no neighbour is due and the
    # 20 ms linger is a bound that is not waited out
    assert linger["cut"] == "not_due"
    assert linger["gap_est_us"] >= 20_000 > linger["call_est_us"] >= 0
    assert linger["oldest_wait_us"] >= 0
    execute = one("serve.batch.execute")[1]
    assert (execute["n"], execute["padded_n"]) == (1, 2)
    assert execute["batch"] == 2                  # the second batch run
    assert one("serve.batch.call")[1]["parent_span_id"] == execute["span_id"]
    assert one("serve.reply")[1]["bytes"] == len("42")


def test_the_replicas_init_gauge_outlives_the_replica(one_request):
    # the gauge is the process's: a replica that another file ran on this
    # worker has left its own
    (_, tags, seconds), = [g for g in one_request["gauges"]
                           if dict(map(tuple, g[1]))["deployment"] == "doubler"]
    assert 0.05 <= seconds < 5.0


# -- the kernels have names -------------------------------------------------


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_the_pallas_calls_carry_their_names(kernel):
    import jax.numpy as jnp
    from ray_tpu.ops import flash_attention
    x = jnp.ones((1, 128, 2, 64), jnp.float32)

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v).sum(),
            argnums=(0, 1, 2))(q, k, v)

    # interpret mode on the CPU: the name rides the lowered text's locations
    text = jax.jit(fwd_bwd).lower(x, x, x).as_text(debug_info=True)
    assert re.search(rf"\b{kernel}\b", text)
    assert f"name={kernel}" in str(jax.make_jaxpr(fwd_bwd)(x, x, x))
