"""The readers of the program's own spans (``benchmark/program_spans.py``)
on synthetic planes, the metric files that name them, the search for a
run's trace among several, a traced toy cell on the CPU, and the steady
trace recorded on the chip during PR 26."""

import glob
import json
import os
import re
import tempfile

import pytest

import benchmark_tiny
from benchmark import harness, manifest, program_spans as ps, reducers
from benchmark.program_spans import Span
from benchmark.trace_reduce import DeviceTrace, Event, Reduced

REPO = benchmark_tiny.REPO
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
US = 1000      # the synthetic planes count in microseconds
NEW_METRICS = sorted(
    os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(REPO, "benchmark", "layer_metrics", "*.json"))
    if "program_spans:" in open(p).read())
HANDLER_1, HANDLER_2, ACTOR, FLUSHER = 1, 2, 3, 4      # threads


def sp(name, start_us, end_us, thread=HANDLER_1, **attrs):
    return Span(name, start_us * US, end_us * US, thread, attrs)


def ev(name, start_us, end_us, category=""):
    return Event(name, start_us * US, end_us * US, category)


def _ctx(reduced, cell="cell"):
    return reducers.Context(cell=type("C", (), {"name": cell})(),
                            trace=reduced, counters={},
                            device_kind="TPU v5 lite")


def _steady():
    """A window of 1,000 us. The device is busy 300-400 and 700-900, so
    idle 700. Two requests on two handler threads overlap; the flusher
    lingers and executes in turn; one request began before the window."""
    ops = [ev("fusion.1", 300, 400), ev("fusion.2", 700, 900),
           ev("fusion.0", 0, 50)]                       # before the window
    spans = [
        sp("serve.request", 50, 450, HANDLER_1, route="/m"),   # cut: 100..450
        sp("serve.route", 52, 54, HANDLER_1),
        sp("serve.await_replica", 60, 440, HANDLER_1),
        sp("serve.request", 200, 950, HANDLER_2, route="/m"),
        sp("serve.route", 210, 214, HANDLER_2),
        sp("serve.await_replica", 220, 930, HANDLER_2),
        sp("serve.reply", 932, 948, HANDLER_2, bytes=9),
        sp("actor.call", 56, 445, ACTOR, method="handle_request",
           mailbox_wait_us=2),
        sp("actor.call", 216, 935, ACTOR, method="handle_request",
           mailbox_wait_us=400),
        sp("actor.call", 500, 510, ACTOR, method="get_metrics",
           mailbox_wait_us=9000),
        sp("actor.call", 520, 530, ACTOR, method="handle_request",
           mailbox_wait_us=-1),                          # never stamped
        sp("serve.batch.linger", 60, 280, FLUSHER),      # cut: 100..280
        sp("serve.batch.execute", 280, 420, FLUSHER, n=1),
        sp("serve.batch.call", 285, 415, FLUSHER),
        sp("serve.batch.linger", 420, 680, FLUSHER),
        sp("serve.batch.execute", 680, 920, FLUSHER, n=3),
        sp("serve.batch.call", 690, 915, FLUSHER),
    ]
    reduced = Reduced((100 * US, 1100 * US), {0: DeviceTrace(ops, [])}, [])
    return reduced, spans


@pytest.fixture
def steady(monkeypatch):
    reduced, spans = _steady()
    monkeypatch.setattr(ps, "program_spans", lambda ctx: spans)
    return _ctx(reduced)


def test_intersect_is_what_both_unions_cover():
    assert ps.intersect([(0, 10)], [(2, 4), (8, 12)]) == [(2, 4), (8, 10)]
    assert ps.intersect([(0, 2), (4, 6)], [(1, 5)]) == [(1, 2), (4, 5)]
    assert ps.intersect([(0, 2)], [(2, 4)]) == []
    assert ps.intersect([], [(0, 1)]) == []


# batch execute: 280-300, 400-420, 680-700, 900-920 = 80; linger (cut at
# the window's edge): 100-280 + 420-680 = 440; request, neither batch span
# open: 920-950 = 30; nothing open: 950-1100 = 150; together the idle 700
IDLE_WANT = {"batch_execute": 8.0, "linger": 44.0, "request": 3.0,
             "none": 15.0}


@pytest.mark.parametrize("cls", sorted(IDLE_WANT))
def test_idle_classes_are_exclusive_in_their_order(steady, cls):
    got = ps.idle_class_pct(steady, {"class": cls})
    assert got == pytest.approx(IDLE_WANT[cls])


def test_idle_classes_sum_to_the_idle_share(steady):
    r = steady.trace
    total = sum(ps.idle_class_pct(steady, {"class": c}) for c in IDLE_WANT)
    assert total == pytest.approx(100.0 * (1 - r.busy_s / r.window_s))
    ns = ps.idle_classes([(100 * US, 300 * US)], _steady()[1], r.window)
    assert sum(ns.values()) == 200 * US


def test_span_means_leave_out_a_span_the_windows_edge_cuts(steady):
    # 420..680 only: the first linger began before the window
    assert ps.span_mean_ms(steady, {"span": "serve.batch.linger"}) \
        == pytest.approx(0.260)
    # of two serve.route spans the first lies before the window
    assert ps.span_mean_ms(steady, {"span": "serve.route"}) \
        == pytest.approx(0.004)


def test_an_attribute_is_averaged_over_the_spans_that_match(steady):
    p = {"span": "actor.call", "where": {"method": "handle_request"},
         "attr": "mailbox_wait_us", "scale": 0.001}
    # 400 us: the first call began before the window, get_metrics is
    # another method, -1 was never stamped
    assert ps.span_mean_ms(steady, p) == pytest.approx(0.4)
    assert ps.span_mean_ms(steady, {**p, "where": {"method": "absent"}}) \
        is None


def test_self_time_subtracts_the_same_threads_child_only(steady):
    p = {"span": "serve.request", "less": "serve.await_replica"}
    # the second request alone lies inside: 750 less its own 710; the
    # first request's await_replica is another thread's
    assert ps.span_self_ms(steady, p) == pytest.approx(0.040)


def test_the_metrics_of_the_reply_and_of_the_batch_read_their_spans(steady):
    def read(name):
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        return reducers.resolve(spec["reducer"])(steady, spec["params"])
    assert read("reply_ms.steady") == pytest.approx(0.016)
    # execute less the call inside it: (140 - 130) and (240 - 225) us
    assert read("batcher_self_ms.steady") == pytest.approx(0.0125)
    assert read("batch_size_mean.steady") == pytest.approx(2.0)


@pytest.mark.parametrize("kernel,want", [("flash_fwd", 0.150),
                                         ("flash_dq", 0.100),
                                         ("flash_dkv", 0.120)])
def test_kernel_time_goes_by_the_kernels_name(kernel, want):
    call = "custom-call tpu_custom_call"
    ops = [ev("while.1", 100, 900),                    # holds the body's
           ev("flash_fwd.16", 100, 200, call),
           ev("flash_fwd.17", 200, 400, call),
           ev("flash_dq.10", 400, 500, call),
           ev("transpose_jvp_flash_dkv__.1", 500, 620, call),
           ev("closed_call.7", 620, 700, call),        # a kernel with no name
           ev("flash_fwd_fusion.3", 700, 800, "fusion"),   # not a kernel
           ev("flash_dq.11", 950, 1050, call)]         # after the window
    ctx = _ctx(Reduced((100 * US, 1000 * US), {0: DeviceTrace(ops, [])}, []))
    assert ps.kernel_ms(ctx, {"kernel": kernel}) == pytest.approx(want)


def test_the_gauge_reader_takes_the_cells_own_deployment():
    from ray_tpu.util.metrics import Gauge
    g = Gauge("bench_test_init_seconds", "test", ("deployment", "replica"))
    g.set(1.5, tags={"deployment": "cell", "replica": "cell#0"})
    g.set(2.5, tags={"deployment": "cell", "replica": "cell#1"})
    g.set(9.0, tags={"deployment": "other", "replica": "other#0"})
    empty = Reduced((0, 10), {}, [])
    p = {"name": "bench_test_init_seconds"}
    assert ps.gauge(_ctx(empty, "cell"), p) == 2.5
    assert ps.gauge(_ctx(empty, "absent"), p) is None
    assert ps.gauge(_ctx(empty, "cell"), {"name": "no_such_gauge"}) is None


# -- the metric files -------------------------------------------------------


def test_the_issues_metrics_are_there_but_the_retired_gauge_and_three_more():
    # PR 60 retired ``replica_init_s.serve``: it read ``serve_startup_s.serve``
    # less 0.06 s (ledger, PR 59); the reader ``gauge`` stays
    assert "replica_init_s.serve" not in NEW_METRICS
    assert NEW_METRICS == sorted([
        "idle_no_request_pct.steady", "idle_linger_pct.steady",
        "idle_request_path_pct.steady", "idle_batch_host_pct.steady",
        "idle_batch_host_pct.offline", "linger_ms.steady",
        "proxy_self_ms.steady", "route_ms.steady",
        "actor_dispatch_ms.steady",
        "flash_fwd_ms.train", "flash_dq_ms.train", "flash_dkv_ms.train",
        # so that serve.reply, serve.batch.call and the batch's n have a
        # reader (REVIEW of PR 26)
        "reply_ms.steady", "batcher_self_ms.steady",
        "batch_size_mean.steady"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metrics_file_resolves_and_agrees_with_its_entry(name):
    m = manifest.Manifest(REPO)
    entry, = [e for e in m.data["per_layer"] if e["name"] == name]
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    for key, value in entry.items():
        assert spec[key] == value, (name, key)
    assert entry["source"] in ("program_span", "program_counter",
                               "device_trace")
    assert callable(reducers.resolve(spec["reducer"]))
    assert spec["what"]
    # the cell gets the file's reader and parameters
    cell = m.cell(entry["workloads"][0])
    mine, = [x for x in cell.per_layer if x["name"] == name]
    assert mine["reducer"] == spec["reducer"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_that_finds_no_span_returns_nothing(name, monkeypatch):
    """What the parent commit's program gives: a device that worked, no
    ``ray_tpu.*`` span, kernels named after the enclosing computation, no
    gauge. The harness then leaves the metric out."""
    monkeypatch.setattr(ps, "program_spans", lambda ctx: ())
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    ops = [ev("closed_call.7", 10, 20, "custom-call tpu_custom_call")]
    worked = Reduced((0, 100 * US), {0: DeviceTrace(ops, [])}, [])
    read = reducers.resolve(spec["reducer"])
    assert read(_ctx(worked), spec["params"]) is None
    # and where there is no device plane at all (the CPU tests' runs)
    assert read(_ctx(Reduced((0, 100 * US), {}, [])), spec["params"]) is None
    assert read(_ctx(None), spec["params"]) is None


# -- finding the run's trace ------------------------------------------------


def _record(root, label):
    """A traced CPU run in a ``bench_trace_*`` directory: the harness's
    window span, a program span inside it. Returns the window as
    ``trace_reduce.load`` reads it."""
    import jax
    from benchmark import trace_reduce
    from ray_tpu import observability
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_", dir=root)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with harness.span(harness.WINDOW_SPAN):
            with observability.span("unit.work", label=label):
                pass
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                             harness.SPAN_PREFIX, harness.WINDOW_SPAN)


def test_a_runs_trace_is_found_among_several_by_its_window(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    first, second = _record(str(tmp_path), "first"), _record(
        str(tmp_path), "second")
    assert first.window != second.window
    for reduced, label in ((first, "first"), (second, "second")):
        span, = ps.program_spans(_ctx(reduced))
        assert (span.name, span.attrs["label"]) == ("unit.work", label)
        assert reduced.window[0] <= span.start <= span.end \
            <= reduced.window[1]
    # a window that is off by a nanosecond is nobody's
    off = Reduced((first.window[0] + 1, first.window[1]), {}, [])
    assert ps.program_spans(_ctx(off)) == ()
    assert ps.program_spans(_ctx(None)) == ()


# -- a traced toy cell on the CPU -------------------------------------------


def test_a_traced_steady_cell_reports_the_span_metrics(tmp_path):
    import ray_tpu
    root = benchmark_tiny.make_root(tmp_path, cells=("tiny-serve-open",))
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    try:
        traced = harness.run_cell("tiny-serve-open", 2**31 + 79, 1.5, True,
                                  root=root, require_tpu=False)
    finally:
        ray_tpu.shutdown()
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    # the reader is held to the span on the profiler's clock: under the
    # toy deployment's bound of 10 ms and its slack, and from nought, since
    # the replica holds a request only while a neighbour is due (PR 38)
    assert 0 <= got["linger_ms.steady"] < 30.0
    assert 0 < got["route_ms.steady"] < got["proxy_self_ms.steady"] < 50.0
    assert 0 <= got["actor_dispatch_ms.steady"] < 50.0
    assert 0 < got["reply_ms.steady"] < got["proxy_self_ms.steady"]
    assert 0 < got["batcher_self_ms.steady"] < 50.0
    assert 1.0 <= got["batch_size_mean.steady"] <= 8.0
    assert 0 < got["serve_startup_s.serve"]
    # no device plane on the CPU: no idle time to divide
    assert not [k for k in got if k.startswith("idle_")]


# -- the steady trace recorded on the chip ----------------------------------


def test_the_steady_trace_recorded_on_the_chip_reduces_to_its_values():
    from benchmark import trace_reduce
    with open(os.path.join(FIXTURES, "serve_steady.expected.json")) as f:
        want = json.load(f)
    path = os.path.join(FIXTURES, "serve_steady.xplane.pb")
    assert os.path.getsize(path) < 2 * 2**20
    reduced = trace_reduce.load(path, "bench.", "window")
    window, spans = ps.read_spans(path)
    assert window == tuple(reduced.window)
    assert reduced.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert reduced.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    counts = {}
    for s in spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    assert counts == want["spans"]
    ctx = _ctx(reduced)
    real = ps.program_spans
    try:
        ps.program_spans = lambda ctx: spans   # the file is not under /tmp
        got = {name: reducers.resolve(reader)(ctx, params)
               for name, (reader, params, _) in want["readers"].items()}
    finally:
        ps.program_spans = real
    for name, (_, _, value) in want["readers"].items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    idle = sum(v for k, v in got.items() if k.startswith("idle_"))
    assert idle == pytest.approx(
        100.0 * (1 - reduced.busy_s / reduced.window_s), abs=1e-6)
    # the ids come back as the plain hex they were minted as
    assert all(re.fullmatch(r"[0-9a-f]{16}", s.attrs[k])
               for s in spans for k in ("trace_id", "span_id"))
    assert {len(s.attrs["parent_span_id"]) for s in spans} == {0, 16}
    # one request's spans share its trace id across three threads
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.attrs.get("trace_id"), []).append(s)
    whole = [group for group in by_trace.values()
             if {"serve.request", "actor.call", "serve.batch.execute"}
             <= {s.name for s in group}]
    assert whole and all(len({s.thread for s in g}) >= 3 for g in whole)
