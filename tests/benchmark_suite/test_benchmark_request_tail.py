"""The reader of single requests (``benchmark/request_tail.py``) on
synthetic spans: the join of a request's spans by its trace and of a member
to its batch, the stages and their remainder, the tail's classes, the holds,
the idle table, every reducer, and the command line's tables on a trace a CPU
profiler session wrote."""

import dataclasses
import glob
import os

import jax
import pytest

from benchmark import reducers, request_tail as rt
from benchmark.program_spans import Span
from benchmark.trace_reduce import DeviceTrace, Event, Reduced

US = 1000       # the synthetic spans count in microseconds
WINDOW = (0, 100_000 * US)
HANDLER, ACTOR, FLUSHER, SAMPLER = 1, 2, 3, 4


def sp(name, start_us, end_us, thread, **attrs):
    return Span(name, start_us * US, end_us * US, thread, attrs)


def request(i, start, *, route=20, mailbox=10, queue=100, call=500, reply=30,
            pre=15, other=25, size=100, size_max=128, n=1, padded_n=2,
            cut="not_due", accept=40, batch=None):
    """The spans of one request that arrives at ``start`` (us): its
    handler's four, its actor call, its wait and (unless ``batch`` names
    another request's) its batch's three on the flusher."""
    trace = f"{i:016x}"
    ids = {"trace_id": trace}
    t_route = start + pre
    t_await = t_route + route
    awaited = mailbox + queue + call + other
    t_reply = t_await + awaited
    end = t_reply + reply
    t_wait = t_await + mailbox + 5
    t_cut = t_wait + queue
    spans = [
        sp("serve.request", start, end, HANDLER + 10 * i, route="/m",
           accept_wait_us=accept, **ids),
        sp("serve.route", t_route, t_await, HANDLER + 10 * i, **ids),
        sp("serve.await_replica", t_await, t_reply, HANDLER + 10 * i, **ids),
        sp("serve.reply", t_reply, end, HANDLER + 10 * i, bytes=9, **ids),
        sp("actor.call", t_await + mailbox, t_reply - 5, ACTOR + 10 * i,
           method="handle_request", mailbox_wait_us=mailbox, **ids),
        sp("serve.replica.wait", t_wait, t_cut + call + 8, ACTOR + 10 * i,
           by="batch", queue_wait_us=queue, call_us=call,
           batch=batch or i + 1, n=n, padded_n=padded_n, size=size,
           size_max=size_max, retried=0, shed=0, **ids),
    ]
    if batch is None:
        spans += [
            sp("serve.batch.linger", t_cut - 50, t_cut - 2, FLUSHER, cut=cut,
               **ids),
            sp("serve.batch.execute", t_cut, t_cut + call + 4, FLUSHER,
               batch=i + 1, n=n, padded_n=padded_n, size_max=size_max, **ids),
            sp("serve.batch.call", t_cut + 2, t_cut + call, FLUSHER, **ids),
        ]
    return spans


def hold(at_us, held_us, cause="gil", holder="loop.py:spin", **attrs):
    return sp("host.hold", at_us, at_us + 1, SAMPLER, held_us=held_us,
              cause=cause, holder=holder, trace_id=f"h{at_us}", **attrs)


def _window():
    """Forty requests 2,000 us apart, all 700 us long but four: one whose
    own call is long, one that waited for another's call, one the host
    was held under, and one slow in the proxy itself."""
    spans = []
    for i in range(40):
        kw = {}
        if i == 7:
            kw = dict(call=9_000, size=2000, size_max=2048, cut="passed")
        elif i == 8:        # arrived while 7's call ran
            kw = dict(queue=6_000, mailbox=1_000)
        elif i == 20:
            kw = dict(other=5_000)
        elif i == 30:
            kw = dict(pre=4_000)
        spans += request(i, 1_000 + 2_000 * i, **kw)
    # held for 3,000 us under request 20's wait (it arrives at 41,000),
    # and once between requests, for 500 us
    spans += [hold(44_500, 3_000), hold(70_950, 500, cause="off_cpu",
                                        holder="")]
    return spans


@pytest.fixture
def rows():
    got, unjoined = rt.requests(_window(), WINDOW)
    assert unjoined == 0 and len(got) == 40
    return got


def test_a_rows_stages_and_its_remainder_add_up_to_its_latency(rows):
    for r in rows:
        assert sum(getattr(r, s) for s in rt.STAGES) + r.other == r.latency
    plain = rows[0]
    assert (plain.order, plain.latency) == (0, 700 * US)
    assert (plain.proxy_self, plain.route, plain.mailbox_wait,
            plain.queue_wait, plain.call, plain.reply, plain.other) == tuple(
        v * US for v in (15, 20, 10, 100, 500, 30, 25))
    assert (plain.accept_wait, plain.size, plain.size_max, plain.n,
            plain.padded_n, plain.batch, plain.cut) == (
        40 * US, 100, 128, 1, 2, 1, "not_due")
    assert plain.call_span == 498 * US and plain.held == 0
    assert [r.order for r in rows] == list(range(40))
    assert rows[7].cut == "passed" and rows[7].size_max == 2048


def test_the_tail_is_the_slowest_tenth_each_with_one_class(rows):
    slow = rt.tail(rows)
    assert [r.order for r in slow] == [7, 8, 20, 30]
    assert [r.klass() for r in slow] == ["own_call", "behind_call", "held",
                                         "other"]
    assert rows[20].held == 3_000 * US      # of a latency of 5,675 us
    # the hold under request 20 covers the whole of request 21 too
    assert rows[21].klass() == "held" and rows[21].latency == 700 * US
    assert all(r.klass() == "own_call" for r in rows
               if r.order not in (7, 8, 20, 21, 30))
    assert rt.tail([]) == [] and len(rt.tail(rows[:5])) == 1


def test_a_hold_under_a_quarter_of_the_latency_is_not_the_rows_class():
    spans = request(0, 1_000, other=5_000) + [hold(3_000, 1_000)]
    (row,), _ = rt.requests(spans, WINDOW)
    assert row.held == 1_000 * US and row.klass() == "other"
    (held,), _ = rt.requests(spans + [hold(5_000, 1_000)], WINDOW)
    assert held.held == 2_000 * US and held.klass() == "held"


def test_members_of_one_batch_join_the_same_execute_span():
    spans = (request(0, 1_000, n=2, batch=None)
             + request(1, 1_010, n=2, batch=1, queue=90))
    rows, unjoined = rt.requests(spans, WINDOW)
    assert unjoined == 0 and [r.batch for r in rows] == [1, 1]
    assert rows[0].call_span == rows[1].call_span == 498 * US
    # an execute of that ordinal that did not lie inside the wait is another
    # replica's: the row is left out and counted
    far = request(2, 50_000, batch=1)
    assert rt.requests(spans + far, WINDOW)[1] == 1


def test_a_generated_request_joins_without_a_batch():
    """A generation engine's caller waits for a slot and then sits in one:
    its wait says ``by="generate"`` and there is no batch to find."""
    spans = [s for s in request(0, 1_000)
             if not s.name.startswith("serve.batch.")]
    wait = next(s for s in spans if s.name == "serve.replica.wait")
    spans[spans.index(wait)] = dataclasses.replace(wait, attrs={
        "trace_id": wait.attrs["trace_id"], "by": "generate",
        "waited_us": 150, "slot": 3, "len": 40, "bucket": 64, "steps": 7,
        "n_new": 8})
    (row,), unjoined = rt.requests(spans, WINDOW)
    assert unjoined == 0
    assert row.queue_wait == 150 * US
    assert row.call == wait.end - wait.start - 150 * US
    assert (row.size, row.size_max, row.n, row.batch, row.cut,
            row.call_span) == (40, 64, 0, 0, "", 0)
    assert sum(getattr(row, s) for s in rt.STAGES) + row.other == row.latency


def test_a_request_without_its_spans_is_counted_and_left_out():
    whole = request(0, 1_000)
    no_wait = [s for s in request(1, 5_000)
               if s.name != "serve.replica.wait"]
    cut_by_the_edge = request(2, WINDOW[1] // US - 100)
    rows, unjoined = rt.requests(whole + no_wait + cut_by_the_edge, WINDOW)
    assert [r.order for r in rows] == [0] and unjoined == 1


def test_holds_stand_for_the_interval_before_their_span():
    a, b = rt.holds(_window())
    assert a.interval == (41_500 * US, 44_500 * US) and a.cause == "gil"
    assert b.interval == (70_450 * US, 70_950 * US) and b.holder == ""


# -- the idle table ----------------------------------------------------------


def test_idle_time_goes_to_a_hold_first_then_to_the_shortest_open_span():
    spans = [
        sp("actor.call", 0, 1_000, ACTOR, method="run"),
        sp("serve.batch.execute", 100, 500, FLUSHER),
        sp("serve.batch.call", 200, 400, FLUSHER),
        sp("serve.replica.wait", 150, 600, ACTOR),
        hold(900, 100),                      # held 800-900
    ]
    window = (0, 1_200 * US)
    idle = [(0, 1_200 * US)]
    got = rt.idle_table(idle, spans, window)
    assert got == {
        "actor.call run": (100 + 200 + 100) * US,   # 0-100, 600-800, 900-1000
        "serve.batch.execute": (100 + 100) * US,    # 100-200, 400-500
        "serve.replica.wait": 100 * US,             # 500-600
        "serve.batch.call": 200 * US,               # 200-400
        "host.hold": 100 * US,                      # 800-900
        rt.NO_SPAN: 200 * US,                       # 1000-1200
    } and sum(got.values()) == 1_200 * US
    # only what is idle counts, and only inside the window
    busy_half = rt.idle_table([(250 * US, 350 * US), (1_100 * US, 5_000 * US)],
                              spans, window)
    assert busy_half == {"serve.batch.call": 100 * US, rt.NO_SPAN: 100 * US}
    assert rt.idle_table([], spans, window) == {}
    # a long poll is open whenever nothing else is: it is left out
    polled = spans + [sp("actor.call", 0, 1_200, HANDLER,
                         method="listen_for_change")]
    assert rt.idle_table(idle, polled, window) == got


# -- the reducers ------------------------------------------------------------


def _ctx(spans, monkeypatch, ops=(), devices=True):
    monkeypatch.setattr(rt, "program_spans", lambda ctx: spans)
    trace = Reduced(WINDOW, {0: DeviceTrace(list(ops), [])} if devices
                    else {}, [])
    return reducers.Context(cell=type("C", (), {"name": "cell"})(),
                            trace=trace, counters={},
                            device_kind="TPU v5 lite")


WANT = {
    ("tail_class_pct", "class", "held"): 25.0,
    ("tail_class_pct", "class", "own_call"): 25.0,
    ("tail_class_pct", "class", "behind_call"): 25.0,
    ("tail_class_pct", "class", "other"): 25.0,
    # the four rows' latency: 9,200 + 7,590 + 5,675 + 4,685 = 27,150 us
    ("tail_stage_share_pct", "stage", "call"): 100 * 10_500 / 27_150,
    ("tail_stage_share_pct", "stage", "queue_wait"): 100 * 6_300 / 27_150,
    ("tail_stage_share_pct", "stage", "mailbox_wait"): 100 * 1_030 / 27_150,
    ("tail_stage_share_pct", "stage", "proxy_self"): 100 * 4_045 / 27_150,
    ("tail_stage_share_pct", "stage", "other"): 100 * 5_075 / 27_150,
    ("request_other_share_pct", None, None): 100 * 25 / 700,
    # 3.5 ms of holds in a window of 0.1 s
    ("hold_ms_per_min", None, None): 3.5 * 600,
    ("hold_ms_per_min", "cause", "gil"): 3.0 * 600,
    ("hold_ms_per_min", "cause", "throttled"): 0.0,
}


@pytest.mark.parametrize("case", sorted(WANT, key=str), ids=lambda c: (
    f"{c[0]}-{c[2]}"))
def test_each_reducer_reads_the_window(case, monkeypatch):
    name, key, value = case
    got = getattr(rt, name)(_ctx(_window(), monkeypatch),
                            {key: value} if key else {})
    assert got == pytest.approx(WANT[case])


def test_the_idle_reducers_need_a_device_and_read_its_gaps(monkeypatch):
    # the device is busy but for 40,000-50,000 us and 70,000-71,000 us
    ops = [Event("fusion.1", 0, 40_000 * US), Event("fusion.2", 50_000 * US,
                                                    70_000 * US),
           Event("fusion.3", 71_000 * US, 100_000 * US)]
    ctx = _ctx(_window(), monkeypatch, ops)
    # the hold of 41,500-44,500 lies in the first gap, the other in the
    # second; request 21's spans opened under the first and were held too
    assert rt.idle_held_pct(ctx, {}) == pytest.approx(100 * 3_500 / 100_000)
    assert rt.idle_by_span(ctx, {"span": "host.hold"}) == pytest.approx(
        3_500e-6)
    by_name = {name: rt.idle_by_span(ctx, {"span": name}) for name in (
        "host.hold", rt.NO_SPAN, "serve.request", "serve.route",
        "serve.await_replica", "serve.reply", "actor.call handle_request",
        "serve.replica.wait", "serve.batch.linger", "serve.batch.execute",
        "serve.batch.call")}
    assert sum(by_name.values()) == pytest.approx(11_000e-6)
    assert rt.idle_by_span(ctx, {"span": "absent"}) == 0.0
    no_device = _ctx(_window(), monkeypatch, devices=False)
    assert rt.idle_held_pct(no_device, {}) is None
    assert rt.idle_by_span(no_device, {"span": "host.hold"}) is None


OLD_PROGRAM = [s for s in request(0, 1_000) + request(1, 3_000)
               if s.name not in ("serve.replica.wait", "host.hold")]


@pytest.mark.parametrize("name,p", [
    ("tail_class_pct", {"class": "held"}),
    ("tail_stage_share_pct", {"stage": "call"}),
    ("request_other_share_pct", {}),
    ("hold_ms_per_min", {}),
    ("idle_held_pct", {}),
])
def test_a_trace_from_before_the_two_spans_reads_nothing(name, p,
                                                         monkeypatch):
    ops = [Event("fusion.1", 0, 40_000 * US)]
    assert getattr(rt, name)(_ctx(OLD_PROGRAM, monkeypatch, ops), p) is None
    assert getattr(rt, name)(_ctx((), monkeypatch, ops), p) is None
    no_trace = reducers.Context(cell=None, trace=None, counters={},
                                device_kind="cpu")
    assert getattr(rt, name)(no_trace, p) is None


def test_a_window_without_a_hold_reads_zero_once_the_program_has_them(
        monkeypatch):
    ops = [Event("fusion.1", 0, 40_000 * US)]
    ctx = _ctx(request(0, 1_000), monkeypatch, ops)
    assert rt.hold_ms_per_min(ctx, {}) == 0.0
    assert rt.idle_held_pct(ctx, {}) == 0.0
    assert rt.tail_class_pct(ctx, {"class": "held"}) == 0.0
    # a training cell: no request, and a hold is its own witness
    alone = _ctx([hold(45_000, 1_000)], monkeypatch, ops)
    assert rt.hold_ms_per_min(alone, {}) == pytest.approx(600.0)
    assert rt.tail_class_pct(alone, {"class": "held"}) is None
    assert rt.idle_by_span(alone, {"span": "host.hold"}) == pytest.approx(
        1_000e-6)
    assert rt.idle_by_span(alone, {"span": rt.NO_SPAN}) == pytest.approx(
        59_000e-6)


def test_the_percentile_is_linear_between_ranks():
    assert rt.percentile([1, 2, 3, 4, 5], 50) == 3
    assert rt.percentile(list(range(1, 241)), 95) == pytest.approx(228.05)
    assert rt.percentile([7], 95) == 7


# -- the command line on a file ----------------------------------------------


def test_the_command_line_prints_a_traces_tables(tmp_path):
    """A CPU profiler session with one request's spans opened by the
    program's own primitive, a hold and the benchmark's window round them:
    ``analyse`` reads the file back and ``render`` prints the tables."""
    import time

    from ray_tpu import observability
    span = observability.span
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(0.002)
            with span("serve.request", route="/m", accept_wait_us=30):
                with span("serve.route"):
                    pass
                with span("serve.await_replica"):
                    with span("actor.call", method="handle_request",
                              mailbox_wait_us=7):
                        with span("serve.replica.wait", by="batch",
                                  queue_wait_us=100, call_us=900, batch=3,
                                  n=1, padded_n=2, size=5, size_max=5,
                                  retried=0, shed=0):
                            with span("serve.batch.linger", cut="waited"):
                                pass
                            with span("serve.batch.execute", batch=3, n=1):
                                with span("serve.batch.call"):
                                    time.sleep(0.001)
                            with span("host.hold", held_us=400, cause="gil",
                                      holder="a.py:f;b.py:g", cpu_us=390,
                                      run_delay_us=3, throttled_us=0,
                                      gc_full=0, majflt=0, nivcsw=1,
                                      threads=4):
                                pass
                with span("serve.reply", bytes=3):
                    pass
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = rt.analyse(path)
    assert found["requests"] == 1 and found["unjoined"] == 0
    row, = found["rows"]
    assert row["tail"] and row["cut"] == "waited" and row["batch"] == 3
    assert (row["queue_wait"], row["call"], row["mailbox_wait"],
            row["accept_wait"]) == (100_000, 900_000, 7_000, 30_000)
    assert (sum(row[s] for s in rt.STAGES) + row["other"] == row["latency"])
    assert 0 < row["held"] <= 400_000
    h, = found["holds"]
    assert (h["held"], h["cause"], h["holder"], h["inside"]) == (
        400_000, "gil", "a.py:f;b.py:g", True)
    assert found["idle_s"] == pytest.approx(found["window_s"])  # no device
    assert set(found["idle_by_span"]) >= {"host.hold", "serve.batch.call",
                                          rt.NO_SPAN}
    assert sum(found["idle_by_span"].values()) == pytest.approx(
        found["idle_s"])
    text = rt.render(found)
    for piece in ("the tail: the 1 slowest of 1", "the tail's classes:",
                  "the tail's latency by stage:", "holds inside the window: 1",
                  "gil: 1,", "holder a.py:f;b.py:g", "by the innermost open "
                  "span", "serve.batch.call", "median other / latency"):
        assert piece in text, text
