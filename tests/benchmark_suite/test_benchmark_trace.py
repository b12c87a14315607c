"""The trace reduction on synthetic planes (interval union, idle gaps,
nesting, exposed collectives, custom-call matching, per-execution numbers)
and on the trace recorded on the chip during PR 25."""

import json
import os

import pytest

from benchmark import reducers, trace_reduce as tr
from benchmark.trace_reduce import DeviceTrace, Event, Reduced

FIXTURES = os.path.join(os.path.dirname(tr.__file__), "fixtures")
US = 1000      # the synthetic planes count in microseconds


def ev(name, start_us, end_us, category=""):
    return Event(name, start_us * US, end_us * US, category)


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 10), (7, 7)]) == [
        (0, 4), (5, 10)]
    assert tr.union_ns([(0, 3), (2, 4), (5, 10)]) == 9
    assert tr.union_ns([]) == 0


def test_gaps_are_what_the_window_has_and_no_interval_covers():
    assert tr.gaps([(2, 4), (6, 8)], (0, 10)) == [(0, 2), (4, 6), (8, 10)]
    assert tr.gaps([(0, 10)], (2, 8)) == []
    assert tr.gaps([], (2, 8)) == [(2, 8)]
    assert tr.clip([(0, 5), (7, 12), (20, 30)], (3, 10)) == [(3, 5), (7, 10)]


def test_overlap_of_two_unions():
    assert tr.overlap_ns([(0, 10)], [(2, 4), (8, 12)]) == 4
    assert tr.overlap_ns([(0, 2), (4, 6)], [(1, 5)]) == 2
    assert tr.overlap_ns([(0, 2)], [(2, 4)]) == 0


def test_leaves_drop_the_operation_that_holds_others():
    events = [ev("while.1", 0, 100), ev("fusion.1", 0, 40),
              ev("custom-call.2", 40, 90), ev("copy.3", 110, 120)]
    assert [e.name for e in tr.leaves(events)] == [
        "fusion.1", "custom-call.2", "copy.3"]
    nested = [ev("while.1", 0, 100), ev("conditional.2", 10, 50),
              ev("fusion.3", 10, 30)]
    assert [e.name for e in tr.leaves(nested)] == ["fusion.3"]


def test_exposed_collective_time_is_what_no_other_operation_covers():
    events = [ev("all-gather-start.1", 0, 2), ev("fusion.1", 2, 50),
              ev("all-gather-done.1", 50, 80),          # waits 30 exposed
              ev("fusion.2", 80, 100),
              ev("all-reduce.3", 100, 130),
              ev("fusion.4", 120, 140)]                 # hides 10 of it
    assert tr.exposed_ns(events) == (2 + 30 + 20) * US
    assert tr.exposed_ns([ev("fusion.1", 0, 10)]) == 0


def test_custom_calls_match_by_name_or_by_category():
    events = [ev("while.1", 0, 100), ev("custom-call.7", 0, 30),
              ev("flash_fwd", 30, 60, "custom-call"),
              ev("fusion.9", 60, 100, "convolution fusion")]
    got = tr.matching(events, ["custom-call", "pallas"])
    assert [e.name for e in got] == ["custom-call.7", "flash_fwd"]


def _step_trace():
    """Two devices, three executions of jit_step and one of another
    program, host spans round them, a window that leaves the first
    execution out."""
    ops, modules = [], []
    for i, start in enumerate((0, 1000, 2100)):
        modules.append(ev(f"jit_step({i})", start, start + 900))
        ops += [ev("while.1", start, start + 800),
                ev("fusion.1", start, start + 500),
                ev("custom-call.2", start + 500, start + 700),
                ev("all-reduce.3", start + 700, start + 800),
                ev("fusion.4", start + 850, start + 900)]
    modules.append(ev("jit_other", 3100, 3200))
    ops.append(ev("fusion.9", 3100, 3200))
    host = [ev("step_fn", 900, 1000),
            ev("loss_readback", 1900, 2100), ev("session.report", 3000, 3100)]
    dev = DeviceTrace(ops, modules)
    half = DeviceTrace([ev("fusion.1", 1000, 1500)], [])
    return Reduced((950 * US, 3300 * US), {0: dev, 1: half}, host)


def test_executions_inside_the_window_busy_and_gaps():
    r = _step_trace()
    runs = r.first.executions("jit_step", r.window)
    assert [m.name for m in runs] == ["jit_step(1)", "jit_step(2)"]
    assert r.first.busy_inside(runs[0]) == 850 * US
    assert r.window_s == pytest.approx(2350e-6)
    # device 0: two steps of 850 and the other program's 100; device 1: 500
    assert r.busy_s == pytest.approx((1800 + 500) / 2 * 1e-6)


def _ctx(r, **counters):
    return reducers.Context(cell=None, trace=r, counters=counters,
                            device_kind="TPU v5 lite")


def test_trace_readers_on_the_synthetic_steps():
    r = _step_trace()
    p = {"program": "jit_step"}
    assert reducers.execution_busy_ms(_ctx(r), p) == pytest.approx(0.85)
    assert reducers.execution_gap_ms(_ctx(r), p) == pytest.approx(0.2)
    share = reducers.op_share_pct(_ctx(r), {"ops": ["custom-call"]})
    assert share == pytest.approx(100 * 400 / 1800)
    assert reducers.collective_exposed_ms(
        _ctx(r, devices=4), p) == pytest.approx(0.1)
    assert reducers.collective_exposed_ms(_ctx(r, devices=1), p) is None


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = Reduced((0, 10), {}, [])
    p = {"program": "jit_step", "ops": ["custom-call"]}
    for name in ("execution_busy_ms", "execution_gap_ms", "op_share_pct",
                 "collective_exposed_ms"):
        assert reducers.resolve(name)(_ctx(empty, devices=4), p) is None
    assert reducers.counter(_ctx(empty), {"key": "absent"}) is None
    assert reducers.counter_ratio(_ctx(empty, a=1.0, b=0),
                                  {"num": "a", "den": "b"}) is None
    assert reducers.counter_ratio(_ctx(empty, a=3.0, b=2),
                                  {"num": "a", "den": "b",
                                   "scale": 1000.0}) == 1500.0
    with pytest.raises(KeyError):
        reducers.resolve("no_such_reader")


def test_breakdown_ranks_leaf_operations_and_names_idle_time_by_span():
    b = _step_trace().breakdown(top=3)
    assert [name for name, _ in b["device_ops"]][0] == "fusion.1"
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(1000e-6)
    assert "while.1" not in dict(b["device_ops"])
    idle = dict(b["idle_gaps"])
    assert idle["loss_readback"] == pytest.approx(200e-6)   # 1900..2100
    assert idle["session.report"] == pytest.approx(100e-6)
    assert "(no span)" in idle


def test_the_trace_recorded_on_the_chip_reduces_to_its_recorded_values():
    with open(os.path.join(FIXTURES, "train_steps.expected.json")) as f:
        want = json.load(f)
    r = tr.load(os.path.join(FIXTURES, "train_steps.xplane.pb"),
                "bench.", "window")
    assert sorted(r.devices) == want["devices"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    runs = r.first.executions(want["program"], r.window)
    assert len(runs) == want["executions"]
    ctx = _ctx(r, **want["counters"])
    ctx.cell = type("C", (), {"deploy": {"model": {"remat": True}}})()
    for name, (reader, params, value) in want["readers"].items():
        got = reducers.resolve(reader)(ctx, params)
        assert got == pytest.approx(value, rel=1e-9), name
    breakdown = r.breakdown()
    assert breakdown["device_ops"][0][0] == want["top_op"]
    # the benchmark's own spans, found by their prefix on the host's plane
    assert sorted(n for n, _ in breakdown["idle_gaps"]) == want["idle_spans"]
