"""``host.hold``: the stack sampler's tick measures its own lateness
(``ray_tpu/observability/sampler.py``).

The planted hold shares its machine with the other workers' tests, so it
asserts on the planted hold alone: other holds may well be recorded beside
it, and its own may last longer than it was planted for.
"""

import os
import sys
import threading
import time

import pytest

from ray_tpu import observability
from ray_tpu._private.profiling import get_profiler
from ray_tpu.observability import metric_names, sampler
from ray_tpu.observability.sampler import classify_hold


@pytest.fixture(autouse=True)
def _sampler_and_ring_restored():
    sampler.stop()
    yield
    sampler.stop()
    observability.disable()
    get_profiler().clear()


# held_us, cpu_us, run_delay_us, throttled_us, gc_full -> cause
CASES = {
    "throttled": ((100_000, 0, 0, 60_000, 0), "throttled"),
    "gc": ((100_000, 90_000, 0, 0, 1), "gc"),
    "gil": ((100_000, 90_000, 0, 0, 0), "gil"),
    "runqueue": ((100_000, 10_000, 70_000, 0, 0), "runqueue"),
    "off-cpu": ((100_000, 1_000, 2_000, 0, 0), "off_cpu"),
    # the order, where two qualify
    "throttled-before-gc": ((100_000, 90_000, 0, 50_000, 3), "throttled"),
    "throttled-before-runqueue": ((100_000, 0, 99_000, 50_000, 0),
                                  "throttled"),
    "gc-before-runqueue": ((100_000, 50_000, 50_000, 0, 1), "gc"),
    "gil-before-runqueue": ((100_000, 50_000, 50_000, 0, 0), "gil"),
    # a collection with the process off the cores is not the cause
    "gc-needs-the-core": ((100_000, 10_000, 0, 0, 2), "off_cpu"),
    # the half
    "just-half": ((100_000, 0, 50_000, 0, 0), "runqueue"),
    "under-half": ((100_000, 49_999, 49_999, 49_999, 1), "off_cpu"),
    # a platform without a source reads -1: it covers nothing
    "every-source-missing": ((100_000, -1, -1, -1, 0), "off_cpu"),
    "no-cgroup": ((100_000, 80_000, -1, -1, 0), "gil"),
    "no-schedstat": ((100_000, 0, -1, 0, 0), "off_cpu"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_hold_is_put_down_to_the_first_cause_that_covers_half(case):
    numbers, cause = CASES[case]
    assert classify_hold(*numbers) == cause
    assert cause in sampler.CAUSES


def _counted(counter):
    """Over every cause: on a machine whose other workers take the cores
    a planted hold may be put down to another cause than ``gil``."""
    assert all(dict(tags)["cause"] in sampler.CAUSES
               for _, tags, _ in counter.samples())
    return sum(v for _, _, v in counter.samples())


def _spin_for_the_planted_hold(seconds, released):
    n, until = 0, time.monotonic() + seconds
    while time.monotonic() < until:
        n += 1
    # stays on this thread's stack until the test has read the hold: on a
    # busy machine the sampler's thread may be given the interpreter only
    # once the loop is over
    released.wait(timeout=30)
    return n


PLANTED_S = 0.3


def test_a_planted_hold_is_recorded_with_its_cause_and_its_holder():
    """A pure-Python loop on a named thread under a switch interval of
    0.3 s keeps the interpreter for 0.3 s at a time: the sampler's thread
    wakes, asks for the interpreter and is given it a switch interval later
    (or, where the process's other threads are given it first, when the
    loop ends)."""
    observability.enable()
    counters = sampler._HoldCounters.get()
    holds_before = _counted(counters.holds)
    seconds_before = _counted(counters.seconds)
    hz = 50.0
    sampler.start(hz=hz)
    time.sleep(0.1)
    released = threading.Event()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(PLANTED_S)
    try:
        t = threading.Thread(target=_spin_for_the_planted_hold,
                             args=(3 * PLANTED_S, released),
                             name="planted-hold")
        t.start()
        time.sleep(3 * PLANTED_S)
    finally:
        sys.setswitchinterval(interval)
    time.sleep(0.3)     # the tick that ends the last hold
    sampler.stop()
    released.set()
    t.join(timeout=30)
    assert not t.is_alive()
    ring = [e["args"] for e in get_profiler().chrome_trace()
            if e["name"] == "host.hold" and e["cat"] == "host"]
    planted = [a for a in ring
               if "_spin_for_the_planted_hold" in a["holder"]]
    assert planted, ring
    hold = max(planted, key=lambda a: a["held_us"])
    # the loop keeps the interpreter and spends the CPU, so the cause is
    # ``gil`` wherever the loop's thread had a core for half the hold; where
    # the machine's other workers took the cores from it, the cause is
    # whatever the same numbers say
    assert hold["cause"] == classify_hold(
        hold["held_us"], hold["cpu_us"], hold["run_delay_us"],
        hold["throttled_us"], hold["gc_full"])
    if hold["cpu_us"] >= hold["held_us"] / 2:
        assert hold["cause"] == "gil"
    # the holder is folded as the sampler folds a stack: its leaf last, the
    # loop itself or the wait it ends in, and eight frames at most
    frames = hold["holder"].split(";")
    assert "test_host_hold.py:_spin_for_the_planted_hold" in frames[-3:]
    assert len(frames) <= 8
    # within a tick of the truth below; above, the whole loop and whatever
    # the machine's other workers add before the sampler's thread runs
    tick_us = 1e6 / hz
    assert PLANTED_S * 1e6 - tick_us <= hold["held_us"] < 30e6
    assert hold["cpu_us"] > 0 and hold["gc_full"] == 0
    assert hold["threads"] >= 2 and hold["nivcsw"] >= 0
    assert "host.hold" in metric_names.SPANS
    # both counters rose by the planted holds at least: one each, and their
    # seconds
    rose = _counted(counters.holds) - holds_before
    assert rose >= len(planted) >= 1
    assert (_counted(counters.seconds) - seconds_before
            >= sum(a["held_us"] for a in planted) / 1e6 - 1e-3)
    assert {counters.holds.name, counters.seconds.name} == {
        metric_names.HOST_HOLDS, metric_names.HOST_HOLD_SECONDS}


def test_a_tick_that_is_on_time_records_nothing(monkeypatch):
    observability.enable()
    recorded = []
    monkeypatch.setattr(sampler.StackSampler, "_record_hold",
                        lambda self, *a: recorded.append(a))
    # no tick of this sampler can be HOLD_S late unless the machine holds
    # the test itself; count what a late tick would have recorded instead
    monkeypatch.setattr(sampler, "HOLD_S", 3600.0)
    s = sampler.start(hz=200.0)
    time.sleep(0.2)
    sampler.stop()
    assert s.snapshot()["ticks"] >= 5
    assert not recorded
    assert not [e for e in get_profiler().chrome_trace()
                if e["name"] == "host.hold"]


def test_a_sampler_that_is_stopped_closes_its_file_descriptors():
    s = sampler.start(hz=100.0)
    deadline = time.monotonic() + 5
    while s._clocks is None and time.monotonic() < deadline:
        time.sleep(0.005)
    clocks = s._clocks
    fds = clocks.fds
    # this platform has both sources; one that lacks them opens nothing
    if os.path.exists("/proc/thread-self/schedstat"):
        assert fds
    for fd in fds:
        assert os.readlink(f"/proc/self/fd/{fd}").endswith(
            ("schedstat", "cpu.stat"))
    sampler.stop()
    assert clocks.fds == []
    for fd in fds:
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue        # closed, and no one has taken the number since
        assert not target.endswith(("schedstat", "cpu.stat"))


def test_the_clocks_read_minus_one_where_the_platform_has_no_source(
        monkeypatch):
    monkeypatch.setattr(sampler, "_cpu_stat_paths", lambda: ["/nonexistent"])
    monkeypatch.setattr(sampler._HostClocks, "_open",
                        staticmethod(lambda paths, needs: -1))
    clocks = sampler._HostClocks()
    try:
        me = threading.get_ident()
        reading = clocks.read({me})
        assert reading.run_delay_ns == -1 and reading.throttled_us == -1
        assert reading.cpu_s > 0 and reading.thread_cpu_s[me] > 0
        assert sampler._since(reading.run_delay_ns, 5) == -1
    finally:
        clocks.close()


def test_a_threads_cpu_clock_is_the_one_pthread_names():
    me = threading.current_thread()
    assert sampler._thread_cpu_clock(me.native_id) == (
        time.pthread_getcpuclockid(me.ident))
    # the clock of a thread that is gone fails and touches nothing
    with pytest.raises(OSError):
        time.clock_gettime(sampler._thread_cpu_clock(2 ** 22 - 3))
