"""The request batcher's cut: which queued requests share a call, and when
the batcher stops waiting for more.

Which: the rule is a pure function (``serve/batching.py`` ``cut_by_size``)
and is tested as one, with no clock; then through the batcher's two entry
points, a real ``Replica`` and a ``@serve.batch`` function, whose first call
is held open while the queue is filled in a known order, so that what each
later cut takes, leaves and reports is counted and not timed.

When: the four reasons to cut (``cut`` on ``serve.batch.linger``: ``full``,
``waited``, ``passed``, ``not_due``).  The last compares two estimates the
batcher keeps of its own traffic; the tests put them into the batcher's own
fields, so that which reason fires does not hang on how fast the test's
threads run.
"""

import contextlib
import threading
from unittest import mock

import pytest

from ray_tpu import observability, serve
from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler
from ray_tpu.observability import metric_names
from ray_tpu.serve._private.replica import Replica
from ray_tpu.serve.batching import _Batcher, cut_by_size, item_size

B248 = (2, 4, 8)
MIXED = [100, 2000, 120, 1900, 90, 2040, 300, 310]


def drain(sizes, cap, buckets):
    """Cut after cut until the queue is empty: the batches, as sizes."""
    queue, batches = list(sizes), []
    while queue:
        taken = cut_by_size(queue, cap, buckets)
        batches.append([queue[i] for i in taken])
        queue = [s for i, s in enumerate(queue) if i not in set(taken)]
    return batches


def sizes_of(items):
    return [item_size(x) for x in items]


# (what is queued, in arrival order; cap; count buckets; the batches that
# cutting until the queue is empty gives)
CASES = {
    "equal sizes under the cap are arrival order":
        ([7, 7, 7], 8, None, [[7, 7, 7]]),
    "equal sizes over the cap are queue[:cap], then the rest":
        ([5] * 11, 8, None, [[5] * 8, [5] * 3]),
    "equal sizes are not trimmed to a count bucket":
        ([64] * 5, 8, B248, [[64] * 5]),
    "scalars have no size and go in arrival order":
        (sizes_of([3, 1.5, None, True, object()]), 4, B248,
         [[1] * 4, [1]]),
    "mappings count as size 1 whatever they hold":
        (sizes_of([{"a": 1}, {"a": 1, "b": [0] * 900}, {}]), 8, B248,
         [[1, 1, 1]]),
    "sequences are sized by len: lists, tuples, strings, bytes":
        (sizes_of([[0] * 100, (0,) * 120, "x" * 90, b"y" * 2000]), 8, B248,
         [[100, 120, 90], [2000]]),
    "sizes of one power-of-two class are alike":
        ([65, 128, 100, 97], 8, B248, [[65, 128, 100, 97]]),
    "a mixed queue is cut into its length classes":
        (MIXED, 8, B248, [[100, 120, 90], [2000, 1900, 2040], [300, 310]]),
    "the same with no count buckets":
        (MIXED, 8, None, [[100, 120, 90], [2000, 1900, 2040], [300, 310]]),
    "a 100 goes with the nearer neighbour, not with the 2,000":
        ([100, 2000, 300], 8, B248, [[100, 300], [2000]]),
    "40 and 200 share a call":
        ([40, 200], 8, B248, [[40, 200]]),
    "a lone long request takes a short one in its padded row":
        ([2000, 100], 8, B248, [[2000, 100]]),
    "with no padded row to fill the long one goes alone":
        ([2000, 100], 8, None, [[2000], [100]]),
    "the oldest is in the cut though the others outnumber it":
        ([2000] + [50] * 7, 8, B248, [[2000], [50] * 7]),
    "short requests hold a long one for one cut at most":
        ([50, 2000] + [50] * 9, 8, B248,
         [[50] * 8, [2000], [50, 50]]),
    "the cap holds inside a class":
        ([100, 2000, 110, 120, 125], 2, B248,
         [[100, 110], [2000, 120], [125]]),
    "a class over the cap gives its oldest":
        ([300, 310, 2000, 320, 330, 340], 4, (2, 4),
         [[300, 310, 320, 330], [2000, 340]]),
}


@pytest.mark.parametrize("case", CASES)
def test_cut_by_size(case):
    sizes, cap, buckets, batches = CASES[case]
    taken = cut_by_size(sizes, cap, buckets)
    assert taken[0] == 0, "the oldest request is in every cut"
    assert taken == sorted(set(taken)), "arrival order inside the batch"
    assert 1 <= len(taken) <= cap
    assert drain(sizes, cap, buckets) == batches
    if len(set(sizes)) == 1:
        assert taken == list(range(min(cap, len(sizes))))


# -- through the two entry points --------------------------------------------

WAIT_S = 10.0       # a join or a poll that takes this long has failed


def estimates(batcher, gap_s, call_ms):
    """Put the two estimates the fourth reason compares into the fields
    the batcher keeps them in (``None`` / 0.0: none yet, as in a new
    batcher).  The first admission after this has no admission before it,
    so it moves neither."""
    with batcher._lock:
        batcher._gap_ewma_s = gap_s
    with batcher._estimate._lock:
        batcher._estimate._ms = call_ms


class ThroughReplica:
    """``fn`` as a batched function deployment's replica.  A function, so
    that the replica sets no init gauge for other files' tests to find."""

    def __init__(self, fn, cap, bound_s, buckets, gap_s, call_ms):
        self.replica = Replica("held", "held#1", fn, (), {}, batch_config={
            "max_batch_size": cap, "batch_wait_timeout_s": bound_s,
            "pad_batch_to": buckets, "target_latency_ms": 1e9})
        self.batcher = self.replica._batcher
        estimates(self.batcher, gap_s, call_ms)

    def submit(self, item):
        return self.replica.handle_request("__call__", (item,), {})

    def set_bound(self, bound_s):
        self.replica.set_batch_config({"batch_wait_timeout_s": bound_s})

    def counts(self):
        return self.replica.get_metrics()

    def close(self):
        self.replica.prepare_for_shutdown(timeout_s=WAIT_S)


class ThroughDecorator:
    """``fn`` under ``@serve.batch``."""

    def __init__(self, fn, cap, bound_s, buckets, gap_s, call_ms):
        self.submit = serve.batch(
            max_batch_size=cap, batch_wait_timeout_s=bound_s,
            pad_batch_to=buckets)(fn)
        # the decorator builds the function's batcher at its first call:
        # one call that admits nothing and hands the batcher back
        with mock.patch.object(_Batcher, "submit", lambda batcher, _: batcher):
            self.batcher = self.submit(None)
        estimates(self.batcher, gap_s, call_ms)

    def set_bound(self, bound_s):
        self.batcher.retune({"batch_wait_timeout_s": bound_s})

    def counts(self):
        return self.batcher.counts()

    def close(self):
        self.batcher.shutdown()


ENTRIES = {"replica": ThroughReplica, "decorator": ThroughDecorator}


def held_deployment():
    """A batched function whose first call stays open until released:
    every reply is ``(len(item), len(items))``."""
    calls, started, release = [], threading.Event(), threading.Event()

    def held(items):
        calls.append([item_size(x) for x in items])
        if len(calls) == 1:
            started.set()
            assert release.wait(WAIT_S)
        return [(item_size(x), len(items)) for x in items]

    held.calls, held.started, held.release = calls, started, release
    return held


def _spans(name):
    return [e["args"] for e in get_profiler().chrome_trace()
            if e["name"] == name]


@contextlib.contextmanager
def spans_on():
    """The ring takes the batcher's spans while the block runs."""
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


def _seen(entry):
    """The batcher's counts and its spans so far."""
    return {"metrics": entry.counts(),
            "linger": _spans("serve.batch.linger"),
            "execute": _spans("serve.batch.execute")}


@spans_on()
def run_held(through, requests, cap, buckets, gap_s=None, call_ms=0.0,
             bound_s=600.0, aged_s=0.0, release_bound_s=None):
    """Fill the queue with ``requests`` in order behind a held first call,
    with a linger of ``bound_s`` (by default one no request could sit out
    twice), then let the flusher cut: the replies in the order of
    ``requests``, the calls, the batcher's counts and its spans (the ring
    is on).  ``through`` is one of ``ENTRIES``; ``gap_s`` and ``call_ms`` are
    the batcher's estimates before its first request; ``aged_s`` puts the
    queued requests' admission that far back, as a call the host held
    would; ``release_bound_s`` is a linger retuned just before the
    release."""
    held = held_deployment()
    entry = ENTRIES[through](held, cap, 0.0, buckets, gap_s, call_ms)
    batcher = entry.batcher
    replies = {}

    def call(key, item):
        replies[key] = entry.submit(item)

    threads = [threading.Thread(target=call, args=("held", "g"))]
    try:
        threads[0].start()
        assert held.started.wait(WAIT_S)
        # from here on a fresh request would linger for minutes
        entry.set_bound(bound_s)
        for k, item in enumerate(requests):
            threads.append(threading.Thread(target=call, args=(k, item)))
            threads[-1].start()
            poll = threading.Event()
            for _ in range(int(WAIT_S / 0.002)):
                if batcher.depth() == k + 1:
                    break
                poll.wait(0.002)
            assert batcher.depth() == k + 1
        if aged_s:
            with batcher._lock:
                for slot in batcher._queue:
                    slot.t_enqueue -= aged_s
        if release_bound_s is not None:
            entry.set_bound(release_bound_s)
        held.release.set()
        for t in threads:
            t.join(WAIT_S)
        assert not any(t.is_alive() for t in threads)
        seen = _seen(entry)
    finally:
        held.release.set()
        entry.close()
    return {"replies": [replies[k] for k in range(len(requests))],
            "calls": held.calls, **seen}


@pytest.fixture(scope="module", params=ENTRIES)
def reordered(request):
    """100, 2,000, 120 and 1,900 tokens queued in that order, cap 4."""
    return run_held(request.param,
                    [[0] * 100, [0] * 2000, [0] * 120, [0] * 1900],
                    cap=4, buckets=(2, 4))


def test_each_caller_gets_its_own_result_when_the_cut_reorders(reordered):
    assert reordered["calls"] == [[1, 1], [100, 120], [2000, 1900]]
    assert reordered["replies"] == [(100, 2), (2000, 2), (120, 2), (1900, 2)]


def test_a_passed_over_request_is_cut_without_a_second_linger(reordered):
    # the queue was full (4 = cap) at the first cut, which left two; the
    # next cut took them at depth 2 < cap with 600 s of linger ahead: three
    # calls in all, and the test is here
    assert len(reordered["calls"]) == 3
    assert [s["depth"] for s in reordered["linger"]] == [1, 4, 2]
    # what a cut passed over is its depth less what it took
    assert [s["depth"] - e["n"] for s, e in zip(
        reordered["linger"], reordered["execute"])] == [0, 2, 0]


def test_the_fill_is_on_the_spans_and_in_get_metrics(reordered):
    execute = reordered["execute"]
    assert [(s["n"], s["padded_n"], s["size_sum"], s["size_max"])
            for s in execute] == [(1, 2, 1, 1), (2, 2, 220, 120),
                                  (2, 2, 3900, 2000)]
    assert all(s["size_sum"] <= s["padded_n"] * s["size_max"]
               for s in execute)
    metrics = reordered["metrics"]
    assert metrics[metric_names.REPLICA_BATCH_SIZE_SUM] == sum(
        s["size_sum"] for s in execute) == 4121
    assert metrics[metric_names.REPLICA_BATCH_PADDED_SUM] == sum(
        s["padded_n"] * s["size_max"] for s in execute) == 4242


@pytest.mark.parametrize("entry", ENTRIES)
def test_scalars_and_dicts_are_cut_in_arrival_order(entry):
    got = run_held(entry, [1, {"x": 2}, 3, {"y": [0] * 500}, 5, 6], cap=4,
                   buckets=(2, 4))
    assert got["calls"] == [[1, 1], [1, 1, 1, 1], [1, 1]]
    assert got["replies"] == [(1, 4)] * 4 + [(1, 2)] * 2
    assert [s["depth"] - e["n"] for s, e in zip(
        got["linger"], got["execute"])] == [0, 2, 0]


# -- when the batcher stops waiting ------------------------------------------

SPARSE = {"gap_s": 1e3, "call_ms": 1.0}     # a neighbour is not due
CLUMP = {"gap_s": 1e-9, "call_ms": 1e6}     # the next is due any moment
SHORT = [0] * 100


@spans_on()
def run_alone(through, requests, bound_s, cap=4, buckets=(2, 4), gap_s=None,
              call_ms=0.0):
    """``requests`` one after the other, each answered before the next is
    sent, through a batcher with the given estimates and a linger of
    ``bound_s``: what ``run_held`` returns."""
    calls = []

    def echo(items):
        calls.append([item_size(x) for x in items])
        return [(item_size(x), len(items)) for x in items]

    entry = ENTRIES[through](echo, cap, bound_s, buckets, gap_s, call_ms)
    try:
        replies = [entry.submit(item) for item in requests]
        seen = _seen(entry)
    finally:
        entry.close()
    return {"replies": replies, "calls": calls, **seen}


# (the driver, the requests, its other arguments) -> (the reason each cut
# fired, the depth it found, the calls as the deployment saw them, rows of
# padding included; the least the oldest request of the last cut had
# waited, in seconds)
CUTS = {
    "sparse admissions are each cut not_due though the bound is 600 s":
        ((run_alone, [SHORT] * 4, dict(bound_s=600.0, **SPARSE)),
         ["not_due"] * 4, [1] * 4, [[100] * 2] * 4, None),
    "what is queued when no neighbour is due goes in one call":
        ((run_held, [SHORT] * 2, dict(cap=4, buckets=(2, 4), **SPARSE)),
         ["waited", "not_due"], [1, 2], [[1, 1], [100] * 2], None),
    "a clump shares one call inside the bound: full":
        ((run_held, [SHORT] * 3, dict(cap=3, buckets=(2, 4), **CLUMP)),
         ["waited", "full"], [1, 3], [[1, 1], [100] * 4], None),
    "a clump short of the cap is cut when the bound is out: waited":
        ((run_held, [SHORT] * 2, dict(cap=4, buckets=(2, 4),
                                      release_bound_s=0.0, **CLUMP)),
         ["waited", "waited"], [1, 2], [[1, 1], [100] * 2], None),
    "a batcher with no estimate yet holds for the configured bound":
        ((run_alone, [SHORT], dict(bound_s=0.15)),
         ["waited"], [1], [[100] * 2], 0.15),
    "with a gap estimate and no call yet the bound holds":
        ((run_alone, [SHORT], dict(bound_s=0.15, gap_s=1e3)),
         ["waited"], [1], [[100] * 2], 0.15),
    "with a call estimate and no second admission yet the bound holds":
        ((run_alone, [SHORT], dict(bound_s=0.15, call_ms=1.0)),
         ["waited"], [1], [[100] * 2], 0.15),
    "a gap just over half the call is not worth the wait":
        ((run_alone, [SHORT], dict(bound_s=600.0, gap_s=0.051,
                                   call_ms=100.0)),
         ["not_due"], [1], [[100] * 2], None),
    "a gap just under half the call is, and the bound is never exceeded":
        ((run_alone, [SHORT], dict(bound_s=0.15, gap_s=0.049,
                                   call_ms=100.0)),
         ["waited"], [1], [[100] * 2], 0.15),
    "a request passed over is cut passed, without a second linger":
        ((run_held, [[0] * 100, [0] * 2000, [0] * 120, [0] * 1900],
          dict(cap=4, buckets=(2, 4), **CLUMP)),
         ["waited", "full", "passed"], [1, 4, 2],
         [[1, 1], [100, 120], [2000, 1900]], None),
    "after a held first call the whole queue is cut without waiting":
        ((run_held, [SHORT] * 3, dict(cap=8, buckets=B248, bound_s=0.05,
                                      aged_s=1.0)),
         ["waited", "waited"], [1, 3], [[1, 1], [100] * 4], 1.0),
}


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("case", CUTS)
def test_why_the_batcher_cut(case, entry):
    (run, requests, more), reasons, depths, calls, waited_s = CUTS[case]
    got = run(entry, requests, **more)
    linger = got["linger"]
    assert [s["cut"] for s in linger] == reasons
    assert [s["depth"] for s in linger] == depths
    assert got["calls"] == calls
    # every request was answered, and nothing expired in the queue
    assert [r[0] for r in got["replies"]] == [len(x) for x in requests]
    # the batcher's two counts (a replica's get_metrics() carries them) are
    # the spans' reasons, counted
    assert got["metrics"][metric_names.REPLICA_BATCH_CUTS] == len(linger)
    assert got["metrics"][metric_names.REPLICA_BATCH_CUTS_NOT_DUE] == \
        reasons.count("not_due")
    for s, reason in zip(linger, reasons):
        if reason == "not_due":
            # the estimates on the span are the two the rule compared
            assert s["gap_est_us"] > 0.5 * s["call_est_us"] > 0
    if waited_s is not None:
        # held for the bound (or for the stall) and let go at the first
        # look after it: WAIT_S is no limit of the batcher's, it is room
        # for a machine busy with other tests
        assert waited_s * 1e6 <= linger[-1]["oldest_wait_us"] \
            < (waited_s + WAIT_S) * 1e6


# -- what a @serve.batch user sees of the one machine -------------------------


def test_a_decorated_function_cuts_a_burst_by_size():
    # a long and three short sequences, four to a call, admitted in any
    # order: the cut is by size, so the short ones share a call and the
    # long one pays for no row but its own
    calls = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=WAIT_S)
    def sizes(items):
        calls.append(sorted(len(x) for x in items))
        return [len(x) for x in items]

    burst = [[0] * 2000, SHORT, SHORT, SHORT]
    replies = [None] * len(burst)
    threads = [threading.Thread(
        target=lambda k=k: replies.__setitem__(k, sizes(burst[k])))
        for k in range(len(burst))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
    assert replies == [2000, 100, 100, 100]
    assert sorted(calls) == [[100, 100, 100], [2000]]


@spans_on()
def test_a_decorated_functions_lone_request_is_let_go_when_none_is_due():
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.25)
    def echo(items):
        threading.Event().wait(0.001)     # a call that takes some time
        return items

    # the first has no estimate to go by and is held for the bound; the
    # second comes a bound and more after it, a hundred calls' time, so
    # no neighbour is due and it is not held ("waited" is tried first: a
    # request cut not_due was let go inside the bound)
    assert [echo(1), echo(2)] == [1, 2]
    linger = _spans("serve.batch.linger")
    assert [s["cut"] for s in linger] == ["waited", "not_due"]
    assert linger[0]["oldest_wait_us"] >= 250_000
    assert linger[1]["gap_est_us"] >= 250_000 > linger[1]["call_est_us"] > 0
