"""A window the host froze in is measured again, and the program's own
stalls still count: the load generator's watcher reports a hold when its
process is stopped and none worth the name when it is not;
``serve_job.run`` keeps the first window that did not freeze (its numbers
and its trace alone, the first window's start for ``setup_s``), reports the
third as it stands where all three froze, and leaves alone a window in which
only the deployment stalled."""

import glob
import http.server
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

import benchmark_tiny
from benchmark import harness, loadgen, serve_job, trace_reduce, traffic
from test_benchmark_jobs import root, runtime  # noqa: F401 - fixtures

SEED = 2**31 + 36
CELL = "tiny-serve-open"
SECONDS = 2.0


def _run(root, trace=False, cell=CELL, **kwargs):
    return harness.run_cell(cell, SEED, SECONDS, trace, root=root,
                            require_tpu=False, **kwargs)


# -- the watcher ---------------------------------------------------------------


class _Answers(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        body = b'{"token": 5, "logit": 1.5}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


@pytest.fixture
def answers():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Answers)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_address[1]}/score"
    server.shutdown()
    server.server_close()
    thread.join(timeout=10)
    assert not thread.is_alive()


@pytest.mark.parametrize("stopped_s", [1.5, 0.0])
def test_the_generator_reports_a_hold_when_its_process_is_stopped(
        answers, stopped_s):
    """The generator as ``offer_load`` starts it: a child of its own. It is
    stopped 0.5 s into its window and continued 1.5 s later, as a frozen
    host would hold it; or left alone."""
    t0 = time.monotonic() + 1.5
    job = {"url": answers, "seed": 3, "vocab_size": 128, "t0": t0,
           "t_end": t0 + 3.0, "timeout_s": 5.0,
           "plan": {"loop": "open", "preroll_s": 0.5,
                    "due_s": [i / 10 - 0.5 for i in range(35)],
                    "lengths": [4, 8, 16]}}
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen"], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True, cwd=benchmark_tiny.REPO)
    try:
        child.stdin.write(json.dumps(job))
        child.stdin.close()
        if stopped_s:
            time.sleep(max(0.0, t0 + 0.5 - time.monotonic()))
            child.send_signal(signal.SIGSTOP)
            time.sleep(stopped_s)
            child.send_signal(signal.SIGCONT)
        out = json.loads(child.stdout.read())
        assert child.wait(timeout=60) == 0
    finally:
        if child.poll() is None:
            child.send_signal(signal.SIGCONT)
            child.kill()
            child.wait()
    assert len(out["records"]) == 35
    assert all(r["status"] == 200 for r in out["records"])
    hold = serve_job.longest_hold(out["holds"], -0.5, 3.0)
    if not stopped_s:
        # a loaded test machine may wake it late; never by what a stop does
        assert out["skip_max_s"] < 1.0 and (hold is None or hold[1] < 1.0)
        return
    woke, late = hold
    assert 1.3 <= late <= 2.5 and 1.9 <= woke <= 3.0
    assert late >= loadgen.HOLD_S
    # requests due while it was stopped went out late: the records say so
    assert max(r["sent"] - r["due"] for r in out["records"]) > 1.0


def test_only_a_hold_that_overlaps_the_window_counts():
    holds = [[-4.0, 3.0], [-2.9, 0.3], [10.0, 0.6], [31.0, 0.9], [40.0, 5.0]]
    # [woke at, late by]: the first ended before the pre-roll began, the
    # last began after the last reply
    assert serve_job.longest_hold(holds, -3.0, 30.5) == [31.0, 0.9]
    assert serve_job.longest_hold(holds, -3.0, 30.0) == [10.0, 0.6]
    assert serve_job.longest_hold(holds[:1], -3.0, 30.0) is None
    assert serve_job.longest_hold([], -3.0, 30.0) is None
    # a long hold that began before the pre-roll and ended inside it
    assert serve_job.longest_hold([[-2.0, 9.0]], -3.0, 30.0) == [-2.0, 9.0]


def test_the_last_reply_of_an_open_loop_ends_what_the_watcher_covers():
    records = [{"i": 0, "len": 4, "due": 9.5, "sent": 9.5, "done": 12.25,
                "status": 200, "token": 1, "logit": 0.5},
               {"i": 1, "len": 4, "due": 10.5, "sent": 10.5, "done": 19.0,
                "status": 200, "token": 1, "logit": 0.5}]   # not this run's
    assert serve_job.reduce_records({"loop": "open"}, records, 10.0, 30.0,
                                    128)["last_reply_s"] == 12.25
    early = [{**records[0], "done": 9.75}, records[1]]
    assert serve_job.reduce_records({"loop": "open"}, early, 10.0, 30.0,
                                    128)["last_reply_s"] == 10.0
    assert serve_job.reduce_records({"loop": "closed"}, records, 10.0, 30.0,
                                    128)["last_reply_s"] == 10.0


# -- serve_job.run with windows handed to it ------------------------------------


class Windows:
    """Stands in for ``serve_job.offer_load``: hands back one canned window
    after another. A window is ``(latency in s, failed requests, holds)``;
    its start is ``t_base`` + 40 s x its number, so which window set
    ``setup_s`` can be read from the result."""

    def __init__(self, t_base, windows):
        self.t_base, self.windows, self.calls = t_base, windows, 0

    def __call__(self, url, plan, seed, vocab_size, seconds, timeout_s,
                 snapshot):
        latency, failed, holds = self.windows[self.calls]
        self.calls += 1
        records = []
        for i, (due, length) in enumerate(zip(plan["due_s"],
                                              plan["lengths"])):
            reply = ({"status": 503,
                      "error": "request aged out of the queue deadline"}
                     if 0 <= due and i % 7 < failed
                     else {"status": 200, "token": 5, "logit": 1.5})
            records.append({"i": i, "len": length, "due": due,
                            "sent": due + 0.001, "done": due + latency,
                            **reply})
        return (self.t_base + 40.0 * self.calls,
                {"records": records, "holds": holds, "skip_max_s": 0.0},
                (snapshot(), snapshot()))


FROZE = [[1.25, 3.1], [1.5, 0.3]]     # a skip of 3.1 s and a smaller one
QUIET = [[-3.0, 9.0]]                 # over before the pre-roll began


def test_a_frozen_window_is_measured_again_and_the_quiet_one_is_the_runs(
        root, runtime, monkeypatch, capfd):
    t_start = time.monotonic()
    windows = Windows(t_start, [(1.0, 3, FROZE), (0.05, 0, QUIET)])
    monkeypatch.setattr(serve_job, "offer_load", windows)
    result = _run(root, t_process_start=t_start)
    assert windows.calls == 2
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 40
    # the second window's numbers, the first window's start
    assert result["metrics"]["ttft_p95_ms"]["value"] == pytest.approx(50.0)
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(40.0)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    out, err = capfd.readouterr()
    for stream in (out, err):
        note, = [line for line in stream.splitlines()
                 if "measured again" in line]
        assert note.startswith("[bench] FROZEN window 1 of 3")
        assert "skipped 3.100 s" in note and "woke at 1.250 s" in note
        assert "of 40 failed there" in note and " 0 of 40" not in note
        assert "every window froze" not in stream


def test_three_frozen_windows_report_the_third_with_its_failures(
        root, runtime, monkeypatch, capfd):
    t_start = time.monotonic()
    windows = Windows(t_start, [(1.0, 1, FROZE), (1.0, 2, FROZE),
                                (0.5, 3, FROZE)])
    monkeypatch.setattr(serve_job, "offer_load", windows)
    result = _run(root, t_process_start=t_start)
    assert windows.calls == 3
    assert not result["correct"]
    plan = traffic.request_plan(benchmark_tiny.TINY_TRAFFIC["tiny-open"],
                                SECONDS, SEED)
    third = sum(1 for i, due in enumerate(plan["due_s"])
                if due >= 0 and i % 7 < 3)
    assert result["failed"] == third > 0 and result["attempted"] == 40
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(40.0)
    err = capfd.readouterr().err
    assert err.count("measured again") == 2
    assert "FROZEN window 3 of 3" in err
    assert "every window froze: this one is reported as it stands" in err
    assert f"FAULT: {third} of 40 requests failed or were refused" in err
    # and the command's exit code says so
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: result)
    assert harness.main(CELL, SEED, SECONDS, False,
                        t_process_start=t_start) == harness.EXIT_INCORRECT


def test_a_stall_of_the_programs_own_is_not_measured_again(
        root, runtime, monkeypatch, capfd):
    """The deployment sleeps once, past the queue deadline, while the
    generator's clock runs on time: what queued behind it expires, the
    window stands and the run is incorrect."""
    from ray_tpu._private.config import _config
    offered, slept = [], []
    real_offer, real_call = serve_job.offer_load, serve_job.LastToken.__call__

    def offer(*args):
        offered.append(time.monotonic())
        return real_offer(*args)

    def call(self, items):
        # once, half a second into the first window
        if offered and not slept and time.monotonic() > (
                offered[0] + serve_job.CHILD_START_S + 0.5 + 0.5):
            slept.append(len(offered))
            time.sleep(1.0)
        return real_call(self, items)

    monkeypatch.setattr(serve_job, "offer_load", offer)
    monkeypatch.setattr(serve_job.LastToken, "__call__", call)
    old = _config.get("serve_queue_deadline_ms")
    _config.set("serve_queue_deadline_ms", 150.0)
    try:
        result = _run(root)
    finally:
        _config.set("serve_queue_deadline_ms", old)
    assert slept == [1] and len(offered) == 1     # one window, and it stands
    assert not result["correct"] and result["failed"] > 0
    err = capfd.readouterr().err
    assert "measured again" not in err and "FROZEN" not in err
    assert "FAILED x" in err
    assert re.search(r"longest gap between starts 1\.\d+ s", err)


def test_a_traced_run_reduces_the_kept_windows_trace_alone(
        root, runtime, monkeypatch):
    """Two real windows, the first called frozen: at the reduction the
    trace directory holds one trace, the second window's."""
    real_offer, real_find = serve_job.offer_load, trace_reduce.find_xplane
    starts, found = [], []

    def offer(*args):
        t0, load, snapshots = real_offer(*args)
        starts.append(t0)
        if len(starts) == 1:
            load["holds"].append([0.9, 2.75])
        return t0, load, snapshots

    def find(trace_dir):
        found.extend(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return real_find(trace_dir)

    monkeypatch.setattr(serve_job, "offer_load", offer)
    monkeypatch.setattr(trace_reduce, "find_xplane", find)
    t_start = time.monotonic()
    result = _run(root, trace=True, t_process_start=t_start)
    assert len(starts) == 2 and len(found) == 1
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert result["attempted"] == 40 and result["failed"] == 0
    assert result["device"]["window_s"] == pytest.approx(SECONDS, abs=0.5)
    assert 0 < result["metrics"]["ttft_p50_ms.steady"]["value"]
    # one window's requests, not two windows': 40 and the pre-roll's
    calls = result["metrics"]["batch_size_mean.steady"]["value"]
    assert 1.0 <= calls <= 4.0
