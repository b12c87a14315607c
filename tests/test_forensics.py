"""Flight recorder + crash bundles + health doctor (the black box).

Acceptance path for the forensics plane: killing a process mid-task —
deterministically via a chaos ``exit`` rule, or with a raw SIGKILL that
runs no hooks at all — must leave a sealed crash bundle on disk from
which ``python -m ray_tpu.doctor --json`` reconstructs the in-flight
trace_id, the last spans/log lines, and the exit reason. Subprocess
tests cover both sealing paths without needing the C++ state service;
the ProcessCluster tests exercise the same story through a real daemon.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import chaos, observability
from ray_tpu._private.config import _config
from ray_tpu._private.profiling import get_profiler


@pytest.fixture(autouse=True)
def _forensics_hygiene():
    prof_was = _config.get("profiling_enabled")
    yield
    chaos.clear()
    observability.disable()
    _config.set("profiling_enabled", prof_was)
    get_profiler().clear()


def _require_state_service():
    """ProcessCluster needs the C++ state service (protoc + g++)."""
    from ray_tpu._native.build import build_state_service
    try:
        build_state_service()
    except Exception as e:
        pytest.skip(f"state service unavailable: {e}")


def _flight_env(tmp_path, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RAY_TPU_FLIGHT_RECORDER_DIR=str(tmp_path),
               RAY_TPU_FLIGHT_RECORDER_SPOOL_MS="50")
    env.update(extra)
    return env


def _bundles(root):
    out = []
    for name in sorted(os.listdir(root)):
        path = os.path.join(root, name, "BUNDLE.json")
        if os.path.exists(path):
            with open(path) as f:
                out.append(json.load(f))
    return out


def _run_doctor(root, *extra_args, env=None):
    p = subprocess.run(
        [sys.executable, "-m", "ray_tpu.doctor",
         "--flight-dir", str(root), "--json", *extra_args],
        env=env or dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, (p.returncode, p.stdout, p.stderr)
    return json.loads(p.stdout)


# -- self-sealing: chaos exit (the deterministic hard-death vehicle) --------

def test_chaos_exit_seals_bundle_with_inflight_trace(tmp_path):
    """A chaos ``exit`` rule fires while a task is in flight: the
    registered exit hook must seal a bundle naming the task and its
    trace id before ``os._exit`` — the deterministic stand-in for dying
    mid-task."""
    code = """
import os
os.environ["RAY_TPU_CHAOS"] = "7:task.execute[key=boom*]@1=exit(41)"
from ray_tpu.observability import recorder
from ray_tpu import chaos
rec = recorder.install("worker")
assert rec is not None and recorder.ENABLED
recorder.task_started("feedc0de", "boom_task",
                      trace_id="trace-abc", span_id="span-1")
chaos.inject("task.execute", key="boom-1")
raise SystemExit("chaos exit did not fire")
"""
    p = subprocess.run([sys.executable, "-c", code],
                       env=_flight_env(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 41, (p.returncode, p.stdout, p.stderr)
    bundles = _bundles(tmp_path)
    assert len(bundles) == 1, os.listdir(tmp_path)
    b = bundles[0]
    assert b["sealed_by"] == "self"
    assert "chaos-exit(41)" in b["exit_reason"]
    assert "task.execute" in b["exit_reason"]
    assert b["trace_ids"] == ["trace-abc"]
    assert b["inflight"]["feedc0de"]["name"] == "boom_task"
    # the chaos tape shows the rule that fired
    assert any("exit(41)" in line for line in b["chaos"])
    # sealing captured every live thread's stack
    assert any("MainThread" in k for k in b["thread_stacks"])


def test_unhandled_exception_seals_bundle(tmp_path):
    code = """
from ray_tpu.observability import recorder
recorder.install("driver")
raise RuntimeError("kaboom-marker")
"""
    p = subprocess.run([sys.executable, "-c", code],
                       env=_flight_env(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "kaboom-marker" in p.stderr  # original excepthook still chained
    (b,) = _bundles(tmp_path)
    assert b["exit_reason"].startswith("unhandled-exception: RuntimeError")
    assert b["exception"]["type"] == "RuntimeError"
    assert "kaboom-marker" in b["exception"]["traceback"]


def test_clean_exit_leaves_no_bundle(tmp_path):
    """A normal interpreter exit is NOT a crash: atexit marks the
    recording clean and neither the sweep nor the doctor bundles it."""
    code = """
from ray_tpu.observability import recorder
recorder.install("driver")
"""
    p = subprocess.run([sys.executable, "-c", code],
                       env=_flight_env(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert _bundles(tmp_path) == []
    from ray_tpu.observability import recorder
    assert recorder.seal_orphans(root=str(tmp_path)) == []
    assert _bundles(tmp_path) == []
    report = recorder.disk_report(root=str(tmp_path))
    assert len(report["recordings"]) == 1
    assert report["recordings"][0]["clean_exit"] is True


# -- posthumous sealing: SIGKILL runs no hooks ------------------------------

def test_sigkill_midtask_doctor_reconstructs(tmp_path):
    """The acceptance criterion: SIGKILL a process mid-task, then
    ``python -m ray_tpu.doctor --json`` seals the orphan posthumously
    and reconstructs the in-flight trace_id, last log lines and exit
    reason from the spool + lastwords the dead process left behind."""
    code = """
import logging, sys, time
from ray_tpu._private import log_ring
log_ring.install()
from ray_tpu.observability import recorder
rec = recorder.install("worker")
logging.getLogger("ray_tpu").warning("lastwords-log-marker")
recorder.task_started("deadbeef", "stuck_task",
                      trace_id="trace-sigkill", span_id="s-9")
print(rec.dir, flush=True)
time.sleep(60)
"""
    p = subprocess.Popen([sys.executable, "-c", code],
                         env=_flight_env(tmp_path),
                         stdout=subprocess.PIPE, text=True)
    try:
        rec_dir = p.stdout.readline().strip()
        assert rec_dir
        # wait for at least one spool tick to hit disk
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if any(n.startswith("spool-") and
                   os.path.getsize(os.path.join(rec_dir, n)) > 0
                   for n in os.listdir(rec_dir)):
                break
            time.sleep(0.05)
        p.kill()
    finally:
        p.wait(timeout=30)
    assert _bundles(tmp_path) == []  # SIGKILL ran no hooks
    rep = _run_doctor(tmp_path, env=_flight_env(tmp_path))
    assert len(rep["sealed_now"]) == 1
    assert rep["healthy"] is False
    (crash,) = rep["crashes"]
    assert crash["sealed_by"] == "posthumous:doctor"
    assert "external-kill" in crash["exit_reason"]
    assert crash["trace_ids"] == ["trace-sigkill"]
    assert crash["inflight_tasks"] == [
        {"task_id": "deadbeef", "name": "stuck_task",
         "trace_id": "trace-sigkill"}]
    (b,) = _bundles(tmp_path)
    assert any("lastwords-log-marker" in line for line in b["logs"])
    # a second doctor run finds nothing new to seal (idempotent)
    rep2 = _run_doctor(tmp_path, env=_flight_env(tmp_path))
    assert rep2["sealed_now"] == []
    assert len(rep2["crashes"]) == 1


def test_seal_orphans_skips_live_processes(tmp_path):
    code = """
import sys, time
from ray_tpu.observability import recorder
rec = recorder.install("worker")
print(rec.dir, flush=True)
time.sleep(60)
"""
    p = subprocess.Popen([sys.executable, "-c", code],
                         env=_flight_env(tmp_path),
                         stdout=subprocess.PIPE, text=True)
    try:
        assert p.stdout.readline().strip()
        from ray_tpu.observability import recorder
        assert recorder.seal_orphans(root=str(tmp_path)) == []
    finally:
        p.kill()
        p.wait(timeout=30)


# -- doctor diagnosis units -------------------------------------------------

def test_doctor_straggler_detection_on_synthetic_timeline():
    from ray_tpu.doctor import diagnose
    events = []
    for pid, dur in (("node:aa", 100.0), ("node:bb", 100.0),
                     ("node:cc", 1000.0)):
        for _ in range(4):
            events.append({"ph": "X", "cat": "task", "name": "train_step",
                           "pid": pid, "dur": dur, "ts": 0})
    collected = {"ts": 0.0, "errors": [], "sealed_now": [],
                 "local": {"root": "", "recordings": [], "bundles": []},
                 "cluster": {"timeline": {"traceEvents": events,
                                          "missing_hosts": []}}}
    rep = diagnose(collected)
    assert len(rep["stragglers"]) == 1
    s = rep["stragglers"][0]
    assert s["process"] == "node:cc" and s["task"] == "train_step"
    assert s["slowdown"] >= 3.0
    # uniform durations → no stragglers
    for e in events:
        e["dur"] = 100.0
    assert diagnose(collected)["stragglers"] == []


def test_doctor_hang_detection_from_heartbeat_gauge():
    from ray_tpu.doctor import diagnose
    snaps = {"node:ab12cd34": [{
        "name": "heartbeat_consecutive_misses", "type": "gauge",
        "help": "", "samples": [["heartbeat_consecutive_misses",
                                 [["node", "ab12cd34"]], 5.0]]}]}
    forensics = {"nodes": {"ab12cd34ef": {
        "stacks": {"MainThread": "File x, line 1"},
        "inflight": {"t1": {"name": "wedged_task"}}}},
        "missing_hosts": []}
    collected = {"ts": 0.0, "errors": [], "sealed_now": [],
                 "local": {"root": "", "recordings": [], "bundles": []},
                 "cluster": {"metrics": {"snapshots": snaps,
                                         "missing_hosts": []},
                             "forensics": forensics}}
    rep = diagnose(collected)
    assert len(rep["hangs"]) == 1
    h = rep["hangs"][0]
    assert h["consecutive_misses"] == 5.0
    assert h["inflight_tasks"] == ["wedged_task"]
    assert "MainThread" in h["stacks"]


def test_doctor_render_text_mentions_the_story(tmp_path):
    """The human rendering names the crash, the trace and the verdict."""
    code = """
import os
os.environ["RAY_TPU_CHAOS"] = "1:task.execute@1=exit(3)"
from ray_tpu.observability import recorder
from ray_tpu import chaos
recorder.install("worker")
recorder.task_started("cafe0001", "render_task", trace_id="trace-render")
chaos.inject("task.execute")
"""
    p = subprocess.run([sys.executable, "-c", code],
                       env=_flight_env(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 3
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.doctor", "--flight-dir",
         str(tmp_path)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    text = out.stdout
    assert "CRASHES (1)" in text
    assert "chaos-exit(3)" in text
    assert "trace-render" in text
    assert "render_task" in text
    assert "verdict:" in text


def test_doctor_healthy_on_empty_dir(tmp_path):
    rep = _run_doctor(tmp_path)
    assert rep["healthy"] is True
    assert rep["crashes"] == []
    # --out writes the same report atomically
    out_path = tmp_path / "report.json"
    rep2 = _run_doctor(tmp_path, "--out", str(out_path))
    assert json.loads(out_path.read_text())["healthy"] is True
    assert rep2["healthy"] is True


# -- through a real cluster (skipped where the state service can't build) ---

def test_cluster_sigkill_daemon_doctor_reconstructs(tmp_path):
    """SIGKILL a real host daemon mid-task; a chaos ``delay`` holds the
    task in flight long enough to die with it. The doctor (disk mode:
    the daemons share this machine's flight dir) must reconstruct the
    in-flight task and its trace id from the posthumous bundle."""
    from ray_tpu.cluster_utils import ProcessCluster
    _require_state_service()
    ray_tpu.shutdown()
    flight_env = {
        "RAY_TPU_FLIGHT_RECORDER_DIR": str(tmp_path),
        "RAY_TPU_FLIGHT_RECORDER_SPOOL_MS": "50",
        # hold task.execute for 30s so the kill lands mid-task
        "RAY_TPU_CHAOS": "5:task.execute[name=*slow_task*]@1=delay(30000)",
    }
    c = ProcessCluster(num_daemons=1, num_cpus=2)
    try:
        # the task is held to the daemon that carries the schedule and the
        # flight directory: on the other it would simply return
        c.add_daemon(num_cpus=2, env=flight_env, resources={"doomed": 1.0})
        observability.enable()
        ray_tpu.init(address=c.address)

        @ray_tpu.remote(resources={"doomed": 1.0})
        def slow_task():
            return 1

        with observability.span("doomed-root") as sp:
            trace_id = sp.trace_id
            ref = slow_task.remote()
            # wait until the task is actually in flight on a daemon:
            # its recorder spools an inflight entry with our trace id
            deadline = time.monotonic() + 60
            seen = False
            while time.monotonic() < deadline and not seen:
                for name in os.listdir(tmp_path):
                    lw = os.path.join(tmp_path, name, "lastwords.bin")
                    if os.path.exists(lw) and \
                            trace_id.encode() in open(lw, "rb").read():
                        seen = True
                        break
                time.sleep(0.1)
            assert seen, "task never showed up in a daemon's lastwords"
            c.kill_daemon(len(c.daemons) - 1)
            del ref
        rep = _run_doctor(tmp_path, env=_flight_env(tmp_path))
        crashes = [x for x in rep["crashes"]
                   if trace_id in x["trace_ids"]]
        assert crashes, rep["crashes"]
        crash = crashes[0]
        assert "external-kill" in crash["exit_reason"]
        assert any(t["name"].endswith("slow_task")
                   for t in crash["inflight_tasks"])
        assert crash["role"] == "host_daemon"
        assert crash["chaos_spec"].endswith("delay(30000)")
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_cluster_chaos_exit_daemon_seals_itself(tmp_path):
    """chaos ``exit`` inside a daemon: the exit hook seals the bundle
    on the way down (sealed_by=self), no posthumous help needed."""
    from ray_tpu.cluster_utils import ProcessCluster
    _require_state_service()
    ray_tpu.shutdown()
    flight_env = {
        "RAY_TPU_FLIGHT_RECORDER_DIR": str(tmp_path),
        "RAY_TPU_FLIGHT_RECORDER_SPOOL_MS": "50",
        "RAY_TPU_CHAOS": "5:task.execute[name=*dying_task*]@1=exit(19)",
    }
    c = ProcessCluster(num_daemons=1, num_cpus=2)
    try:
        c.add_daemon(num_cpus=2, env=flight_env, resources={"doomed": 1.0})
        ray_tpu.init(address=c.address)

        @ray_tpu.remote(resources={"doomed": 1.0})  # as the drill above
        def dying_task():
            return 1

        ref = dying_task.remote()
        deadline = time.monotonic() + 60
        sealed = []
        while time.monotonic() < deadline and not sealed:
            sealed = [b for b in _bundles(tmp_path)
                      if b["sealed_by"] == "self"]
            time.sleep(0.2)
        assert sealed, "daemon did not self-seal on chaos exit"
        b = sealed[0]
        assert "chaos-exit(19)" in b["exit_reason"]
        assert b["role"] == "host_daemon"
        assert any(t["name"].endswith("dying_task")
                   for t in b["inflight"].values())
        del ref
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_dashboard_forensics_endpoint(tmp_path):
    """/api/forensics federates stacks + bundle inventories; the head's
    own process always reports."""
    import urllib.request
    from ray_tpu.cluster_utils import ProcessCluster
    from ray_tpu.dashboard import start_dashboard
    _require_state_service()
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=1, num_cpus=2)
    try:
        ray_tpu.init(address=c.address)
        head = start_dashboard(c.address)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{head.port}/api/forensics",
                    timeout=30) as r:
                payload = json.loads(r.read())
            assert "head" in payload and "nodes" in payload
            assert isinstance(payload["missing_hosts"], list)
            assert payload["head"]["stacks"]  # our own threads at least
            for node in payload["nodes"].values():
                assert "stacks" in node and "forensics" in node
        finally:
            head.stop()
    finally:
        ray_tpu.shutdown()
        c.shutdown()
