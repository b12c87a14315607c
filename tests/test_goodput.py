"""Goodput & efficiency ledger: wall-clock attribution, compile
accounting, clock-skew correction, federation, and the SLO surfaces.

Covers the exclusive-category ledger (interval nesting, step marks,
derived idle summing to wall-clock), jit first-trace/recompile
detection, the checkpoint bounded-queue stall hook, the data-iterator
wait hook, cross-node federation math (``merge_payloads`` /
``/api/goodput``), the ``ray-tpu top --goodput`` and doctor
``--goodput-baseline`` surfaces, the NTP-style clock-offset estimator
feeding ``task.e2e`` skew correction, and a ProcessCluster preemption
drill (self-skips without the C++ state service).
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu._private import clocksync
from ray_tpu.observability import goodput, perf


@pytest.fixture(autouse=True)
def _goodput_state():
    was = goodput.ENABLED
    goodput.enable()
    goodput.reset()
    goodput.set_job(goodput.DEFAULT_JOB)
    yield
    goodput.reset()
    goodput.set_job(goodput.DEFAULT_JOB)
    if not was:
        goodput.disable()


def _require_state_service():
    """ProcessCluster needs the C++ state service (protoc + g++)."""
    from ray_tpu._native.build import build_state_service
    try:
        build_state_service()
    except Exception as e:
        pytest.skip(f"state service unavailable: {e}")


# -- ledger core ------------------------------------------------------------

def test_categories_are_exclusive_and_sum_to_wall():
    with goodput.interval("data_wait"):
        time.sleep(0.03)
    with goodput.interval("collective_wait"):
        time.sleep(0.02)
    snap = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]
    cats = snap["cats"]
    assert set(cats) == set(goodput.CATEGORIES)
    assert sum(cats.values()) == pytest.approx(snap["wall_s"], rel=1e-9)
    assert cats["data_wait"] >= 0.025
    assert cats["collective_wait"] >= 0.015
    assert cats["idle"] >= 0.0
    assert snap["goodput_pct"] == pytest.approx(
        100.0 * cats["compute"] / snap["wall_s"], abs=1e-6)


def test_unknown_category_rejected():
    with pytest.raises(ValueError):
        goodput.account("checkpoint_stall", 1.0)  # raylint: allow(metric-registry) the rejection under test
    with pytest.raises(ValueError):
        goodput.account("idle", 1.0)  # derived, never accounted
    with pytest.raises(ValueError):
        goodput.interval("not_a_category")  # raylint: allow(metric-registry) the rejection under test


def test_nested_intervals_pause_the_outer():
    """Inner interval time is attributed once, to the inner category:
    the enclosing interval accrues only its own exclusive time."""
    with goodput.interval("data_wait"):
        time.sleep(0.02)
        with goodput.interval("compile"):
            time.sleep(0.04)
        time.sleep(0.02)
    cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
    assert cats["compile"] >= 0.035
    assert 0.03 <= cats["data_wait"] <= 0.06  # ~0.04, never the full 0.08


def test_step_mark_credits_unattributed_time_as_compute():
    goodput.step_mark()                   # anchor the ledger/step window
    led_t0 = time.monotonic()
    time.sleep(0.03)                      # unclaimed -> compute
    with goodput.interval("data_wait"):   # claimed -> not compute
        time.sleep(0.03)
    credited = goodput.step_mark()
    elapsed = time.monotonic() - led_t0
    assert 0.02 <= credited <= elapsed - 0.025
    cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
    assert cats["compute"] == pytest.approx(credited, abs=1e-3)
    # a second immediate mark credits ~nothing (attributed counter reset)
    assert goodput.step_mark() <= 0.01


def test_instrument_jit_counts_compiles_and_recompiles():
    calls = []

    def fn(x):
        calls.append(x)
        time.sleep(0.01)
        return x

    wrapped = goodput.instrument_jit(fn, name="t.step")
    assert wrapped(1.0) == 1.0            # first trace: compile
    assert wrapped(2.0) == 2.0            # same signature: steady state
    assert wrapped("s") == "s"            # new signature: recompile
    snap = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]
    assert snap["compile_count"] == 2
    assert snap["recompile_count"] == 1
    assert snap["cats"]["compile"] >= 0.015
    assert len(calls) == 3                # wrapper never swallows calls
    # perf mirror: compile durations land in the jit.compile histogram
    if perf.ENABLED:
        hists = perf.snapshot()["hists"]
        assert sum(hists.get("jit.compile", {"counts": [0]})["counts"]) >= 2


def test_disabled_fast_path_is_a_noop():
    goodput.disable()
    goodput.account("data_wait", 5.0)
    with goodput.interval("compile"):
        pass
    assert goodput.step_mark() == 0.0
    wrapped = goodput.instrument_jit(lambda x: x, name="t.off")
    assert wrapped(3) == 3
    assert goodput.snapshot()["jobs"] == {}
    goodput.enable()


def test_merge_payloads_adds_seconds_and_recomputes_pct():
    node_a = {"jobs": {"j": {"wall_s": 100.0, "compile_count": 1,
                             "recompile_count": 0,
                             "cats": {"compute": 90.0, "idle": 10.0}}}}
    node_b = {"jobs": {"j": {"wall_s": 100.0, "compile_count": 2,
                             "recompile_count": 1,
                             "cats": {"compute": 10.0, "idle": 90.0}}}}
    merged = goodput.merge_payloads([node_a, node_b])
    rec = merged["j"]
    assert rec["wall_s"] == 200.0 and rec["nodes"] == 2
    assert rec["cats"]["compute"] == 100.0
    assert rec["compile_count"] == 3 and rec["recompile_count"] == 1
    # recomputed from merged seconds (50%), not averaged pcts
    assert rec["goodput_pct"] == pytest.approx(50.0)
    # malformed node payloads are skipped, not fatal
    assert goodput.merge_payloads([None, {"jobs": {"j": "bogus"}},
                                   node_a])["j"]["wall_s"] == 100.0


def test_families_export_and_extract_roundtrip():
    goodput.account("data_wait", 1.25)
    fams = goodput.families()
    assert len(fams) == 1 and fams[0]["type"] == "gauge"
    by_tags = {tuple(sorted(dict(tags).items())): v
               for _n, tags, v in fams[0]["samples"]}
    key = (("category", "data_wait"), ("job", goodput.DEFAULT_JOB))
    assert by_tags[key] == pytest.approx(1.25)
    # the raw payload survives a JSON federation hop untouched
    wire = json.loads(json.dumps(fams))
    payload = goodput.extract_goodput(wire)
    assert payload["jobs"][goodput.DEFAULT_JOB]["cats"]["data_wait"] == \
        pytest.approx(1.25)
    assert goodput.extract_goodput([{"name": "x", "samples": []}]) is None


def test_metrics_snapshot_carries_goodput_family():
    from ray_tpu.util import metrics
    goodput.account("collective_wait", 0.5)
    snap = metrics.snapshot()
    assert any(f.get("name") == "raytpu_goodput_seconds" for f in snap)


# -- instrumentation hooks --------------------------------------------------

def test_ckpt_stall_accounted_on_full_queue(tmp_path):
    """save() on a full bounded queue blocks under the ckpt_stall
    interval; a drain from another thread unblocks it."""
    import threading

    import numpy as np
    from ray_tpu._private.config import _config
    from ray_tpu.checkpoint.engine import CheckpointEngine

    depth_was = _config.checkpoint_queue_depth
    _config.set("checkpoint_queue_depth", 1)
    try:
        eng = CheckpointEngine(str(tmp_path / "ckpt"))
        eng._ensure_writer = lambda: None   # keep the queue full
        eng._queue.put_nowait(None)         # occupy the single slot

        def drain():
            time.sleep(0.1)
            eng._queue.get()

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        eng.save({"x": np.zeros(4)}, step=1)
        t.join(timeout=10)
        cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
        assert cats["ckpt_stall"] >= 0.08
    finally:
        _config.set("checkpoint_queue_depth", depth_was)


def test_data_wait_iterator_attribution():
    from ray_tpu.data.dataset import _data_wait_iter

    def slow_batches():
        for i in range(3):
            time.sleep(0.02)
            yield i

    assert list(_data_wait_iter(slow_batches())) == [0, 1, 2]
    cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
    assert cats["data_wait"] >= 0.05


def test_collective_wait_decorator():
    from ray_tpu.collective.collective import _collective_wait

    @_collective_wait
    def fake_allreduce(x):
        time.sleep(0.03)
        return x

    assert fake_allreduce(7) == 7
    cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
    assert cats["collective_wait"] >= 0.025


def test_session_report_marks_steps():
    """session.report drives step_mark: per-step wall time no explicit
    interval claimed accrues as compute on the training process."""
    from ray_tpu.train import session

    session._init_session(world_rank=0, world_size=1)
    try:
        goodput.step_mark()           # open the step window
        time.sleep(0.03)              # the "device step"
        with goodput.interval("data_wait"):
            time.sleep(0.03)          # claimed: must not become compute
        session.report({"loss": 1.0})
    finally:
        session._shutdown_session()
    cats = goodput.snapshot()["jobs"][goodput.DEFAULT_JOB]["cats"]
    assert cats["compute"] >= 0.02
    assert cats["compute"] <= 0.05    # the data_wait slice stayed out


# -- clock-skew correction --------------------------------------------------

@pytest.fixture()
def _clocksync_state():
    was = clocksync.ENABLED
    clocksync.ENABLED = True
    clocksync.reset()
    yield
    clocksync.reset()
    clocksync.ENABLED = was


def test_clocksync_lowest_rtt_sample_wins(_clocksync_state):
    # congested sample: rtt 0.4s, midpoint 10.2, offset +1.2
    clocksync.observe(10.0, 10.4, 9.0)
    assert clocksync.offset_s() == pytest.approx(1.2)
    # clean sample: rtt 0.02s, midpoint 10.51, offset +1.51 -> wins
    clocksync.observe(10.5, 10.52, 9.0)
    assert clocksync.offset_s() == pytest.approx(1.51)
    assert clocksync.synced()
    # a later congested sample never displaces the low-RTT estimate
    clocksync.observe(11.0, 11.8, 9.0)
    assert clocksync.offset_s() == pytest.approx(1.51)


def test_clocksync_rebase_roundtrip_and_guards(_clocksync_state):
    clocksync.observe(100.0, 100.02, 90.01)   # offset ~ +10.0
    local = 123.456
    assert clocksync.to_local_s(clocksync.to_server_s(local)) == \
        pytest.approx(local)
    assert clocksync.to_server_s(local) == pytest.approx(local - 10.0,
                                                         abs=0.02)
    before = clocksync.offset_s()
    clocksync.observe(50.0, 49.0, 40.0)   # negative rtt: clock stepped
    clocksync.observe(50.0, 50.01, 0.0)   # beacon absent (old service)
    assert clocksync.offset_s() == before
    clocksync.reset()
    assert clocksync.offset_s() == 0.0 and not clocksync.synced()


def test_clocksync_exports_skew_gauge(_clocksync_state):
    clocksync.observe(10.0, 10.02, 9.51)  # offset ~ +0.5s
    samples = clocksync._skew_gauge().samples()
    assert any(name == "clock_skew_ms" and v == pytest.approx(500.0, abs=20)
               for name, _t, v in samples)


def test_spec_stamp_rebases_through_service_timebase(_clocksync_state):
    """_spec_to_msg ships perf_submit_s in the service timebase;
    _msg_to_spec rebases onto the receiving clock. With one process
    playing both sides the round trip is identity; the wire stamp is
    shifted by the estimated offset."""
    from ray_tpu.protocol import pb
    clocksync.observe(200.0, 200.02, 150.01)  # offset ~ +50s
    stamp = time.time()
    wire = clocksync.to_server_s(stamp)
    assert wire == pytest.approx(stamp - 50.0, abs=0.1)
    msg = pb.TaskSpecMsg(perf_submit_s=wire)
    parsed = pb.TaskSpecMsg()
    parsed.ParseFromString(msg.SerializeToString())
    assert clocksync.to_local_s(parsed.perf_submit_s) == \
        pytest.approx(stamp, abs=1e-6)


def test_heartbeat_reply_carries_server_time_field():
    from ray_tpu.protocol import pb
    rep = pb.HeartbeatReply(recognized=True, server_time_ms=1234.5)
    parsed = pb.HeartbeatReply()
    parsed.ParseFromString(rep.SerializeToString())
    assert parsed.server_time_ms == 1234.5
    # absent field reads 0.0 — the "service predates the beacon" marker
    assert pb.HeartbeatReply().server_time_ms == 0.0


# -- surfaces: top / render / doctor ----------------------------------------

def test_top_partial_federation_renders_placeholder():
    """A node that never recorded a family gets a '—' placeholder row
    instead of silently vanishing from the table."""
    from ray_tpu.scripts.cli import _render_top, _top_rows
    summ = {"count": 10.0, "mean_ms": 1.0, "p50_ms": 1.0,
            "p95_ms": 1.0, "p99_ms": 1.0}
    payload = {"nodes": {"node:aa": {"task.execute": summ,
                                     "rpc.call": summ},
                         "node:bb": {"rpc.call": summ}}}
    rows = {(n, h): s for n, h, s, _f in _top_rows(payload)}
    assert rows[("node:bb", "task.execute")] is None
    assert rows[("node:aa", "task.execute")] == summ
    text = _render_top(payload)
    placeholder = [ln for ln in text.splitlines()
                   if ln.startswith("node:bb") and "task.execute" in ln]
    assert len(placeholder) == 1 and "—" in placeholder[0]
    assert not any("—" in ln for ln in text.splitlines()
                   if "rpc.call" in ln)


def test_render_goodput_table():
    from ray_tpu.scripts.cli import _render_goodput
    rec = {"wall_s": 100.0, "goodput_pct": 90.0,
           "cats": {c: 0.0 for c in goodput.CATEGORIES}}
    rec["cats"].update(compute=90.0, idle=10.0)
    payload = {"categories": list(goodput.CATEGORIES),
               "jobs": {"train-1": rec},
               "nodes": {"node:aa": {"train-1": rec}},
               "missing_hosts": ["node:dead"]}
    text = _render_goodput(payload)
    lines = text.splitlines()
    assert "GOODPUT%" in lines[0] and "restart_" in lines[0]
    assert any(ln.startswith("CLUSTER") and "90.0%" in ln for ln in lines)
    assert any(ln.startswith("node:aa") for ln in lines)
    assert "1 unreachable host(s) omitted" in lines[-1]
    empty = _render_goodput({"categories": list(goodput.CATEGORIES)})
    assert "no goodput ledgers" in empty


def test_doctor_goodput_section_and_baseline_drift():
    from ray_tpu import doctor
    goodput.account("data_wait", 2.0)
    goodput.account("restart_downtime", 30.0)
    goodput.step_mark()
    collected = {"ts": time.time(), "errors": [],
                 "cluster": {"metrics": {"snapshots": {
                     "head": goodput.families()}}}}
    job = goodput.DEFAULT_JOB
    loose = doctor._goodput_reports(
        collected, baseline={job: {"goodput_pct": 0.0,
                                   "restart_downtime_s": 60.0}})
    assert loose["jobs"][job]["cats"]["restart_downtime"] == \
        pytest.approx(30.0)
    assert loose["drift"] == []
    tight = doctor._goodput_reports(
        collected, baseline={job: {"goodput_pct": 99.0,
                                   "restart_downtime_s": 1.0,
                                   "tolerance": 1.0}})
    assert {d["metric"] for d in tight["drift"]} == \
        {"goodput_pct", "restart_downtime_s"}
    # unknown jobs in the baseline are ignored, not phantom drift
    assert doctor._goodput_reports(
        collected, baseline={"ghost": {"goodput_pct": 99.0}})["drift"] == []
    report = doctor.diagnose(
        collected, goodput_baseline={job: {"goodput_pct": 99.0}})
    assert not report["healthy"]
    assert report["goodput"]["drift"]
    rendered = doctor.render_text(report)
    assert "GOODPUT" in rendered and "GOODPUT DRIFT" in rendered
    assert "restart_downtime" in rendered


def test_head_goodput_merges_and_degrades():
    """_goodput merges per-node payloads and surfaces unreachable hosts
    without failing the endpoint."""
    from ray_tpu.dashboard.head import DashboardHead
    goodput.account("data_wait", 1.0)
    head = DashboardHead.__new__(DashboardHead)
    fams = goodput.families()
    head._metric_snapshots = lambda: (
        {"head": fams, "node:aa": fams, "node:bb": []}, ["node:cc"])
    payload = head._goodput()
    job = goodput.DEFAULT_JOB
    assert payload["missing_hosts"] == ["node:cc"]
    assert set(payload["nodes"]) == {"head", "node:aa"}
    merged = payload["jobs"][job]
    assert merged["nodes"] == 2
    assert merged["cats"]["data_wait"] == pytest.approx(2.0)
    assert merged["wall_s"] == pytest.approx(
        2 * fams[0]["goodput"]["jobs"][job]["wall_s"], rel=0.5)
    assert set(payload["categories"]) == set(goodput.CATEGORIES)


# -- acceptance drill (self-skip without the C++ state service) --------------

def test_cluster_goodput_preemption_drill():
    """node.preempt chaos evicts the daemon hosting a stateful actor:
    the survivor's restore accounts the cross-process downtime gap, the
    federated /api/goodput shows it (categories still summing to
    wall-clock within 1%), goodput_pct recovers as compute resumes, and
    a doctor goodput baseline flags the lowered budget."""
    from ray_tpu.cluster_utils import ProcessCluster
    from ray_tpu.dashboard.head import DashboardHead
    from ray_tpu import doctor
    from ray_tpu.util.scheduling_strategies import \
        NodeAffinitySchedulingStrategy
    from tests.test_drain import Keeper, _actor_call_with_retry
    _require_state_service()
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=2, num_cpus=2)
    # the chaos daemon's 6th watcher poll (~3s) returns the eviction
    # notice; a soft affinity puts the actor on it and lets a survivor
    # take it afterwards (a resource that only the victim has would not)
    chaos_addr = c.add_daemon(env={"RAY_TPU_CHAOS": "7:node.preempt@6=drop",
                                   "RAY_TPU_PREEMPT_LEAD_S": "20"})
    try:
        ray_tpu.init(address=c.address)
        rt = ray_tpu._private.worker.global_worker().runtime

        chaos_node = next(n.node_id.hex() for n in rt.state.list_nodes()
                          if n.address == chaos_addr)
        k = Keeper.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
            chaos_node, soft=True)).remote()
        assert ray_tpu.get(k.inc.remote(), timeout=60) == 1
        victim_node, _pid = ray_tpu.get(k.where.remote(), timeout=30)

        # wait out the eviction: the victim drains and decommissions
        deadline = time.monotonic() + 90
        gone = False
        while time.monotonic() < deadline:
            info = {n.node_id.hex(): n for n in rt.state.list_nodes()}
            n = info.get(victim_node)
            if n is not None and not n.alive:
                gone = True
                break
            time.sleep(0.5)
        assert gone, "chaos daemon never decommissioned"

        # actor migrates + resumes; the survivor accounts the gap
        assert _actor_call_with_retry(k.inc, 90) == 2

        head = DashboardHead(c.address)
        try:
            payload = head._goodput()
            job = goodput.DEFAULT_JOB
            merged = payload["jobs"].get(job)
            assert merged is not None, payload
            downtime = merged["cats"].get("restart_downtime", 0.0)
            assert downtime > 0.0, "preemption gap never attributed"
            pct_before = merged["goodput_pct"]
            # per-node and merged ledgers: categories sum to wall-clock
            # within 1% (the exclusivity acceptance bound)
            for node, jobs in payload["nodes"].items():
                for jname, rec in jobs.items():
                    total = sum(rec["cats"].values())
                    assert total == pytest.approx(
                        rec["wall_s"], rel=0.01), (node, jname)
            assert sum(merged["cats"].values()) == pytest.approx(
                merged["wall_s"], rel=0.01)

            # goodput recovers: steady compute on the driver raises the
            # merged percentage above the post-eviction reading (the
            # drill's wall is dominated by idle/downtime, so a ~1s
            # compute burst moves the merged ratio up)
            compute_before = merged["cats"].get("compute", 0.0)
            goodput.step_mark()
            for _ in range(20):
                time.sleep(0.05)
                goodput.step_mark()
            after = head._goodput()["jobs"][job]
            assert after["cats"]["compute"] >= compute_before + 0.5
            assert after["goodput_pct"] > pct_before

            # the doctor gate flags the preemption-lowered budget
            snaps, _missing = head._metric_snapshots()
            collected = {"ts": time.time(), "errors": [],
                         "cluster": {"metrics": {"snapshots": snaps}}}
            report = doctor.diagnose(
                collected,
                goodput_baseline={job: {"goodput_pct": 99.0,
                                        "restart_downtime_s": 0.001}})
            metrics_flagged = {d["metric"]
                               for d in report["goodput"]["drift"]}
            assert "restart_downtime_s" in metrics_flagged
        finally:
            head.stop()
    finally:
        ray_tpu.shutdown()
        c.shutdown()
