"""Continuous performance plane: histograms, sampler, federation, SLO gate.

Covers the streaming latency histograms (bucket math, lock-free shard
merge, Prometheus export, cross-process federation), the periodic stack
sampler (folded-stack aggregation, trace tagging, windowed diffs), the
``ray-tpu top`` straggler view, and the drift-detection gates
(``bench_micro --check`` and the doctor's ``--perf-baseline``).
"""

import json
import math
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import observability
from ray_tpu.observability import perf, sampler


@pytest.fixture(autouse=True)
def _perf_state():
    was = perf.ENABLED
    perf.enable()
    perf.reset()
    yield
    sampler.stop()
    perf.reset()
    if not was:
        perf.disable()


def _require_state_service():
    """ProcessCluster needs the C++ state service (protoc + g++)."""
    from ray_tpu._native.build import build_state_service
    try:
        build_state_service()
    except Exception as e:
        pytest.skip(f"state service unavailable: {e}")


# -- histogram core ---------------------------------------------------------

def test_bucket_bounds_layout():
    b = perf.bucket_bounds()
    assert len(b) == 64  # perf_hist_buckets default
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] == math.inf
    assert b[-2] == pytest.approx(60_000.0)
    assert all(x < y for x, y in zip(b, b[1:]))
    # geometric: constant ratio between consecutive finite bounds
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 3)]
    assert max(ratios) / min(ratios) == pytest.approx(1.0, rel=1e-9)


def test_bucket_boundary_exactness():
    """A value exactly on a bucket boundary lands in THAT bucket
    (Prometheus ``le`` is inclusive), never the next one up."""
    b = perf.bucket_bounds()
    h = perf.get("t.boundary")
    for i in (0, 3, 17, len(b) - 2):
        h.observe(b[i])
    counts, _ = h.merged()
    for i in (0, 3, 17, len(b) - 2):
        assert counts[i] == 1, f"bound {i} leaked into another bucket"
    assert sum(counts) == 4
    # below-domain and absurd values clamp to the edge buckets
    h2 = perf.get("t.edges")
    h2.observe(0.0)
    h2.observe(1e12)
    counts2, _ = h2.merged()
    assert counts2[0] == 1 and counts2[-1] == 1


def test_cross_thread_shard_merge():
    h = perf.get("t.threads")
    n_threads, per_thread = 8, 500

    def work():
        for _ in range(per_thread):
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    counts, sum_ms = h.merged()
    assert sum(counts) == n_threads * per_thread
    assert sum_ms == pytest.approx(n_threads * per_thread * 1.0)
    # one single-writer shard per observing thread
    assert len(h._shards) == n_threads


def test_quantile_within_bucket_error_vs_numpy():
    """Histogram quantiles vs exact numpy percentiles on a lognormal
    latency distribution: the geometric-midpoint estimate must stay
    within the bucket error bound (one bucket of slack for rank
    discretization)."""
    rng = np.random.RandomState(7)
    vals = rng.lognormal(mean=1.0, sigma=0.6, size=5000)  # ~ms scale
    h = perf.get("t.quantile")
    for v in vals:
        h.observe(float(v))
    counts, _ = h.merged()
    bound = 2.0 * (math.sqrt(perf.bucket_ratio()) - 1.0) + 0.02
    for q in (0.50, 0.95, 0.99):
        est = perf.quantile(counts, q)
        ref = float(np.percentile(vals, q * 100))
        assert abs(est - ref) / ref <= bound, \
            f"q={q}: est {est} vs numpy {ref} beyond {bound:.2%}"


def test_summarize_and_merge_counts():
    h = perf.get("t.summarize")
    for _ in range(100):
        h.observe(10.0)
    counts, sum_ms = h.merged()
    s = perf.summarize(counts, sum_ms)
    assert s["count"] == 100
    assert s["mean_ms"] == pytest.approx(10.0)
    for key in ("p50_ms", "p95_ms", "p99_ms"):
        assert abs(s[key] - 10.0) / 10.0 <= \
            math.sqrt(perf.bucket_ratio()) - 1.0
    # federation merge is an exact element-wise sum
    merged = perf.merge_counts([counts, counts, counts])
    assert sum(merged) == 300
    assert perf.summarize(merged, 3 * sum_ms)["p50_ms"] == s["p50_ms"]


def test_enabled_fast_path():
    perf.disable()
    perf.observe("t.off", 5.0)
    assert "t.off" not in perf.snapshot()["hists"]
    perf.enable()
    perf.observe("t.on", 5.0)
    assert perf.snapshot()["hists"]["t.on"]["counts"]


def test_families_export_and_extract_roundtrip():
    perf.observe("t.export", 2.5)
    perf.observe("t.export", 250.0)
    fams = [f for f in perf.families()
            if f["name"] == "raytpu_perf_t_export_ms"]
    assert len(fams) == 1
    fam = fams[0]
    assert fam["type"] == "histogram"
    buckets = [(dict(tags)["le"], v) for name, tags, v in fam["samples"]
               if name.endswith("_bucket")]
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 2.0
    cumulative = [v for _le, v in buckets]
    assert cumulative == sorted(cumulative)  # cumulative by construction
    assert any(name.endswith("_count") and v == 2.0
               for name, _t, v in fam["samples"])
    # the raw payload survives a JSON federation hop untouched
    wire = json.loads(json.dumps([fam]))
    got = perf.extract_perf(wire)
    assert sum(got["t.export"]["counts"]) == 2
    assert got["t.export"]["sum_ms"] == pytest.approx(252.5)


def test_metrics_snapshot_carries_perf_families():
    from ray_tpu.util import metrics
    perf.observe("t.metrics_bridge", 1.0)
    snap = metrics.snapshot()
    assert any(f.get("name") == "raytpu_perf_t_metrics_bridge_ms"
               for f in snap)
    text = metrics.generate_prometheus_text()
    assert "raytpu_perf_t_metrics_bridge_ms_bucket" in text


# -- stack sampler ----------------------------------------------------------

def _spin(stop_s):
    x = 0
    while time.monotonic() < stop_s:
        x += 1
    return x


def test_sampler_folds_stacks():
    s = sampler.start(hz=200.0)
    try:
        t = threading.Thread(target=_spin,
                             args=(time.monotonic() + 0.6,),
                             name="spinner", daemon=True)
        t.start()
        t.join()
    finally:
        sampler.stop()
    prof = s.snapshot()
    assert prof["ticks"] > 0
    assert prof["samples"], "no stacks collected"
    spin_rows = [r for r in prof["samples"]
                 if "test_perf.py:_spin" in r["stack"]]
    assert spin_rows, "busy thread never sampled"
    # root-first folding: the thread bootstrap precedes the target frame
    assert all(r["stack"].index("threading.py") <
               r["stack"].index("test_perf.py:_spin")
               for r in spin_rows)
    text = sampler.collapsed(prof)
    assert any(line.rsplit(" ", 1)[1].isdigit()
               for line in text.splitlines())


def test_sampler_trace_tagging():
    """Samples landing while a thread is inside an observability span are
    attributed to that span's trace id."""
    obs_was = observability.ENABLED
    observability.enable()
    s = sampler.start(hz=200.0)
    try:
        with observability.span("perf.tagged") as sp:
            trace_id = sp.trace_id
            _spin(time.monotonic() + 0.6)
    finally:
        sampler.stop()
        if not obs_was:
            observability.disable()
    tagged = [r for r in s.snapshot()["samples"]
              if r["trace"] == trace_id]
    assert tagged, "no sample attributed to the active span"
    assert sampler._trace_stacks == {}  # balanced enter/exit


def test_diff_and_merge_profiles():
    older = {"hz": 10.0, "ticks": 5, "duration_s": 0.5,
             "samples": [{"stack": "a;b", "trace": "", "count": 3},
                         {"stack": "a;c", "trace": "t1", "count": 2}]}
    newer = {"hz": 10.0, "ticks": 9, "duration_s": 0.9,
             "samples": [{"stack": "a;b", "trace": "", "count": 7},
                         {"stack": "a;c", "trace": "t1", "count": 2},
                         {"stack": "d", "trace": "", "count": 1}]}
    win = sampler.diff_profiles(newer, older)
    assert win["ticks"] == 4
    by_key = {(r["stack"], r["trace"]): r["count"]
              for r in win["samples"]}
    assert by_key == {("a;b", ""): 4, ("d", ""): 1}  # unchanged key drops
    merged = sampler.merge_profiles([older, newer])
    assert merged["ticks"] == 14
    total = {(r["stack"], r["trace"]): r["count"]
             for r in merged["samples"]}
    assert total[("a;b", "")] == 10 and total[("a;c", "t1")] == 4
    pp = sampler.pprof_json(win)
    assert pp["sample_type"] == [{"type": "samples", "unit": "count"}]
    assert pp["period"] == pytest.approx(0.1)
    assert {"location": ["a", "b"], "value": [4]} in pp["samples"]


# -- drift detection --------------------------------------------------------

def test_bench_check_drift_pos_neg(tmp_path, monkeypatch):
    import bench_micro
    baseline = tmp_path / "base.json"
    baseline.write_text(json.dumps([
        {"metric": "inproc_task_execute_p99_us", "value": 100.0,
         "unit": "us"},
        {"metric": "inproc_perf_overhead_pct", "value": 15.0, "unit": "%"},
    ]))
    monkeypatch.setattr(bench_micro, "RESULTS", [
        {"metric": "inproc_task_execute_p99_us", "value": 100.0,
         "unit": "us"},
        {"metric": "inproc_perf_overhead_pct", "value": 5.0, "unit": "%"},
    ])
    assert bench_micro.check_against(str(baseline), 0.7) == 0
    monkeypatch.setattr(bench_micro, "RESULTS", [
        {"metric": "inproc_task_execute_p99_us", "value": 500.0,
         "unit": "us"},
    ])
    assert bench_micro.check_against(str(baseline), 0.7) == 1


def test_doctor_perf_section_and_baseline_drift():
    from ray_tpu import doctor
    for _ in range(50):
        perf.observe("task.execute", 10.0)
    collected = {"ts": time.time(), "errors": [],
                 "cluster": {"metrics": {"snapshots": {
                     "head": perf.families()}}}}
    loose = doctor._perf_reports(
        collected, baseline={"task.execute": {"p99_ms": 100.0}})
    assert loose["cluster"]["task.execute"]["count"] == 50
    assert loose["drift"] == []
    tight = doctor._perf_reports(
        collected, baseline={"task.execute": {"p99_ms": 1.0,
                                              "tolerance": 1.5}})
    assert [d["hist"] for d in tight["drift"]] == ["task.execute"]
    report = doctor.diagnose(
        collected, perf_baseline={"task.execute": {"p99_ms": 1.0}})
    assert not report["healthy"]
    assert report["perf"]["drift"]
    rendered = doctor.render_text(report)
    assert "PERF DRIFT" in rendered and "task.execute" in rendered


def test_top_straggler_rule():
    from ray_tpu.scripts.cli import _top_rows
    summ = {"count": 10.0, "mean_ms": 1.0, "p50_ms": 1.0,
            "p95_ms": 1.0, "p99_ms": 1.0}
    slow = dict(summ, p95_ms=50.0, p99_ms=60.0)
    payload = {"nodes": {"node:aa": {"task.execute": summ},
                         "node:bb": {"task.execute": summ},
                         "node:cc": {"task.execute": slow}}}
    flags = {(n, h): f for n, h, _s, f in _top_rows(payload)}
    assert flags[("node:cc", "task.execute")]
    assert not flags[("node:aa", "task.execute")]
    # two samples on the slow node is below the >=3 sample guard
    payload["nodes"]["node:cc"]["task.execute"] = dict(slow, count=2.0)
    flags = {(n, h): f for n, h, _s, f in _top_rows(payload)}
    assert not flags[("node:cc", "task.execute")]


# -- in-process hot-path wiring --------------------------------------------

def test_task_path_records_histograms():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def tiny():
            return 1

        assert ray_tpu.get([tiny.remote() for _ in range(20)]) == [1] * 20
        snap = perf.snapshot()["hists"]
        assert sum(snap["task.execute"]["counts"]) >= 20
        assert sum(snap["task.e2e"]["counts"]) >= 1
    finally:
        ray_tpu.shutdown()


# -- federation across real daemons (self-skip without the state service) ---

def test_cluster_top_json_straggler_and_profile():
    """Acceptance drill: a multi-daemon cluster with a chaos-injected
    50ms task delay on ONE node.  ``ray-tpu top --json`` must report
    per-node p50/p95/p99 with counts matching the workload, the slowed
    node must show a shifted p99 and carry the straggler flag, and
    ``/api/profile`` must federate sampler profiles from the daemons."""
    from ray_tpu.cluster_utils import ProcessCluster
    from ray_tpu.dashboard.head import DashboardHead
    from ray_tpu.scripts import cli
    _require_state_service()
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=0, num_cpus=2)
    per_node = 8
    try:
        c.add_daemon(num_cpus=2, resources={"n0": float(per_node)})
        c.add_daemon(num_cpus=2, resources={"n1": float(per_node)})
        c.add_daemon(num_cpus=2, resources={"n2": float(per_node)},
                     env={"RAY_TPU_CHAOS":
                          "1:task.execute@1+=delay(0.05)"})
        ray_tpu.init(address=c.address)

        refs = []
        for res in ("n0", "n1", "n2"):
            @ray_tpu.remote(resources={res: 1})
            def pinned():
                return 1

            refs += [pinned.remote() for _ in range(per_node)]
        assert ray_tpu.get(refs, timeout=120) == [1] * (3 * per_node)

        out = []
        real_print = print

        def fake_print(*a, **k):
            out.append(" ".join(str(x) for x in a))

        cli.print = fake_print
        try:
            cli.main(["top", "--address", c.address, "--json"])
        finally:
            cli.print = real_print
        payload = json.loads("\n".join(out))

        cluster = payload["cluster"]
        assert cluster["task.execute"]["count"] >= 3 * per_node
        assert "rpc.call" in cluster  # driver + daemons talk RPC
        node_rows = {node: per["task.execute"]
                     for node, per in payload["nodes"].items()
                     if "task.execute" in per}
        assert len(node_rows) == 3
        for node, s in node_rows.items():
            assert s["count"] >= per_node
            for key in ("p50_ms", "p95_ms", "p99_ms"):
                assert s[key] > 0
        slow = max(node_rows, key=lambda n: node_rows[n]["p95_ms"])
        assert node_rows[slow]["p99_ms"] >= 40.0  # the 50ms injection
        fast_p99 = [s["p99_ms"] for n, s in node_rows.items() if n != slow]
        assert all(node_rows[slow]["p99_ms"] >= 2 * p for p in fast_p99)
        assert {"node": slow, "name": "task.execute"} in \
            payload["stragglers"]

        head = DashboardHead(c.address)
        try:
            prof = head._profile()
            # this process is the head and, as the driver, a node as well:
            # its sampler is federated under both labels
            driver = ray_tpu._private.worker.global_worker().runtime
            mine = ("head", f"node:{driver.local_node.node_id.hex()[:8]}")
            daemon_hosts = [h for h in prof["hosts"] if h not in mine]
            assert len(daemon_hosts) == 3  # every daemon's sampler federated
            assert prof["merged"]["ticks"] > 0
            assert prof["collapsed"]
            assert prof["pprof"]["samples"]
        finally:
            head.stop()
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_fresh_histogram_build_does_not_self_deadlock():
    """bucket_bounds() runs inside PerfHistogram.__init__, which get()
    constructs while holding the registry lock — the bounds cache must
    use its own lock or the first observe after a reset() wedges."""
    perf.reset()                       # bounds cache cold
    done = threading.Event()

    def first_observe():
        perf.get("perf.selftest.fresh").observe(1.0)
        done.set()

    t = threading.Thread(target=first_observe, daemon=True)
    t.start()
    assert done.wait(5.0), "histogram construction deadlocked"
    assert perf.get("perf.selftest.fresh").count() == 1
