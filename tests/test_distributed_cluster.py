"""Multi-process cluster tests: real state-service + host-daemon processes,
tasks/actors/objects crossing OS process boundaries, chaos recovery.

The process-level analogue of the reference's multi-raylet Cluster tests
(python/ray/tests/test_multi_node*.py, test_chaos.py): every daemon is a
separate process speaking the wire protocol; killing one is a real SIGKILL.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import ProcessCluster


@pytest.fixture()
def cluster():
    ray_tpu.shutdown()  # earlier module-scoped runtimes must not leak in
    c = ProcessCluster(num_daemons=2, num_cpus=2)
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_tasks_run_across_daemon_processes(cluster):
    @ray_tpu.remote
    def where(x):
        return os.getpid(), x * 2

    refs = [where.remote(i) for i in range(40)]
    results = ray_tpu.get(refs, timeout=60)
    pids = {pid for pid, _ in results}
    values = [v for _, v in results]
    assert values == [2 * i for i in range(40)]
    assert os.getpid() not in pids, "driver must not execute tasks"
    assert len(pids) == 2, f"expected both daemons used, got {pids}"


def test_task_chaining_across_processes(cluster):
    @ray_tpu.remote
    def a():
        return np.arange(1000)

    @ray_tpu.remote
    def b(arr):
        return int(arr.sum())

    assert ray_tpu.get(b.remote(a.remote()), timeout=60) == 499500


def test_large_object_cross_process_fetch(cluster):
    """A >inline-threshold result stays in the executing daemon's store and
    is pulled chunked by the driver."""
    @ray_tpu.remote
    def big():
        return np.ones((1500, 1500), dtype=np.float64)  # ~18 MB

    arr = ray_tpu.get(big.remote(), timeout=120)
    assert arr.shape == (1500, 1500)
    assert float(arr.sum()) == 1500 * 1500


def test_put_ref_used_by_remote_task(cluster):
    data = np.arange(200000)  # ~1.6MB: fetched from the driver by the daemon
    ref = ray_tpu.put(data)

    @ray_tpu.remote
    def total(arr):
        return int(arr.sum())

    assert ray_tpu.get(total.remote(ref), timeout=60) == int(data.sum())
    # Nested in a container: resolved at execution via the borrow protocol.

    @ray_tpu.remote
    def total_nested(d):
        return int(ray_tpu.get(d["ref"]).sum())

    assert ray_tpu.get(total_nested.remote({"ref": ref}),
                       timeout=60) == int(data.sum())


def test_actor_on_daemon_with_ordered_calls(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0
            self.pid = os.getpid()

        def inc(self):
            self.n += 1
            return self.n

        def where(self):
            return self.pid

    c = Counter.remote()
    results = ray_tpu.get([c.inc.remote() for _ in range(20)], timeout=60)
    assert results == list(range(1, 21)), "actor calls must stay ordered"
    assert ray_tpu.get(c.where.remote(), timeout=30) != os.getpid()


def test_named_actor_resolution(cluster):
    @ray_tpu.remote
    class Registry:
        def __init__(self):
            self.data = {}

        def set(self, k, v):
            self.data[k] = v
            return True

        def get(self, k):
            return self.data.get(k)

    reg = Registry.options(name="global-registry").remote()
    assert ray_tpu.get(reg.set.remote("k", 42), timeout=60)
    handle = ray_tpu.get_actor("global-registry")
    assert ray_tpu.get(handle.get.remote("k"), timeout=30) == 42


def test_daemon_death_task_retry(cluster):
    """SIGKILL the daemon running a task: it must retry on the survivor."""
    @ray_tpu.remote(max_retries=3)
    def slow(i):
        time.sleep(1.5)
        return os.getpid(), i

    refs = [slow.remote(i) for i in range(8)]
    time.sleep(0.5)  # let pushes land on both daemons
    cluster.kill_daemon(0)
    results = ray_tpu.get(refs, timeout=120)
    survivor_pid = cluster.daemons[1]["proc"].pid
    assert all(pid == survivor_pid for pid, _ in results)
    assert sorted(i for _, i in results) == list(range(8))


def test_daemon_death_actor_restart(cluster):
    @ray_tpu.remote(max_restarts=2)
    class Stateful:
        def __init__(self):
            self.pid = os.getpid()
            self.n = 0

        def bump(self):
            self.n += 1
            return self.pid, self.n

    s = Stateful.remote()
    pid1, n = ray_tpu.get(s.bump.remote(), timeout=60)
    victim = next(i for i, d in enumerate(cluster.daemons)
                  if d["proc"].pid == pid1)
    cluster.kill_daemon(victim)
    deadline = time.monotonic() + 90
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2, _ = ray_tpu.get(s.bump.remote(), timeout=10)
            break
        except ray_tpu.exceptions.RayTpuError:
            time.sleep(0.5)  # raylint: allow(bare-retry) deadline-bounded test poll
    assert pid2 is not None and pid2 != pid1, "actor must restart elsewhere"


def test_owner_daemon_dies_lineage_reconstructs(cluster):
    """Large task result lives only in daemon A's store; kill A; get() must
    re-execute the producing task on the survivor (ObjectRecoveryManager
    role, object_recovery_manager.h:90)."""
    @ray_tpu.remote(max_retries=2)
    def produce():
        return os.getpid(), np.full((1200, 1200), 7.0)  # ~11 MB, not inlined

    ref = produce.remote()
    pid, arr = ray_tpu.get(ref, timeout=120)
    victim = next(i for i, d in enumerate(cluster.daemons)
                  if d["proc"].pid == pid)
    # Drop our cached local copy so the only copy dies with the daemon.
    rt = ray_tpu._private.worker.global_worker().runtime
    from ray_tpu._private.ids import ObjectID
    rt.local_node.store.free(ref.id())
    rt._location_hints.pop(ref.id(), None)
    del arr
    cluster.kill_daemon(victim)
    time.sleep(4)  # heartbeat timeout -> NODE_DEAD -> directory cleanup
    pid2, arr2 = ray_tpu.get(ref, timeout=120)
    assert pid2 != pid
    assert float(arr2[0, 0]) == 7.0


def test_wait_across_processes(cluster):
    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 2

    f, s = fast.remote(), slow.remote()
    ready, pending = ray_tpu.wait([f, s], num_returns=1, timeout=30)
    assert ready == [f] and pending == [s]


def test_spillback_on_infeasible_local(cluster):
    """A request larger than one daemon's capacity but fitting another is
    served; an impossible request errors cleanly."""
    addr = cluster.add_daemon(num_cpus=8)

    @ray_tpu.remote(num_cpus=6)
    def heavy():
        return os.getpid()

    pid = ray_tpu.get(heavy.remote(), timeout=60)
    assert pid == cluster.daemons[-1]["proc"].pid

    @ray_tpu.remote(num_cpus=64)
    def impossible():
        return 0

    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        ray_tpu.get(impossible.remote(), timeout=60)


# -- host-shared object plane ----------------------------------------------

def test_same_host_fetch_goes_through_arena(cluster):
    """Daemons + driver on one host share the shm arena: a large fetch
    lands the payload in the arena (fd-passed memfd pages), not in a TCP
    stream. (plasma store.h role)"""
    rt = ray_tpu._private.worker.global_worker().runtime
    if rt.host_arena is None:
        pytest.skip("native arena unavailable in this environment")

    @ray_tpu.remote
    def produce():
        return np.full((700, 700), 3.25)  # ~3.9 MB

    before = rt.host_arena.stats()[2]
    val = ray_tpu.get(produce.remote(), timeout=60)
    assert float(val[0, 0]) == 3.25
    used, cap, count = rt.host_arena.stats()
    assert count >= before + 1, "payload should be cached in the arena"
    assert used > 3_000_000
    # zero-copy decode: the array is a read-only view over the shared
    # arena pages (protocol-5 out-of-band buffers), not a pickled copy
    assert not val.flags.owndata
    assert not val.flags.writeable


def test_arena_survives_repeat_fetches_and_eviction(cluster):
    rt = ray_tpu._private.worker.global_worker().runtime
    if rt.host_arena is None:
        pytest.skip("native arena unavailable")

    @ray_tpu.remote
    def make(i):
        return np.full((256, 256), float(i))

    refs = [make.remote(i) for i in range(6)]
    for i, r in enumerate(refs):
        v = ray_tpu.get(r, timeout=60)
        assert float(v[0, 0]) == float(i)
    # re-fetch: second consumer path hits the existing arena entries
    for i, r in enumerate(refs):
        rt.local_node.store.free(r.id())
        rt._location_hints.pop(r.id(), None)
        v = ray_tpu.get(r, timeout=60)
        assert float(v[0, 0]) == float(i)


def test_a_cluster_whose_daemons_joined_a_tensor_plane_is_down_in_five_seconds():
    """``ProcessCluster.shutdown`` on two daemons that hold a tensor plane
    (``jax.distributed.initialize`` in each): both leave on SIGTERM, through
    their own handler and with exit code 0, and the state service after
    them, within 5 s and with no process left. JAX's preemption service took
    the signal for itself once a plane was up, and each daemon then waited
    out ``shutdown``'s 10 s and was killed."""
    from ray_tpu.collective import create_collective_group

    @ray_tpu.remote(num_cpus=2)  # fills a daemon: one rank per process
    class Rank:
        def sum(self, group_name):
            from ray_tpu import collective as col
            return np.asarray(col.allreduce(np.arange(4.0),
                                            group_name=group_name))

    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=2, num_cpus=2, tp_cpu_devices=2)
    try:
        ray_tpu.init(address=c.address)
        ranks = [Rank.remote() for _ in range(2)]
        create_collective_group(ranks, 2, [0, 1], backend="xla",
                                group_name="down")
        for out in ray_tpu.get([r.sum.remote("down") for r in ranks],
                               timeout=120):
            np.testing.assert_allclose(out, 2 * np.arange(4.0))
    finally:
        ray_tpu.shutdown()
        began = time.monotonic()
        c.shutdown()
        took = time.monotonic() - began
    assert [d["proc"].poll() for d in c.daemons] == [0, 0]
    assert c.state_proc.poll() is not None
    assert took < 5.0, f"shutdown took {took:.1f} s"


def test_push_path_streams_object_to_peer():
    """With the arena off, large task args are proactively pushed to the
    executing daemon with windowed backpressure (push_manager.h role)."""
    ray_tpu.shutdown()
    os.environ["RAY_TPU_ARENA_ENABLED"] = "0"
    c = ProcessCluster(num_daemons=2, num_cpus=2)
    try:
        ray_tpu.init(address=c.address,
                     _system_config={"arena_enabled": False,
                                     "object_push_threshold_bytes": 4096})
        rt = ray_tpu._private.worker.global_worker().runtime
        assert rt.host_arena is None

        big = ray_tpu.put(np.full((600, 600), 1.5))  # ~2.9 MB driver-local

        # 1) deterministic: push directly to a chosen daemon (no pull race)
        target = c.daemons[1]["address"]
        rt._push_mgr.maybe_push(target, big.id(), 4096)
        deadline = time.monotonic() + 30
        addrs = []
        while time.monotonic() < deadline:
            rep = rt.state.get_locations(big.id().binary())
            addrs = list(rep.addresses)
            if target in addrs:
                break
            time.sleep(0.2)
        assert target in addrs, addrs

        # 2) end-to-end: a dependent task resolves the arg (push or pull)
        before = rt._push_mgr.pushes_initiated

        @ray_tpu.remote
        def consume(arr):
            return float(arr[0, 0]), os.getpid()

        v, pid = ray_tpu.get(consume.remote(big), timeout=60)
        assert v == 1.5
        # the task-push trigger must have initiated a NEW push (beyond the
        # direct one above) toward the executing daemon
        assert rt._push_mgr.pushes_initiated > before
    finally:
        ray_tpu.shutdown()
        c.shutdown()
        os.environ.pop("RAY_TPU_ARENA_ENABLED", None)
        from ray_tpu._private.config import _config
        _config.set("arena_enabled", True)
        _config.set("object_push_threshold_bytes", 256 * 1024)


def test_daemon_admission_backpressure_liveness():
    """A daemon with a tiny admission queue spills back instead of
    absorbing unbounded work — and the submitter's retry machinery still
    completes everything (liveness under backpressure)."""
    ray_tpu.shutdown()
    os.environ["RAY_TPU_DAEMON_ADMISSION_QUEUE_LIMIT"] = "4"
    c = ProcessCluster(num_daemons=2, num_cpus=2)
    try:
        ray_tpu.init(address=c.address)

        @ray_tpu.remote
        def slowish(i):
            time.sleep(0.05)
            return i

        refs = [slowish.remote(i) for i in range(60)]
        out = ray_tpu.get(refs, timeout=120)
        assert out == list(range(60))
    finally:
        ray_tpu.shutdown()
        c.shutdown()
        os.environ.pop("RAY_TPU_DAEMON_ADMISSION_QUEUE_LIMIT", None)


def test_arena_owner_death_degrades_to_tcp(cluster):
    """SIGKILL the arena owner (first daemon): same-host transfers must
    degrade to the TCP plane and the cluster keeps serving objects."""
    rt = ray_tpu._private.worker.global_worker().runtime
    if rt.host_arena is None:
        pytest.skip("native arena unavailable")

    @ray_tpu.remote(max_retries=2)
    def produce(i):
        return np.full((300, 300), float(i))

    assert float(ray_tpu.get(produce.remote(1), timeout=60)[0, 0]) == 1.0
    cluster.kill_daemon(0)  # daemon 0 started first: owns the arena
    time.sleep(4)           # NODE_DEAD
    out = ray_tpu.get([produce.remote(i) for i in range(2, 6)], timeout=120)
    assert [float(v[0, 0]) for v in out] == [2.0, 3.0, 4.0, 5.0]


def test_state_service_restart_cluster_survives(tmp_path):
    """GCS fault tolerance: SIGKILL the state service mid-run and restart
    it on the same port (journal-recovered). Clients reconnect, daemons
    re-register via the unrecognized-heartbeat path, and tasks + actors
    keep working — the cluster must not wedge."""
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=2, num_cpus=2,
                       data_dir=str(tmp_path / "gcs"))
    try:
        ray_tpu.init(address=c.address)

        @ray_tpu.remote
        class Keeper:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        k = Keeper.remote()
        assert ray_tpu.get(k.bump.remote(), timeout=60) == 1

        c.restart_state_service()

        # daemons re-register on their next unrecognized heartbeat; the
        # driver's client reconnects on its next call
        @ray_tpu.remote
        def f(x):
            return x + 1

        from ray_tpu._private.rpc import RpcConnectionError
        deadline = time.monotonic() + 60
        out = None
        while time.monotonic() < deadline:
            try:
                out = ray_tpu.get([f.remote(i) for i in range(4)],
                                  timeout=20)
                break
            except (ray_tpu.exceptions.RayTpuError, TimeoutError,
                    RpcConnectionError, OSError):
                # the reconnection window surfaces several shapes
                time.sleep(0.5)  # raylint: allow(bare-retry) deadline-bounded test poll
        assert out == [1, 2, 3, 4]
        # the actor (state preserved in its daemon) keeps serving
        assert ray_tpu.get(k.bump.remote(), timeout=60) == 2
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_autoscaler_scales_up_process_cluster():
    """The autoscaler drives a REAL multi-process cluster: an infeasible
    task becomes unmet demand, the provider spawns a daemon process, and
    the task runs there (cluster-level scale-up end to end)."""
    from ray_tpu.autoscaler.autoscaler import (AutoscalerConfig,
                                               StandardAutoscaler)
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=1, num_cpus=2)
    try:
        ray_tpu.init(address=c.address)
        rt = ray_tpu._private.worker.global_worker().runtime
        provider = c.node_provider({"big": {"CPU": 8}})
        scaler = StandardAutoscaler(
            AutoscalerConfig(min_workers=0, max_workers=2,
                             idle_timeout_s=1.0,
                             node_types={"big": {"CPU": 8}}),
            provider, runtime=rt)

        @ray_tpu.remote(num_cpus=6)
        def heavy():
            return os.getpid()

        ref = heavy.remote()   # infeasible on the 2-CPU daemon
        deadline = time.monotonic() + 60
        launched = 0
        while time.monotonic() < deadline and not launched:
            launched = scaler.update()["launched"]
            time.sleep(0.3)
        assert launched == 1, "autoscaler never saw the unmet demand"
        pid = ray_tpu.get(ref, timeout=90)
        assert pid == c.daemons[-1]["proc"].pid  # ran on the new daemon

        # scale DOWN: the big daemon goes idle; past idle_timeout_s the
        # autoscaler terminates it (runtime_node_id resolution path)
        deadline = time.monotonic() + 60
        terminated = 0
        while time.monotonic() < deadline and not terminated:
            terminated = scaler.update()["terminated"]
            time.sleep(0.3)
        assert terminated == 1, "idle daemon never terminated"
        assert provider.non_terminated_nodes() == []
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_serve_replicas_across_daemon_processes(cluster):
    """Serve on a REAL multi-process cluster: the controller and replicas
    are actors on daemon processes; serve.run blocks until ready so the
    first request cannot race replica placement."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def who(req):
        return {"pid": os.getpid()}

    try:
        h = serve.run(who.bind(), name="who")
        pids = {h.remote(None).result(timeout=30)["pid"]
                for _ in range(12)}
        daemon_pids = {d["proc"].pid for d in cluster.daemons}
        # replicas live in daemon processes (pack placement may co-locate
        # them on one daemon, so >= 1 distinct pid)
        assert pids and pids <= daemon_pids, (pids, daemon_pids)
    finally:
        serve.shutdown()


def test_task_push_batching_mode(cluster):
    """task_push_batching=True routes pushes through TaskBatchMsg frames
    with per-task reply seqs: results, errors, and follow-up work all
    behave exactly as unbatched pushes."""
    from ray_tpu._private.config import _config
    _config.set("task_push_batching", True)
    try:
        @ray_tpu.remote(num_cpus=0.01)
        def double(x):
            return x * 2

        @ray_tpu.remote(num_cpus=0.01)
        def boom():
            raise ValueError("batched boom")

        assert ray_tpu.get([double.remote(i) for i in range(200)],
                           timeout=60) == [i * 2 for i in range(200)]
        with pytest.raises(Exception):
            ray_tpu.get(boom.remote(), timeout=30)
        assert ray_tpu.get(double.remote(21), timeout=30) == 42
    finally:
        _config.set("task_push_batching", False)


def test_heartbeat_resource_delta_broadcast(cluster):
    """ray_syncer role: CHANGED availability is pushed to subscribers as
    a NODE_RESOURCES event at heartbeat latency (no ListNodes polling);
    unchanged heartbeats publish nothing for that node."""
    import threading

    from ray_tpu._private.state_client import StateClient
    from ray_tpu.protocol import pb

    rt = ray_tpu._private.worker.global_worker().runtime
    events = []
    got_change = threading.Event()

    def on_event(ev):
        if ev.kind == "NODE_RESOURCES":
            info = pb.NodeInfo()
            info.ParseFromString(ev.payload)
            events.append(dict(info.available.amounts))
            got_change.set()

    sub = StateClient(rt.state_addr)
    sub.subscribe(["nodes"], on_event)
    try:
        @ray_tpu.remote(num_cpus=1)
        def hold():
            import time as _t
            _t.sleep(2.5)
            return 1

        ref = hold.remote()
        # capacity drop (and later recovery) must arrive as pushes
        assert got_change.wait(timeout=15), "no NODE_RESOURCES delta"
        assert ray_tpu.get(ref, timeout=30) == 1
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if len(events) >= 2:
                break
            time.sleep(0.2)
        assert len(events) >= 2, events  # drop + recovery
    finally:
        sub.close()
