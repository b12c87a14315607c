"""Multi-host tensor plane tests: compiled collectives across daemon
PROCESSES (the reference's NCCL-group contract,
``nccl_collective_group.py:127`` + ``train/torch/config.py:54-96``), run
on CPU daemons with virtual devices + Gloo — the process-level analogue of
a multi-host TPU slice.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import ProcessCluster


@pytest.fixture()
def tp_cluster():
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=2, num_cpus=2, tp_cpu_devices=2)
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@ray_tpu.remote(num_cpus=2)  # fills a daemon: one rank per process
class Rank:
    def __init__(self):
        self.pid = os.getpid()

    def where(self):
        return self.pid

    def plane_info(self):
        import jax
        return {"pid": self.pid,
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
                "local": len(jax.local_devices()),
                "global": len(jax.devices())}

    def run(self, op, tensor, group_name, **kw):
        from ray_tpu import collective as col
        return np.asarray(getattr(col, op)(tensor, group_name=group_name,
                                           **kw))

    def p2p(self, group_name, peer, send_first):
        from ray_tpu import collective as col
        if send_first:
            col.send(np.arange(4.0), peer, group_name)
            return None
        return np.asarray(col.recv(peer, group_name))


def _spawn_plane(cluster, n=2, gname="tp-test"):
    from ray_tpu.collective import create_collective_group
    actors = [Rank.remote() for _ in range(n)]
    pids = ray_tpu.get([a.where.remote() for a in actors], timeout=60)
    daemon_pids = {d["proc"].pid for d in cluster.daemons}
    assert set(pids) <= daemon_pids and len(set(pids)) == n, \
        f"ranks must land on distinct daemons: {pids}"
    create_collective_group(actors, n, list(range(n)), backend="xla",
                            group_name=gname)
    return actors


def test_cross_process_allreduce(tp_cluster):
    """Two daemon processes allreduce through ONE compiled collective:
    jax.process_count() == 2 in each rank proves the plane spans OS
    processes, not threads."""
    actors = _spawn_plane(tp_cluster, gname="tp-ar")
    infos = ray_tpu.get([a.plane_info.remote() for a in actors], timeout=120)
    assert {i["process_index"] for i in infos} == {0, 1}
    assert all(i["process_count"] == 2 for i in infos)
    assert all(i["global"] == 2 * i["local"] for i in infos)
    assert len({i["pid"] for i in infos}) == 2

    refs = [a.run.remote("allreduce", np.arange(8.0) + 10 * r, "tp-ar")
            for r, a in enumerate(actors)]
    out = ray_tpu.get(refs, timeout=120)
    expected = (np.arange(8.0)) + (np.arange(8.0) + 10)
    for o in out:
        np.testing.assert_allclose(o, expected)


def test_cross_process_ops(tp_cluster):
    actors = _spawn_plane(tp_cluster, gname="tp-ops")
    # broadcast from rank 1
    refs = [a.run.remote("broadcast", np.full(4, float(r)), "tp-ops",
                         src_rank=1)
            for r, a in enumerate(actors)]
    for o in ray_tpu.get(refs, timeout=120):
        np.testing.assert_allclose(o, np.full(4, 1.0))
    # allgather
    refs = [a.run.remote("allgather", np.full(3, float(r)), "tp-ops")
            for r, a in enumerate(actors)]
    for o in ray_tpu.get(refs, timeout=120):
        np.testing.assert_allclose(o, np.stack([np.zeros(3), np.ones(3)]))
    # reducescatter: rank r gets chunk r of the sum
    base = np.arange(4.0)
    refs = [a.run.remote("reducescatter", base + r, "tp-ops")
            for r, a in enumerate(actors)]
    out = ray_tpu.get(refs, timeout=120)
    full = (base) + (base + 1)
    np.testing.assert_allclose(out[0], full[:2])
    np.testing.assert_allclose(out[1], full[2:])


def test_cross_process_p2p(tp_cluster):
    actors = _spawn_plane(tp_cluster, gname="tp-p2p")
    s = actors[0].p2p.remote("tp-p2p", 1, True)
    r = actors[1].p2p.remote("tp-p2p", 0, False)
    got = ray_tpu.get([s, r], timeout=60)[1]
    np.testing.assert_allclose(got, np.arange(4.0))


@ray_tpu.remote(num_cpus=2)  # fills a daemon: one rank per process
class BulkRank:
    def send_big(self, group_name, peer, n):
        import numpy as _np

        from ray_tpu import collective as col
        col.send(_np.arange(n, dtype=_np.float32).reshape(-1, 1024),
                 peer, group_name)
        return True

    def recv_big(self, group_name, peer, n):
        import numpy as _np

        from ray_tpu import collective as col
        out = _np.asarray(col.recv(peer, group_name))
        assert out.shape == (n // 1024, 1024)
        assert float(out[-1, -1]) == float(n - 1)
        # bulk transfers must NOT transit the state-KV p2p namespace
        import ray_tpu as _rt
        state = _rt._private.worker.global_worker().runtime.state
        leftovers = [k for k in state.kv_keys(namespace=b"tplane-p2p")
                     if b">" in k]
        return leftovers


def test_cross_process_p2p_bulk_lane(tp_cluster):
    """A multi-MB tensor rides the raw-lane P2P_DATA path (NCCL-send
    role): correct bytes, nothing parked in the control-plane KV."""
    from ray_tpu.collective import create_collective_group
    actors = [BulkRank.remote() for _ in range(2)]
    create_collective_group(actors, 2, [0, 1], backend="xla",
                            group_name="tp-bulk")
    n = 2 * 1024 * 1024  # 8 MB of float32
    s = actors[0].send_big.remote("tp-bulk", 1, n)
    r = actors[1].recv_big.remote("tp-bulk", 0, n)
    sent, leftovers = ray_tpu.get([s, r], timeout=120)
    assert sent is True
    assert leftovers == []


# ---------------------------------------------------------------- trainer

def _make_dp_loop():
    """Returns the train loop as a CLOSURE: daemons cannot import this test
    module, so the loop must cloudpickle by value (same constraint as the
    reference — worker nodes need importable code or by-value functions)."""

    def _dp_loop(config):
        # Least-squares DP training over the session's (possibly
        # process-spanning) mesh; gradients allreduce inside the step.
        import jax
        import jax.numpy as jnp
        import numpy as np
        import os
        import time
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ray_tpu.air.checkpoint import Checkpoint
        from ray_tpu.train import session

        mesh = session.get_mesh()
        rank = session.get_world_rank()
        start, w = 0, np.zeros(3, np.float32)
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            d = ckpt.to_dict()
            start, w = d["step"], d["w"]

        rng = np.random.RandomState(rank)
        w_true = np.array([1.0, -2.0, 0.5], np.float32)
        X_local = rng.randn(8, 3).astype(np.float32)
        y_local = X_local @ w_true

        w_dev = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P()))
        X = session.shard_batch(X_local)
        y = session.shard_batch(y_local)

        @jax.jit
        def step(w, X, y):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean((X @ w - y) ** 2))(w)
            return w - 0.2 * g, loss

        for s in range(start, config["steps"]):
            w_dev, loss = step(w_dev, X, y)
            if s == config.get("kill_step") and rank == 0 and ckpt is None:
                # the test kills this daemon now, at this step
                with open(config["kill_file"], "w") as f:
                    f.write(str(os.getpid()))
            if config.get("step_sleep"):
                time.sleep(config["step_sleep"])
            ck = None
            if rank == 0:
                ck = Checkpoint.from_dict(
                    {"step": s + 1, "w": np.asarray(w_dev)})
            session.report({"loss": float(loss), "step": s,
                            "procs": jax.process_count(),
                            "global_devices": len(jax.devices())},
                           checkpoint=ck)

    return _dp_loop


def test_trainer_dp_across_daemons(tp_cluster):
    """JaxTrainer DP step spanning two daemon PROCESSES: the session mesh
    covers both processes' devices and the gradient psum is compiled
    across them."""
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    trainer = JaxTrainer(
        _make_dp_loop(), train_loop_config={"steps": 15},
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 2},
            placement_strategy="STRICT_SPREAD"),
        collective_backend="xla")
    res = trainer.fit()
    assert res.error is None, res.error
    assert res.metrics_history, "no results streamed"
    assert all(m["procs"] == 2 for m in res.metrics_history)
    assert all(m["global_devices"] == 4 for m in res.metrics_history)
    losses = [m["loss"] for m in res.metrics_history if m["step"] in (0, 14)]
    assert min(losses) < max(losses), "loss did not move"
    final = res.checkpoint.to_dict()
    np.testing.assert_allclose(final["w"], [1.0, -2.0, 0.5], atol=0.35)


@pytest.fixture()
def tp_cluster4():
    ray_tpu.shutdown()
    c = ProcessCluster(num_daemons=4, num_cpus=2, tp_cpu_devices=2)
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_trainer_resumes_across_daemon_kill(tp_cluster4, tmp_path):
    """SIGKILL one worker's daemon mid-training: the JAX coordination
    service fails the whole plane (its peers abort — device-owner
    processes are expendable), and the trainer restarts the group on the
    spare daemons FROM THE CHECKPOINT (reference contract:
    backend_executor.py:461-531 elastic restart). The kill is tied to a
    step, not to a clock: rank 0 names its daemon when it has taken step 2
    of the first attempt, and the test kills that daemon then."""
    import threading
    from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    killed = threading.Event()
    kill_file = tmp_path / "kill"

    trainer = JaxTrainer(
        _make_dp_loop(),
        train_loop_config={"steps": 8, "step_sleep": 0.4, "kill_step": 2,
                           "kill_file": str(kill_file)},
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 2},
            placement_strategy="STRICT_SPREAD"),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)),
        collective_backend="xla")

    def kill_at_the_step():
        deadline = time.monotonic() + 120
        while not kill_file.exists() or not kill_file.read_text():
            if time.monotonic() > deadline:
                return
            time.sleep(0.05)
        pid = int(kill_file.read_text())
        for i, d in enumerate(tp_cluster4.daemons):
            if d["proc"].pid == pid:
                tp_cluster4.kill_daemon(i)
                killed.set()

    t = threading.Thread(target=kill_at_the_step, daemon=True)
    t.start()
    res = trainer.fit()
    assert killed.is_set(), "chaos never fired"
    assert res.error is None, f"trainer did not recover: {res.error}"
    steps_seen = sorted({m["step"] for m in res.metrics_history})
    assert steps_seen[-1] == 7, steps_seen
    final = res.checkpoint.to_dict()
    assert final["step"] == 8
