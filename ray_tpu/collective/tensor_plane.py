"""Multi-host tensor plane: state-KV-brokered ``jax.distributed`` rendezvous.

This is the piece that makes compiled collectives span daemon *processes*
(and, on real hardware, TPU hosts). The reference bootstraps its NCCL
communicators by parking an ``NCCLUniqueID`` in a named store actor that
every rank reads
(``python/ray/util/collective/collective_group/nccl_collective_group.py:54-95``)
and its torch trainers run ``dist.init_process_group`` with a rank-0
address (``python/ray/train/torch/config.py:54-96``). The TPU-native
equivalent is JAX's multi-controller runtime: rank 0 opens the coordination
service, every process calls ``jax.distributed.initialize``, and from then
on ``jax.devices()`` is the GLOBAL device set — collectives are compiled
into programs and ride ICI/DCN, not this control plane.

What the state-service KV brokers here, keyed by (group, epoch):
- the coordinator address (rank 0 binds a free port and publishes it),
- the world size (so mismatched joins fail loudly),
- a liveness epoch: after a failure the group re-forms under epoch+1, and
  stale processes shut their old runtime down before rejoining.

On CPU test clusters the same path runs over Gloo
(``jax_cpu_collectives_implementation``) with ``jax_num_cpu_devices``
virtual devices per process — the driver-validated dryrun analogue of a
multi-host TPU slice.
"""

from __future__ import annotations

import logging
import socket
import threading
import time
from typing import Optional

logger = logging.getLogger("ray_tpu")

KV_NS = b"tplane"

_lock = threading.Lock()
_active_plane: Optional[dict] = None  # {"group", "epoch", "world", "rank"}

_epoch_gauge = None


def _mark(event: str, group: str, epoch: int, **args) -> None:
    """Epoch lifecycle breadcrumb: a trace instant (when tracing is on)
    plus the ``tplane_epoch`` gauge, so a doctor correlating collective
    stalls can see exactly when a plane formed, re-formed, or went away
    (epoch -1).  Re-forms used to vanish silently."""
    global _epoch_gauge
    try:
        import ray_tpu.observability as _obs
        _obs.instant(f"tplane:{event}", cat="comms", group=group,
                     epoch=epoch, **args)
        if _epoch_gauge is None:
            from ray_tpu.observability.metric_names import TPLANE_EPOCH_GAUGE
            from ray_tpu.util import metrics
            # raylint: allow(data-race) idempotent lazy gauge init; the metrics registry dedups by name
            _epoch_gauge = metrics.Gauge(
                TPLANE_EPOCH_GAUGE,
                "active tensor-plane epoch per group (-1 once shut down)",
                ("group",))
        # Bounded cardinality: tag is the collective group name, a small
        # application-chosen set, never a per-task or per-object id.
        _epoch_gauge.set(float(epoch), tags={"group": group})
    except Exception:
        logger.debug("tplane lifecycle mark failed", exc_info=True)


def _runtime_and_kv(runtime=None):
    """The distributed runtime + its state-service KV."""
    if runtime is None:
        from ray_tpu._private import worker as _worker
        runtime = _worker.try_global_runtime()
    state = getattr(runtime, "state", None)
    if state is None:
        raise RuntimeError(
            "tensor plane needs a cluster (ray_tpu.init(address=...) or a "
            "host daemon); no state service in this process")
    return runtime, state


def _free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def current_plane() -> Optional[dict]:
    with _lock:
        return dict(_active_plane) if _active_plane else None


def init_tensor_plane(group_name: str, world_size: int, rank: int,
                      *, epoch: int = 0, num_cpu_devices: Optional[int] = None,
                      timeout_s: float = 60.0, runtime=None) -> dict:
    """Join the process-spanning tensor plane for ``group_name``/``epoch``.

    Must be called at most once per (group, epoch) per process; one process
    is one rank (the device-owner stance: libtpu is single-owner, so a TPU
    host contributes exactly one process). Re-joining under a newer epoch
    tears the previous JAX distributed runtime down first — that is how a
    group re-forms after a member died.
    """
    import jax

    runtime, state = _runtime_and_kv(runtime)
    key = f"{group_name}/{epoch}".encode()

    with _lock:
        global _active_plane
        if _active_plane is not None:
            if (_active_plane["group"] == group_name
                    and _active_plane["epoch"] == epoch):
                if _active_plane["rank"] != rank:
                    raise RuntimeError(
                        f"process already joined {group_name}@{epoch} as "
                        f"rank {_active_plane['rank']}, not {rank}")
                return dict(_active_plane)
            # Older (or different) plane: leave it before rejoining.
            _mark("reform", group_name, epoch,
                  old_group=_active_plane["group"],
                  old_epoch=_active_plane["epoch"])
            try:
                jax.distributed.shutdown()
            except Exception:
                logger.debug("jax.distributed.shutdown failed", exc_info=True)
            _active_plane = None

    # CPU test clusters: virtual devices + gloo collectives. Must land
    # before the backend initializes; harmless no-ops otherwise. Daemons
    # advertise their device count via RAY_TPU_TP_CPU_DEVICES (set by
    # ProcessCluster) so worker actors need no explicit argument.
    import os
    if num_cpu_devices is None:
        env_n = os.environ.get("RAY_TPU_TP_CPU_DEVICES")
        if env_n:
            num_cpu_devices = int(env_n)
    if num_cpu_devices is not None:
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            if "xla_force_host_platform_device_count" not in os.environ.get(
                    "XLA_FLAGS", ""):
                jax.config.update("jax_num_cpu_devices",
                                  int(num_cpu_devices))
        except Exception:
            logger.warning("could not configure cpu collectives",
                           exc_info=True)

    if rank == 0:
        # Advertise the host peers can actually reach: the address this
        # daemon registered with the cluster (loopback only on
        # single-machine test clusters).
        addr = getattr(runtime, "address", "") or "127.0.0.1:0"
        host = addr.rsplit(":", 1)[0] or "127.0.0.1"
        coord = f"{host}:{_free_port(host)}"
        state.kv_put(key, f"{coord}|{world_size}".encode(),
                     overwrite=True, namespace=KV_NS)
    else:
        deadline = time.monotonic() + timeout_s
        coord = None
        while time.monotonic() < deadline:
            raw = state.kv_get(key, namespace=KV_NS)
            if raw:
                coord_s, world_s = raw.decode().split("|")
                if int(world_s) != world_size:
                    raise ValueError(
                        f"group {group_name}@{epoch} exists with world_size "
                        f"{world_s}, joined with {world_size}")
                coord = coord_s
                break
            time.sleep(0.02)
        if coord is None:
            raise TimeoutError(
                f"rank {rank}: no coordinator for {group_name}@{epoch} "
                f"within {timeout_s}s")

    # JAX's preemption service takes the process's SIGTERM for itself (its
    # notifier replaces the handler), and nothing here reads its sync point:
    # a daemon that had joined a plane then outlived SIGTERM until it was
    # killed. The daemon's own watcher (host_daemon) handles a preemption.
    jax.config.update("jax_enable_preemption_service", False)
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=world_size, process_id=rank,
                               initialization_timeout=int(timeout_s))
    plane = {"group": group_name, "epoch": epoch, "world": world_size,
             "rank": rank, "coordinator": coord,
             "local_devices": len(jax.local_devices()),
             "global_devices": len(jax.devices())}
    with _lock:
        _active_plane = plane
    _mark("join", group_name, epoch, rank=rank, world=world_size,
          devices=plane["global_devices"])
    logger.info("tensor plane %s@%d up: rank %d/%d, %d global devices",
                group_name, epoch, rank, world_size,
                plane["global_devices"])
    return dict(plane)


def shutdown_tensor_plane():
    import jax
    with _lock:
        global _active_plane
        if _active_plane is None:
            return
        gone = _active_plane
        try:
            jax.distributed.shutdown()
        except Exception:
            logger.debug("jax.distributed.shutdown failed", exc_info=True)
        _active_plane = None
    _mark("shutdown", gone["group"], -1, last_epoch=gone["epoch"])
