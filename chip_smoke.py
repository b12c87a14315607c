"""Chip smoke: the main path once on the TPU, through the normal entry points.

``python chip_smoke.py`` (one chip) runs three phases, each a child process,
one after another, because a chip belongs to one process at a time:

- *train*:   ``JaxTrainer.fit`` -> ``session.get_mesh`` -> ``make_lm_train_step``
  fed by a ``ray_tpu.data`` shard, ``session.report`` every step, one
  checkpoint through the engine; then the same steps under plain attention.
- *serve*:   ``serve.run`` of a class deployment around ``jax.jit`` of
  ``transformer.apply`` with batch buckets (2, 4, 8); requests by handle and
  by HTTP, each compared with a direct plain-attention forward.
- *cluster*: ``python -m ray_tpu.scripts.cluster start --head`` (C++ state
  service built here, a host daemon that detects its chip), a driver that
  attaches and stays off jax, one ``num_tpus=1`` task on the daemon's device.

``python chip_smoke.py --multichip`` (four chips) runs only the sharded
train step: one worker holding four chips, on ``data=4`` and on
``data=2 x tensor=2``, kernel on, against plain attention on the same mesh.

Width is ``WIDTH`` below (the widest transformer the repo runs); weights and
tokens come from ``--seed``. This process never starts a jax backend. Any
phase that fails, or any device that is not a TPU, makes the exit code
non-zero. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or to
``.jax_cache`` beside this file; every child and the daemon share it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))

# bench.py's transformer section: the widest transformer the repo runs.
WIDTH = dict(vocab_size=32000, d_model=1024, n_layers=12, n_heads=16,
             max_seq_len=1024)
BATCH = 8            # sequences of max_seq_len tokens
TRAIN_STEPS, MULTICHIP_STEPS = 5, 3
BUCKETS = (2, 4, 8)

# Tolerances between the Pallas kernel and plain attention, fixed before any
# run from the dtype: activations are bfloat16 (eps 2^-8 = 3.9e-3) and the
# two attentions round differently in each of 12 layers, so activations
# drift by about eps*sqrt(12) = 1.4e-2. The loss is a mean over 8,192
# tokens, where that drift mostly cancels; a gradient norm does not average
# it away; the largest of 32,000 logits is about 4.5, so 1.4e-2 of it is
# 0.06.
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 5e-2
LOGIT_ATOL = 0.1

KERNEL = "tpu_custom_call"
PHASE_TIMEOUT_S = {"train": 600, "serve": 300, "cluster": 240,
                   "multichip": 900}


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def set_compile_cache(environ) -> str:
    """The cache directory the children will use: the environment's when it
    names one, else one fixed path inside the checkout (the path is part of
    the cache key, so it must not move)."""
    return environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))


def _device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * abs(b)


# --------------------------------------------------------------------------- #
# train (one chip) and multichip (four): one loop, run by JaxTrainer
# --------------------------------------------------------------------------- #


def _train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``. For each mesh in ``config["meshes"]``
    (None = the mesh the session hands out): ``steps`` steps with the kernel,
    reported one by one, then the same steps from the same parameters on the
    same batches under plain attention."""
    import dataclasses

    import jax
    import numpy as np
    import optax

    from ray_tpu.air import Checkpoint
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import make_lm_train_step, session

    cfg, steps = config["cfg"], config["steps"]
    session_mesh = session.get_mesh()
    if session_mesh is None:
        raise SmokeFailure("session.get_mesh() returned None")
    shard = session.get_dataset_shard("train")
    batches = shard.iter_batches(batch_size=BATCH, batch_format="numpy")
    key = jax.random.PRNGKey(config["seed"])

    def run(run_cfg, mesh, tokens_for_step, report):
        init_fn, step_fn, shard_batch = make_lm_train_step(
            run_cfg, mesh, optimizer=optax.adamw(3e-4, weight_decay=0.01))
        state = init_fn(key)
        rows, tokens = [], None
        for i in range(steps):
            tokens = shard_batch(tokens_for_step(i))
            t0 = time.perf_counter()
            state, metrics = step_fn(state, tokens)
            row = {"step": i, "loss": float(metrics["loss"]),
                   "grad_norm": float(metrics["grad_norm"]),
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            if report:
                last = config["checkpoint"] and i == steps - 1
                session.report(
                    dict(row, mesh=dict(mesh.shape)),
                    checkpoint=Checkpoint.from_dict(
                        {"step": i, "params": state[0]}) if last else None)
        return rows, state, tokens, step_fn.__wrapped__   # the jitted step

    for axes in config["meshes"]:
        mesh = session_mesh if axes is None else build_mesh(
            MeshConfig(**axes), list(session_mesh.devices.flat))
        seen: List[Any] = []

        def next_batch(i):
            # a tensor column comes back as an object array of rows
            seen.append(np.stack(next(batches)["tokens"]).astype(np.int32))
            return seen[i]

        rows, state, tokens, jitted = run(cfg, mesh, next_batch, report=True)
        params = state[0]
        text = jitted.lower(state, tokens).compile().as_text()
        stats = [d.memory_stats() for d in mesh.devices.flat]
        summary = {
            "mesh": dict(mesh.shape),
            "rows": rows,
            "compiles": jitted._cache_size(),
            "kernel_in_program": KERNEL in text,
            "all_reduce_in_program": "all-reduce" in text,
            "param_devices": len({s.device for s in
                                  params["blocks"]["attn"]["wq"]
                                  .addressable_shards}),
            "param_shard_shape": list(params["blocks"]["attn"]["wq"]
                                      .addressable_shards[0].data.shape),
            "batch_devices": len({s.device for s in
                                  tokens.addressable_shards}),
            "batch_shard_shape": list(tokens.addressable_shards[0]
                                      .data.shape),
            "bytes_in_use": [s and s.get("bytes_in_use") for s in stats],
            "peak_bytes": [s and s.get("peak_bytes_in_use") for s in stats],
        }
        del state, params
        ref_rows, ref_state, _, _ = run(
            dataclasses.replace(cfg, use_flash=False), mesh,
            lambda i: seen[i], report=False)
        del ref_state
        summary["ref_rows"] = ref_rows
        session.report({"summary": summary})


def _fit(cfg, meshes, steps: int, seed: int, tpus_per_worker: int,
         storage: Optional[str], phase: str):
    """Drive ``_train_loop`` through ``JaxTrainer.fit`` and judge what it
    reported. Returns the phase's result and the last checkpoint; raises
    SmokeFailure."""
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    if not ray_tpu.is_initialized():
        ray_tpu.init()
    rng = np.random.default_rng(seed)
    n_rows = BATCH * steps * len(meshes)
    tokens = rng.integers(0, cfg.vocab_size,
                          (n_rows, cfg.max_seq_len + 1), np.int32)
    dataset = rd.from_items([{"tokens": row} for row in tokens],
                            parallelism=len(meshes))
    t0 = time.perf_counter()
    result = JaxTrainer(
        _train_loop,
        train_loop_config={"cfg": cfg, "steps": steps, "seed": seed,
                           "meshes": meshes,
                           "checkpoint": storage is not None},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"TPU": tpus_per_worker}),
        run_config=RunConfig(name="chip_smoke", storage_path=storage),
        datasets={"train": dataset}).fit()
    fit_s = time.perf_counter() - t0
    if result.error is not None:
        raise SmokeFailure(f"JaxTrainer.fit failed: {result.error!r}")
    history = result.metrics_history
    summaries = [m["summary"] for m in history if "summary" in m]
    _check(len(summaries) == len(meshes),
           f"{len(summaries)} mesh summaries for {len(meshes)} meshes")
    reported = [m for m in history if "loss" in m]
    _check(len(reported) == steps * len(meshes),
           f"session.report streamed {len(reported)} steps, expected "
           f"{steps * len(meshes)}")

    for s in summaries:
        _say(phase, f"mesh {s['mesh']}: step 0 took "
             f"{s['rows'][0]['seconds']:.2f} s (compile included), later "
             f"steps {[round(r['seconds'], 3) for r in s['rows'][1:]]} s")
        _say(phase, "  kernel loss/grad_norm " + str(
            [(round(r["loss"], 4), round(r["grad_norm"], 4))
             for r in s["rows"]]))
        _say(phase, "  plain  loss/grad_norm " + str(
            [(round(r["loss"], 4), round(r["grad_norm"], 4))
             for r in s["ref_rows"]]))
        _say(phase, f"  compiles {s['compiles']}, kernel in program "
             f"{s['kernel_in_program']}, all-reduce in program "
             f"{s['all_reduce_in_program']}; wq shard "
             f"{s['param_shard_shape']} on {s['param_devices']} device(s), "
             f"batch shard {s['batch_shard_shape']} on "
             f"{s['batch_devices']}; peak bytes {s['peak_bytes']}")
        for row, ref in zip(s["rows"], s["ref_rows"]):
            _check(np.isfinite(row["loss"]) and np.isfinite(row["grad_norm"]),
                   f"non-finite step {row}")
            _check(_close(row["loss"], ref["loss"], LOSS_RTOL),
                   f"step {row['step']} loss {row['loss']} vs plain "
                   f"{ref['loss']} beyond rtol {LOSS_RTOL}")
            _check(_close(row["grad_norm"], ref["grad_norm"],
                          GRAD_NORM_RTOL),
                   f"step {row['step']} grad norm {row['grad_norm']} vs "
                   f"plain {ref['grad_norm']} beyond rtol {GRAD_NORM_RTOL}")
        _check(s["compiles"] == 1,
               f"the step compiled {s['compiles']} times, not once")
        n_devices = math.prod(s["mesh"].values())
        _check(s["param_devices"] == s["batch_devices"] == n_devices,
               f"parameters on {s['param_devices']} and batch on "
               f"{s['batch_devices']} device(s) of a mesh of {n_devices}")
    _say(phase, f"tolerance: loss rtol {LOSS_RTOL}, grad-norm rtol "
         f"{GRAD_NORM_RTOL} (bfloat16); fit() took {fit_s:.1f} s")
    # the worker is a thread of this process: its devices are ours
    return {"device": _device_info(), "summaries": summaries}, \
        result.checkpoint


def phase_train(cfg, steps: int = TRAIN_STEPS, seed: int = 0
                ) -> Dict[str, Any]:
    import numpy as np

    from ray_tpu.air import Checkpoint
    from ray_tpu.checkpoint import list_manifest_names

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as storage:
        out, live = _fit(cfg, [None], steps, seed, 1, storage, "train")
        root = os.path.join(storage, "chip_smoke", "checkpoints")
        names = list_manifest_names(root)
        _check(len(names) == 1,
               f"the engine committed {len(names)} manifests, expected 1")
        t0 = time.perf_counter()
        restored = Checkpoint.from_manifest(root).to_dict()
        live = live.to_dict()
        _check(restored["step"] == steps - 1,
               f"restored step {restored['step']}")
        for name in ("embed", "ln_f"):
            _check(np.array_equal(np.asarray(restored["params"][name]),
                                  np.asarray(live["params"][name])),
                   f"restored params[{name!r}] differ from the live ones")
        _say("train", f"checkpoint {names[0]} restored and compared in "
             f"{time.perf_counter() - t0:.1f} s")
    return out


def phase_multichip(cfg, n_chips: int = 4, steps: int = MULTICHIP_STEPS,
                    seed: int = 0) -> Dict[str, Any]:
    meshes = [None, {"data": n_chips // 2, "tensor": 2}]
    out, _ = _fit(cfg, meshes, steps, seed, n_chips, None, "multichip")
    first, second = out["summaries"]
    _check(first["mesh"]["data"] == n_chips,
           f"the session's mesh is {first['mesh']}, expected data={n_chips}")
    _check(second["mesh"]["data"] == n_chips // 2
           and second["mesh"]["tensor"] == 2, f"second mesh {second['mesh']}")
    _check(second["param_shard_shape"][2] * 2 == cfg.n_heads,
           f"wq shard {second['param_shard_shape']} is not split over "
           f"tensor=2")
    for s in out["summaries"]:
        _check(s["all_reduce_in_program"],
               f"no gradient all-reduce in the program on {s['mesh']}")
    return out


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #


def phase_serve(cfg, seed: int = 0) -> Dict[str, Any]:
    import dataclasses
    import urllib.request

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import transformer

    def last_position(run_cfg):
        def fwd(params, tokens):
            return transformer.apply(params, tokens, run_cfg)[:, -1, :]
        return fwd

    @serve.deployment(max_batch_size=8, batch_wait_timeout_s=0.05,
                      pad_batch_to=BUCKETS,
                      ray_actor_options={"num_tpus": 1})
    class LM:
        def __init__(self, seed: int):
            self.device = ray_tpu.get_runtime_context().get_tpu_devices()[0]
            self.params = jax.device_put(
                transformer.init_params(jax.random.PRNGKey(seed), cfg),
                self.device)
            self.traced: List[Any] = []
            fwd = last_position(cfg)

            def top(params, tokens):
                self.traced.append(tokens.shape)  # runs only while tracing
                logits = fwd(params, tokens)
                return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

            self.top = jax.jit(top)
            # Compile every bucket before the first request, as a server
            # does at start-up: a compilation inside a request would age
            # the queue behind it past serve_queue_deadline_ms.
            for n in BUCKETS:
                jax.block_until_ready(self.top(self.params, jnp.zeros(
                    (n, cfg.max_seq_len), jnp.int32, device=self.device)))

        def __call__(self, items):
            tokens = jax.device_put(np.asarray(items, np.int32), self.device)
            token, logit = jax.device_get(self.top(self.params, tokens))
            return [{"token": int(t), "logit": float(v),
                     "batch": len(items), "traces": len(self.traced),
                     "platform": self.device.platform}
                    for t, v in zip(token, logit)]

    if not ray_tpu.is_initialized():
        ray_tpu.init()
    rng = np.random.default_rng(seed)
    bursts = (1, 3, 6)
    n_http = 3
    requests = rng.integers(0, cfg.vocab_size,
                            (sum(bursts) + n_http, cfg.max_seq_len), np.int32)
    answers: List[Any] = [None] * len(requests)
    serve.start()
    try:
        t0 = time.perf_counter()
        handle = serve.run(LM.bind(seed), name="lm", route_prefix="/lm")
        _say("serve", f"serve.run returned in {time.perf_counter() - t0:.1f}"
             " s (parameters made on the device, one compilation for each "
             f"bucket of {BUCKETS})")

        def by_handle(i):
            try:
                answers[i] = handle.remote(requests[i].tolist()).result(
                    timeout=200)
            except Exception as e:  # noqa: BLE001 - judged below, by index
                answers[i] = e

        i = 0
        for n in bursts:
            threads = [threading.Thread(target=by_handle, args=(j,))
                       for j in range(i, i + n)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=210)
            _say("serve", f"burst of {n} by handle: "
                 f"{time.perf_counter() - t0:.2f} s")
            i += n
        base = serve.start_http_proxy()
        for j in range(i, i + n_http):
            t0 = time.perf_counter()
            req = urllib.request.Request(
                f"{base}/lm", data=json.dumps(requests[j].tolist()).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=200) as resp:
                answers[j] = json.loads(resp.read())
            _say("serve", f"request by HTTP: {time.perf_counter() - t0:.2f} s")
    finally:
        serve.shutdown()

    for j, a in enumerate(answers):
        _check(isinstance(a, dict), f"request {j} returned {a!r}")
    # The direct call: same parameters (same seed), plain attention.
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    plain_cfg = dataclasses.replace(cfg, use_flash=False)
    plain = jax.jit(last_position(plain_cfg))  # raylint: allow(jit-stability) called once in a run
    ref = np.asarray(plain(params, jnp.asarray(requests)))
    worst = 0.0
    for j, a in enumerate(answers):
        _check(a["batch"] in BUCKETS,
               f"request {j} ran in a batch of {a['batch']}, not a bucket")
        _check(np.isfinite(a["logit"]), f"request {j}: logit {a['logit']}")
        # The served token's logit agrees with the reference's at that
        # token, and is within tolerance of the reference's best (two
        # near-equal logits may swap places under bfloat16).
        err = max(abs(a["logit"] - float(ref[j, a["token"]])),
                  float(ref[j].max()) - float(ref[j, a["token"]]))
        worst = max(worst, err)
        _check(err <= LOGIT_ATOL,
               f"request {j}: token {a['token']} logit {a['logit']} vs "
               f"plain {float(ref[j, a['token']])} (best "
               f"{float(ref[j].max())}) beyond atol {LOGIT_ATOL}")
    traces = max(a["traces"] for a in answers)
    batches = sorted({a["batch"] for a in answers})
    _check(traces <= len(BUCKETS),
           f"{traces} compilations for {len(BUCKETS)} buckets")
    _say("serve", f"{len(answers)} requests ({sum(bursts)} by handle, "
         f"{n_http} by HTTP) in batches of {batches}; {traces} compilations "
         f"for buckets {BUCKETS}; worst logit error {worst:.4f} "
         f"(atol {LOGIT_ATOL})")
    platforms = {a["platform"] for a in answers}
    _check(platforms == {jax.devices()[0].platform},
           f"replica ran on {platforms}")
    return {"device": _device_info(), "traces": traces, "batches": batches,
            "worst_logit_err": worst}


# --------------------------------------------------------------------------- #
# cluster
# --------------------------------------------------------------------------- #


def _cluster_cli(*args: str, timeout: float) -> None:
    subprocess.run([sys.executable, "-m", "ray_tpu.scripts.cluster", *args],
                   check=True, timeout=timeout, cwd=ROOT)


def stop_cluster(run_dir: str) -> None:
    if os.path.exists(os.path.join(run_dir, "supervisor.pid")):
        _cluster_cli("stop", "--run-dir", run_dir, timeout=60)


def phase_cluster(run_dir: str, seed: int = 0) -> Dict[str, Any]:
    """The README's start-up. This driver must stay off jax: the daemon owns
    the chip."""
    from ray_tpu._native.build import build_state_service

    t0 = time.perf_counter()
    build_state_service()
    _say("cluster", "state service built (or found built) in "
         f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    # no --num-tpus: the daemon detects its chip
    _cluster_cli("start", "--head", "--run-dir", run_dir, timeout=200)
    try:
        _say("cluster", f"head up in {time.perf_counter() - t0:.1f} s")
        with open(os.path.join(run_dir, "address")) as f:
            address = f.read().strip()
        with open(os.path.join(run_dir, "token")) as f:
            token = f.read().strip()

        import ray_tpu
        ray_tpu.init(address=address, auth_token=token)
        try:
            resources = ray_tpu.cluster_resources()
            _say("cluster", f"cluster_resources() = {resources}")
            _check(resources.get("TPU") == 1,
                   f"the daemon advertises TPU={resources.get('TPU')}")

            @ray_tpu.remote(num_tpus=1)
            def matmul(seed: int, n: int = 1024):
                import jax
                import jax.numpy as jnp
                import numpy as np
                dev = ray_tpu.get_runtime_context().get_tpu_devices()[0]
                ka, kb = jax.random.split(jax.random.PRNGKey(seed))
                a = jax.device_put(
                    jax.random.normal(ka, (n, n), jnp.bfloat16), dev)
                b = jax.device_put(
                    jax.random.normal(kb, (n, n), jnp.bfloat16), dev)
                matmul_jit = jax.jit(jnp.matmul)  # raylint: allow(jit-stability) called once in a run
                out = matmul_jit(a, b)
                want = (np.asarray(a, np.float32) @ np.asarray(b, np.float32))
                err = float(np.abs(np.asarray(out, np.float32) - want).max()
                            / np.abs(want).max())
                return {"platform": dev.platform, "kind": dev.device_kind,
                        "on": str(out.devices()), "rel_err": err}

            t0 = time.perf_counter()
            got = ray_tpu.get(matmul.remote(seed), timeout=200)
            _say("cluster", f"task returned {got} in "
                 f"{time.perf_counter() - t0:.1f} s")
            _check(got["rel_err"] <= 2 ** -7,
                   f"bf16 matmul off by {got['rel_err']} of the largest "
                   "entry (tolerance 2^-7)")
            jax_mod = sys.modules.get("jax")
            _check(jax_mod is None
                   or not jax_mod._src.xla_bridge.backends_are_initialized(),
                   "the driver started a jax backend")
        finally:
            ray_tpu.shutdown()
    finally:
        stop_cluster(run_dir)
    return {"device": {"platform": got["platform"], "kind": got["kind"],
                       "count": int(resources["TPU"])}}


# --------------------------------------------------------------------------- #
# the parent: children one after another, then the verdict
# --------------------------------------------------------------------------- #


def _run_child(phase: str, seed: int, run_dir: str) -> Dict[str, Any]:
    """Run one phase in a child; its last stdout line is its JSON result."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(seed), "--run-dir", run_dir]
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT,
                            start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(PHASE_TIMEOUT_S[phase], kill)
    timer.start()
    last = ""
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                print(line, flush=True)
                last = line
        rc = proc.wait()
    finally:
        timer.cancel()
        kill()   # whatever the child left in its process group
    seconds = time.perf_counter() - t0
    try:
        result = json.loads(last)
    except ValueError:
        result = {}
    if not isinstance(result, dict) or result.get("phase") != phase:
        result = {"phase": phase, "ok": False,
                  "error": f"no result line (exit code {rc})"}
    if rc != 0:
        result["ok"] = False
        result.setdefault("error", f"exit code {rc}")
    result["seconds"] = round(seconds, 1)
    return result


def judge_on_chip(out: Dict[str, Any]) -> None:
    """What only a chip run can show, over a phase's result: the device is
    a TPU, the Pallas kernel (not interpret mode, not the reference) is in
    every compiled step, and every device of the mesh holds bytes."""
    _check(out["device"]["platform"] == "tpu",
           f"the device is {out['device']}, not a TPU")
    for s in out.get("summaries", ()):
        _check(s["kernel_in_program"],
               f"no {KERNEL} in the compiled step on {s['mesh']}: the "
               "Pallas kernel is not in the program")
        _check(all(b and b > 0 for b in s["bytes_in_use"]),
               f"bytes_in_use {s['bytes_in_use']} on {s['mesh']}")


def _child_main(phase: str, seed: int, run_dir: str) -> int:
    """One phase in this process; prints its JSON result as the last line."""
    result: Dict[str, Any] = {"phase": phase, "ok": False}
    try:
        if phase == "cluster":
            out = phase_cluster(run_dir, seed)
        else:
            import jax.numpy as jnp

            from ray_tpu.models.transformer import TransformerConfig
            result["device"] = _device_info()
            _check(result["device"]["platform"] == "tpu",
                   f"jax found {result['device']}, not a TPU")
            cfg = TransformerConfig(dtype=jnp.bfloat16, use_flash=True,
                                    **WIDTH)
            out = {"train": phase_train, "serve": phase_serve,
                   "multichip": phase_multichip}[phase](cfg, seed=seed)
        result["device"] = out["device"]
        judge_on_chip(out)
        result["ok"] = True
    except Exception as e:  # noqa: BLE001 - a phase's failure is its result
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the sharded train step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(PHASE_TIMEOUT_S),
                    help=argparse.SUPPRESS)    # set by the parent
    ap.add_argument("--run-dir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return _child_main(args.phase, args.seed, args.run_dir)

    cache = set_compile_cache(os.environ)
    print(f"[smoke] compile cache: {cache}", flush=True)
    phases = ["multichip"] if args.multichip else ["train", "serve",
                                                   "cluster"]
    want_count = 4 if args.multichip else 1
    run_dir = tempfile.mkdtemp(prefix="chip_smoke_cluster_")
    results = []
    try:
        for phase in phases:
            results.append(_run_child(phase, args.seed, run_dir))
            print(f"[smoke] {phase}: "
                  f"{'ok' if results[-1]['ok'] else 'FAILED'} in "
                  f"{results[-1]['seconds']} s", flush=True)
            if not results[-1]["ok"]:
                break
    finally:
        stop_cluster(run_dir)
        shutil.rmtree(run_dir, ignore_errors=True)

    failed = [f"{r['phase']}: {r.get('error')}" for r in results
              if not r["ok"]]
    devices = [r.get("device") for r in results]
    if not failed and any(d != devices[0] for d in devices):
        failed.append(f"the phases disagree on the device: {devices}")
    if not failed and (devices[0]["platform"] != "tpu"
                       or devices[0]["count"] != want_count):
        failed.append(f"need {want_count} TPU chip(s), found {devices[0]}")
    if failed:
        for f in failed:
            print(f"[smoke] {f}", file=sys.stderr, flush=True)
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
