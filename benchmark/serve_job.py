"""A serving cell: ``serve.run`` of one replica, requests over HTTP through
``serve.start_http_proxy()``, the router and the replica's batcher, from a
child process that never imports JAX.

The system has no decode loop, so a reply *is* the first token: the token
with the largest logit at the prompt's last position, and that logit. The
deployment pads a batch to the smallest length bucket that holds it, runs
``transformer.backbone``, gathers each item's last real position and runs
``transformer.head`` on it. Every (batch bucket, length bucket) shape is
compiled when the replica starts. After the window a few seeded prompts are
sent once more and compared with the plain float32 reference.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness, manifest, reference, traffic

KERNEL = "tpu_custom_call"
CHILD_START_S = 2.0     # for the load generator to start and read its plan

# The served forward holds weights and activations in bfloat16 (eps 2^-8);
# the reference is float32. Rounding drifts activations by about
# eps * sqrt(24 layers) = 1.9e-2; logits of seeded weights are about unit
# normal and the largest of 92,544 is near 4.5, so the served logit may be
# off by about 0.09, and two near-equal logits may swap places: the worst of
# 8 prompts read 0.07 to 0.12 over three seeds on the chip (PR 25). A
# forward in 8-bit floats (eps 2^-4) would be off by more than 1.
LOGIT_ATOL = 0.3

_LIVE: Dict[str, "LastToken"] = {}    # replicas are threads of this process


class LastToken:
    """The deployment: ``__call__`` takes a LIST of prompts (token ids) and
    returns for each the first token and its logit."""

    def __init__(self, name: str, dims: Dict[str, Any], model: Dict[str, Any],
                 batch_buckets: List[int], length_buckets: List[int],
                 seed: int, on_tpu: bool):
        import jax
        import jax.numpy as jnp

        import ray_tpu
        from ray_tpu.models import transformer

        self.length_buckets = sorted(length_buckets)
        cfg = harness.transformer_config(
            dims, self.length_buckets[-1], {**model, "remat": False})
        self.device = (ray_tpu.get_runtime_context().get_tpu_devices()[0]
                       if on_tpu else jax.devices()[0])
        dtype = cfg.dtype

        def init(key):
            return jax.tree.map(lambda p: p.astype(dtype),
                                transformer.init_params(key, cfg))

        with jax.default_device(self.device):
            self.params = jax.jit(init)(harness.prng_key(seed))
        self.traced: List[Any] = []

        def first_token(params, tokens, last):
            self.traced.append(tokens.shape)     # runs only while tracing
            x = transformer.backbone(params, tokens, cfg)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
            logits = transformer.head(params, x, cfg)[:, 0]
            return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

        self.first_token = jax.jit(first_token)
        self.calls = 0
        self.kernel_in_program: Optional[bool] = None
        self.temp_bytes = 0
        # every shape the batcher can hand over, before the first request
        for n in batch_buckets:
            for length in self.length_buckets:
                args = (self.params,
                        jnp.zeros((n, length), jnp.int32, device=self.device),
                        jnp.zeros((n,), jnp.int32, device=self.device))
                jax.block_until_ready(self.first_token(*args))
        if on_tpu:
            # the largest shape's program: is the kernel in it, and how
            # much the device holds for it while it runs
            compiled = self.first_token.lower(*args).compile()
            self.kernel_in_program = KERNEL in compiled.as_text()
            self.temp_bytes = harness.temp_bytes(compiled)
        self.shapes = len(self.traced)
        _LIVE[name] = self

    def __call__(self, items: List[List[int]]):
        import jax
        with harness.span("deployment.__call__"):
            self.calls += 1
            longest = max(len(p) for p in items)
            length = next((b for b in self.length_buckets if b >= longest),
                          None)
            if length is None:
                raise ValueError(f"a prompt of {longest} tokens is longer "
                                 f"than the last bucket")
            tokens = np.zeros((len(items), length), np.int32)
            for row, prompt in zip(tokens, items):
                row[:len(prompt)] = prompt
            last = np.array([len(p) - 1 for p in items], np.int32)
            token, logit = jax.device_get(self.first_token(
                self.params, jax.device_put(tokens, self.device),
                jax.device_put(last, self.device)))
        return [{"token": int(t), "logit": float(v)}
                for t, v in zip(token, logit)]


def _post(url: str, prompt: List[int], timeout_s: float) -> Dict[str, Any]:
    req = urllib.request.Request(
        url, data=json.dumps(prompt).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


def _replica_queue_wait(name: str) -> Dict[str, float]:
    """Count and summed milliseconds of the replica's queue-wait histogram
    (both exact; its quantiles carry bucket error and are not used)."""
    import ray_tpu
    from ray_tpu import serve
    info = ray_tpu.get(
        serve.api._get_controller().get_replica_handles.remote(name))
    count, sum_ms = 0, 0.0
    for replica in info["handles"]:
        perf = ray_tpu.get(replica.get_metrics.remote())["perf"]
        count += sum(perf["queue_wait"]["counts"])
        sum_ms += perf["queue_wait"]["sum_ms"]
    return {"count": count, "sum_ms": sum_ms}


def reduce_records(plan: Dict[str, Any], records: List[Dict[str, Any]],
                   seconds: float, timeout_s: float, vocab_size: int
                   ) -> Dict[str, Any]:
    """From the load generator's records to the cell's end-to-end numbers.
    Open loop: every request *due* inside the window counts, timed from the
    instant it was due; one that failed or was refused counts as the
    slowest. Closed loop: the real prompt tokens of the replies received
    inside the window, over the window."""

    def ok(r: Dict[str, Any]) -> bool:
        return (r.get("status") == 200
                and isinstance(r.get("token"), int)
                and 0 <= r["token"] < vocab_size
                and isinstance(r.get("logit"), float)
                and math.isfinite(r["logit"]))

    out: Dict[str, Any] = {"malformed": sum(
        1 for r in records if r.get("status") == 200 and not ok(r))}
    if plan["loop"] == "open":
        mine = [r for r in records if 0.0 <= r["due"] < seconds]
        good = [r for r in mine if ok(r)]
        latency = [(r["done"] - r["due"]) * 1e3 for r in good]
        worst = max(latency + [timeout_s * 1e3])
        latency += [worst] * (len(mine) - len(good))
        late = [(r["sent"] - r["due"]) * 1e3 for r in mine]
        out.update(
            attempted=len(mine), failed=len(mine) - len(good),
            answered=len(good),
            metrics={"ttft_p50_ms": traffic.percentile(latency, 50),
                     "ttft_p95_ms": traffic.percentile(latency, 95)},
            lateness_p95_ms=traffic.percentile(late, 95),
            backlog_mid=sum(1 for r in mine if r["due"] < seconds / 2
                            <= r["done"]),
            backlog_end=sum(1 for r in mine if r["done"] >= seconds),
            tokens=sum(r["len"] for r in good))
    else:
        mine = [r for r in records if 0.0 <= r["done"] < seconds]
        good = [r for r in mine if ok(r)]
        tokens = sum(r["len"] for r in good)
        out.update(
            attempted=len(mine), failed=len(mine) - len(good),
            answered=len(good), tokens=tokens,
            metrics={"serve_tokens_per_s": tokens / seconds},
            lateness_p95_ms=0.0)
    return out


def offer_load(url: str, plan: Dict[str, Any], seed: int, vocab_size: int,
               seconds: float, timeout_s: float, snapshot):
    """Start the load generator's process, hold the window open and return
    ``(t0, records, (snapshot() at the window's start, at its end))``. The
    window is marked in the profiler's trace by a span of this thread."""
    t0 = time.monotonic() + plan["preroll_s"] + CHILD_START_S
    t_end = t0 + seconds
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out: List[str] = []
    reader = threading.Thread(target=lambda: out.append(child.stdout.read()))
    reader.start()
    try:
        child.stdin.write(json.dumps({
            "url": url, "plan": plan, "seed": seed, "vocab_size": vocab_size,
            "t0": t0, "t_end": t_end, "timeout_s": timeout_s}))
        child.stdin.close()
        time.sleep(max(0.0, t0 - time.monotonic()))
        with harness.span(harness.WINDOW_SPAN):
            before = snapshot()
            time.sleep(max(0.0, t_end - time.monotonic()))
            after = snapshot()
        # the generator stops by itself once every reply is in
        child.wait(timeout=timeout_s + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        reader.join()
    if child.returncode != 0:
        raise RuntimeError(f"the load generator exited with "
                           f"{child.returncode}")
    return t0, json.loads(out[0])["records"], (before, after)


def _compare(url: str, dims: Dict[str, Any], seed: int, lengths: List[int],
             per_length: int, device, timeout_s: float) -> Dict[str, Any]:
    """Seeded prompts sent through the served path, each against the
    reference's logits at its last position, from the same seeded
    parameters in float32."""
    import jax
    from ray_tpu.models import transformer

    cfg = harness.transformer_config(dims, max(lengths),
                                     {"dtype": "float32"})
    with jax.default_device(device):
        params = jax.jit(lambda k: transformer.init_params(k, cfg))(
            harness.prng_key(seed))
    ref_fn = jax.jit(lambda p, t: reference.last_logits(p, t, dims))
    worst, rows, index = 0.0, [], 10_000_000   # past any request's index
    for length in lengths:
        prompts = [traffic.prompt_tokens(seed, index + i, length,
                                         dims["vocab_size"])
                   for i in range(per_length)]
        index += per_length
        refs = np.asarray(ref_fn(params, jax.device_put(
            np.asarray(prompts, np.int32), device)))
        for prompt, ref in zip(prompts, refs):
            got = _post(url, prompt, timeout_s)
            # the served token's logit agrees with the reference's at that
            # token, and that token is within tolerance of the reference's
            # best (two near-equal logits may swap places under bfloat16)
            err = max(abs(got["logit"] - float(ref[got["token"]])),
                      float(ref.max()) - float(ref[got["token"]]))
            worst = max(worst, err)
            rows.append({"len": length, "token": got["token"],
                         "logit": got["logit"], "ref_best": int(ref.argmax()),
                         "ref_logit": float(ref[got["token"]]), "err": err})
    return {"worst": worst, "rows": rows}


def run(env: harness.Env) -> harness.Outcome:
    import jax

    import ray_tpu
    from ray_tpu import serve

    cell = env.cell
    dims = manifest.model_dims(cell.config, "serve", cell.chips)
    mix, opts = cell.traffic, cell.deploy["deployment"]
    timeout_s = float(mix["timeout_s"])
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    deployment = serve.deployment(
        name=cell.name, max_batch_size=int(opts["max_batch_size"]),
        batch_wait_timeout_s=float(opts["batch_wait_timeout_s"]),
        pad_batch_to=tuple(opts["pad_batch_to"]),
        target_latency_ms=float(opts.get("target_latency_ms", 0.0)),
        ray_actor_options={"num_tpus": 1} if env.on_tpu else {})(LastToken)
    t_serve = time.monotonic()
    serve.start()
    serve.run(deployment.bind(
        cell.name, dims, cell.deploy.get("model", {}),
        list(opts["pad_batch_to"]), list(opts["length_buckets"]), env.seed,
        env.on_tpu), name=cell.name, route_prefix=opts["route"])
    url = serve.start_http_proxy() + opts["route"]
    serve_startup_s = time.monotonic() - t_serve
    replica = _LIVE[cell.name]
    harness.say(f"serve.run + proxy in {serve_startup_s:.1f} s; "
                f"{replica.shapes} shapes compiled at replica start")

    plan = traffic.request_plan(mix, env.seconds, env.seed)

    def snapshot():
        return (replica.calls, len(replica.traced),
                _replica_queue_wait(cell.name))

    with harness.profiled(env):
        t0, records, snapshots = offer_load(
            url, plan, env.seed, dims["vocab_size"], env.seconds, timeout_s,
            snapshot)
    memory_peak = harness.memory_peak([replica.device], replica.temp_bytes)
    got = reduce_records(plan, records, env.seconds, timeout_s,
                         dims["vocab_size"])
    sample = cell.deploy["reference"]
    check = _compare(url, dims, env.seed, list(sample["prompt_lengths"]),
                     int(sample["prompts_per_length"]), replica.device,
                     timeout_s)
    compiled_after = len(replica.traced)
    serve.shutdown()

    (calls0, traced0, wait0), (calls1, traced1, wait1) = snapshots
    faults = []
    if traced1 != traced0 or compiled_after != replica.shapes:
        faults.append(f"{compiled_after - replica.shapes} compilation(s) "
                      "after the replica's warm-up")
    if env.on_tpu and not replica.kernel_in_program:
        faults.append(f"no {KERNEL} in the compiled forward")
    if got["malformed"]:
        faults.append(f"{got['malformed']} malformed replies")
    if got["failed"]:
        faults.append(f"{got['failed']} of {got['attempted']} requests "
                      "failed or were refused")
    if not got["answered"]:
        faults.append("no request was answered inside the window")
    if check["worst"] > LOGIT_ATOL:
        faults.append(f"served logits off the reference by "
                      f"{check['worst']:.4f} (atol {LOGIT_ATOL})")
    notes = [
        f"{plan['loop']} loop: {got['attempted']} requests, "
        f"{got['failed']} failed, {got['tokens']} prompt tokens answered; "
        f"generator lateness p95 {got['lateness_p95_ms']:.2f} ms",
        f"deployment calls in the window: {calls1 - calls0}; replica "
        f"queue_wait samples {wait1['count'] - wait0['count']}",
        f"reference (float32): worst logit error {check['worst']:.4f} "
        f"over {len(check['rows'])} prompts of {sample['prompt_lengths']} tokens "
        f"(atol {LOGIT_ATOL})"]
    if plan["loop"] == "open":
        notes.append(f"in flight at the middle of the window "
                     f"{got['backlog_mid']}, at its end "
                     f"{got['backlog_end']}")
    notes.extend(f"FAULT: {f}" for f in faults)
    return harness.Outcome(
        correct=not faults, attempted=got["attempted"], failed=got["failed"],
        end_to_end=got["metrics"], t_first_measured=t0,
        counters={**got["metrics"], "serve_startup_s": serve_startup_s,
                  "calls": calls1 - calls0, "answered": got["answered"],
                  "queue_wait_count": wait1["count"] - wait0["count"],
                  "queue_wait_sum_ms": wait1["sum_ms"] - wait0["sum_ms"],
                  "backlog_mid": got.get("backlog_mid"),
                  "backlog_end": got.get("backlog_end"),
                  "lateness_p95_ms": got["lateness_p95_ms"],
                  "window_s": env.seconds, "dims": dims, "devices": 1},
        memory_peak_bytes=memory_peak, notes=notes)
