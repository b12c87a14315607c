"""A serving cell: ``serve.run`` of one replica, requests over HTTP through
``serve.start_http_proxy()``, the router and the replica's batcher, from a
child process that never imports JAX.

The system has no decode loop, so a reply *is* the first token: the token
with the largest logit at the prompt's last position, and that logit. The
deployment pads a batch to the smallest length bucket that holds it, runs
``transformer.backbone``, gathers each item's last real position and runs
``transformer.head`` on it. Every (batch bucket, length bucket) shape is
compiled when the replica starts.

A window the host froze in is measured again. The load generator watches
its own clock (``loadgen.Watcher``); where it notes a skip
(``loadgen.HOLD_S`` or more) between the start of the pre-roll and the
window's last reply, every process on the machine was held, the system under
test among them, and ``offer_load`` is called again on the same deployment, plan and
seed, ``ATTEMPTS`` times at most. The first window that did not freeze is
the run's, alone: its records, snapshots, counters and trace. A window in
which the program stalled while the generator's clock ran on time stands,
and one failed request fails the run.

After the kept window a few seeded prompts are sent once more through the
served path and their replies kept; then the deployment is shut down and the
replica's weights are freed; only then are the float32 reference's weights
made on the device and its logits compared with the replies, so the device
never holds both (4 bytes a parameter, not 6).
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness, loadgen, manifest, traffic

KERNEL = "tpu_custom_call"
CHILD_START_S = 2.0     # for the load generator to start and read its plan
ATTEMPTS = 3            # windows offered at most, the first one included

_LIVE: Dict[str, "LastToken"] = {}    # replicas are threads of this process


class LastToken:
    """The deployment: ``__call__`` takes a LIST of prompts (token ids) and
    returns for each the first token and its logit."""

    def __init__(self, name: str, config: Dict[str, Any],
                 dims: Dict[str, Any], model: Dict[str, Any],
                 batch_buckets: List[int], length_buckets: List[int],
                 seed: int, on_tpu: bool):
        import jax
        import jax.numpy as jnp

        import ray_tpu
        from ray_tpu.models import transformer

        self.length_buckets = sorted(length_buckets)
        cfg = manifest.adapter(config).program_config(
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
        # (start, end) of every __call__ on time.monotonic(): a stalled
        # host shows as a gap between batches that no arrival explains
        self.call_spans: List[Any] = []
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
            t_call = time.monotonic()
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
            self.call_spans.append((t_call, time.monotonic()))
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

    def failure(r: Dict[str, Any]) -> Dict[str, Any]:
        error = r.get("error") or (
            "malformed reply" if r.get("status") == 200 else "")
        return {**{k: r.get(k) for k in ("i", "len", "due", "sent", "done",
                                         "status")}, "error": str(error)}

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
            tokens=sum(r["len"] for r in good),
            last_reply_s=max([seconds] + [r["done"] for r in mine]))
    else:
        mine = [r for r in records if 0.0 <= r["done"] < seconds]
        good = [r for r in mine if ok(r)]
        tokens = sum(r["len"] for r in good)
        out.update(
            attempted=len(mine), failed=len(mine) - len(good),
            answered=len(good), tokens=tokens,
            metrics={"serve_tokens_per_s": tokens / seconds},
            lateness_p95_ms=0.0, last_reply_s=seconds)
    out["failed_records"] = [failure(r) for r in mine if not ok(r)]
    return out


def failure_notes(failed: List[Dict[str, Any]]) -> List[str]:
    """One line for each distinct cause among the failed requests (status
    and the error's text with its numbers struck out, so that two refusals
    that differ in an estimate are one cause): how many, the span of the
    instants they were due (seconds from the window's start) and one reply
    verbatim. Who refused is in the text: the router ("exceed their
    latency budget"), the replica ("queue deadline"), the proxy ("too many
    in-flight", "draining"), or for status 0 the connection."""
    causes: Dict[Any, List[Dict[str, Any]]] = {}
    for r in failed:
        key = (r["status"], re.sub(r"\d+(\.\d+)?", "#", r["error"]))
        causes.setdefault(key, []).append(r)
    notes = []
    for (status, _), rows in sorted(causes.items(),
                                    key=lambda kv: -len(kv[1])):
        due = [r["due"] for r in rows]
        slowest = max(r["done"] - r["sent"] for r in rows)
        notes.append(
            f"FAILED x{len(rows)}: status {status}, due {min(due):.3f}.."
            f"{max(due):.3f} s, answered within {slowest:.3f} s of being "
            f"sent, requests {[r['i'] for r in rows][:12]}: "
            f"{rows[0]['error']!r}")
    return notes


def call_gap_note(call_spans: List[Any], t0: float, seconds: float
                  ) -> Optional[str]:
    """The longest gap between the starts of consecutive batch calls that
    began inside the window, and the longest call: with Poisson arrivals a
    gap of more than a second between batches, with requests due inside
    it, is a host that stalled, and a call of seconds is a device or a
    transfer that did."""
    inside = [(a - t0, b - t0) for a, b in call_spans
              if 0.0 <= a - t0 < seconds]
    if len(inside) < 2:
        return None
    gap, at = max((b[0] - a[0], a[0]) for a, b in zip(inside, inside[1:]))
    longest, started = max((b - a, a) for a, b in inside)
    return (f"deployment.__call__ x{len(inside)} in the window: longest gap "
            f"between starts {gap:.3f} s (from {at:.3f} s), longest call "
            f"{longest:.3f} s (at {started:.3f} s)")


def offer_load(url: str, plan: Dict[str, Any], seed: int, vocab_size: int,
               seconds: float, timeout_s: float, snapshot):
    """Start the load generator's process, hold the window open and return
    ``(t0, what the generator printed ("records" and "holds"), (snapshot()
    at the window's start, at its end))``. The window is marked in the
    profiler's trace by a span of this thread."""
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
    return t0, json.loads(out[0]), (before, after)


def longest_hold(holds: List[List[float]], start: float, end: float
                 ) -> Optional[List[float]]:
    """The longest of the generator's holds (``[woke at, late by]``, in
    seconds from t0) any part of which lies in ``[start, end]``."""
    inside = [h for h in holds if h[0] - h[1] < end and h[0] > start]
    return max(inside, key=lambda h: h[1], default=None)


def kept_window(env: harness.Env, plan: Dict[str, Any], timeout_s: float,
                vocab_size: int, call_spans: List[Any], offer):
    """Call ``offer()`` (an ``offer_load`` of the same deployment, plan and
    seed: the replica holds no cache, so the same prompts are the same
    work) until a window did not freeze, ``ATTEMPTS`` times at most. A
    window froze when the generator noted a skip of its clock between the
    start of the pre-roll and the last reply the window counts.
    Returns the first window's start, then the kept window's start, load,
    reduction and snapshots, and a note for each window that froze. The
    kept window's trace is the only one in ``env.trace_dir``."""
    again: List[str] = []
    t_first = None
    for attempt in range(1, ATTEMPTS + 1):
        if env.trace and attempt > 1:
            shutil.rmtree(env.trace_dir, ignore_errors=True)
            os.makedirs(env.trace_dir)
        with harness.profiled(env):
            t0, load, snapshots = offer()
        t_first = t0 if t_first is None else t_first
        got = reduce_records(plan, load["records"], env.seconds, timeout_s,
                             vocab_size)
        hold = longest_hold(load["holds"], -plan["preroll_s"],
                            got["last_reply_s"])
        if hold is None:
            break
        gap = (call_gap_note(call_spans, t0, env.seconds)
               or "fewer than two batch calls")
        again.append(
            f"FROZEN window {attempt} of {ATTEMPTS}: the load generator's "
            f"own clock skipped {hold[1]:.3f} s (it woke at {hold[0]:.3f} s "
            f"of the window; {len(load['holds'])} skip(s) of "
            f"{loadgen.HOLD_S} s or more), so the host held every "
            f"process: {got['failed']} of {got['attempted']} failed there; "
            f"{gap}; "
            + ("measured again" if attempt < ATTEMPTS else
               "every window froze: this one is reported as it stands"))
    return t_first, t0, load, got, snapshots, again


def _sample_prompts(seed: int, lengths: List[int], per_length: int,
                    vocab_size: int) -> List[List[int]]:
    """The seeded prompts of the comparison, ``per_length`` of each length,
    with indices past any request's."""
    index = 10_000_000
    return [traffic.prompt_tokens(seed, index + i, length, vocab_size)
            for i, length in enumerate(n for n in lengths
                                       for _ in range(per_length))]


def _compare(replies: List[Dict[str, Any]], prompts: List[List[int]], adapter,
             dims: Dict[str, Any], seed: int, device) -> Dict[str, Any]:
    """What the served path replied to each seeded prompt against the
    reference's logits at its last position, from the same seeded
    parameters in float32. Called once the replica's own are freed: the
    reference's weights are made here."""
    import jax
    from ray_tpu.models import transformer

    cfg = adapter.program_config(dims, max(len(p) for p in prompts),
                                 {"dtype": "float32"})
    with jax.default_device(device):
        params = jax.jit(lambda k: transformer.init_params(k, cfg))(
            harness.prng_key(seed))
    ref_fn = jax.jit(lambda p, t: adapter.last_logits(p, t, dims))
    worst, rows = 0.0, []
    for length in sorted({len(p) for p in prompts}):
        mine = [i for i, p in enumerate(prompts) if len(p) == length]
        refs = np.asarray(ref_fn(params, jax.device_put(
            np.asarray([prompts[i] for i in mine], np.int32), device)))
        for i, ref in zip(mine, refs):
            got = replies[i]
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


def _free(name: str, replica: LastToken) -> None:
    """Drop the replica and delete its weights from the device."""
    import jax
    _LIVE.pop(name, None)
    params, replica.params = replica.params, None
    for leaf in jax.tree.leaves(params):
        leaf.delete()


def run(env: harness.Env) -> harness.Outcome:
    import jax

    import ray_tpu
    from ray_tpu import serve

    cell = env.cell
    adapter = manifest.adapter(cell.config)
    dims = adapter.dims(cell.config, "serve", cell.chips)
    logit_atol = adapter.TOLERANCES["logit_atol"]
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
        cell.name, cell.config, dims, cell.deploy.get("model", {}),
        list(opts["pad_batch_to"]), list(opts["length_buckets"]), env.seed,
        env.on_tpu), name=cell.name, route_prefix=opts["route"])
    url = serve.start_http_proxy() + opts["route"]
    serve_startup_s = time.monotonic() - t_serve
    replica = _LIVE[cell.name]
    harness.say(f"serve.run + proxy in {serve_startup_s:.1f} s; "
                f"{replica.shapes} shapes compiled at replica start")

    plan = traffic.request_plan(mix, env.seconds, env.seed)

    def snapshot():
        return (len(replica.call_spans), len(replica.traced),
                _replica_queue_wait(cell.name))

    def offer():
        return offer_load(url, plan, env.seed, dims["vocab_size"],
                          env.seconds, timeout_s, snapshot)

    t_first, t0, load, got, snapshots, again = kept_window(
        env, plan, timeout_s, dims["vocab_size"], replica.call_spans, offer)
    memory_peak = harness.memory_peak([replica.device], replica.temp_bytes)
    sample = cell.deploy["reference"]
    prompts = _sample_prompts(env.seed, list(sample["prompt_lengths"]),
                              int(sample["prompts_per_length"]),
                              dims["vocab_size"])
    replies = [_post(url, prompt, timeout_s) for prompt in prompts]
    compiled_after = len(replica.traced)
    serve.shutdown()
    device = replica.device
    _free(cell.name, replica)
    check = _compare(replies, prompts, adapter, dims, env.seed, device)

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
    if check["worst"] > logit_atol:
        faults.append(f"served logits off the reference by "
                      f"{check['worst']:.4f} (atol {logit_atol})")
    notes = [
        f"{plan['loop']} loop: {got['attempted']} requests, "
        f"{got['failed']} failed, {got['tokens']} prompt tokens answered; "
        f"generator lateness p95 {got['lateness_p95_ms']:.2f} ms, its "
        f"clock skipped {load['skip_max_s'] * 1e3:.1f} ms at most",
        f"deployment calls in the window: {calls1 - calls0}; replica "
        f"queue_wait samples {wait1['count'] - wait0['count']}",
        f"reference (float32): worst logit error {check['worst']:.4f} "
        f"over {len(check['rows'])} prompts of {sample['prompt_lengths']} tokens "
        f"(atol {logit_atol})"]
    if plan["loop"] == "open":
        notes.append(f"in flight at the middle of the window "
                     f"{got['backlog_mid']}, at its end "
                     f"{got['backlog_end']}")
    gaps = call_gap_note(replica.call_spans, t0, env.seconds)
    if gaps:
        notes.append(gaps)
    notes.extend(again)
    notes.extend(failure_notes(got["failed_records"]))
    notes.extend(f"FAULT: {f}" for f in faults)
    return harness.Outcome(
        correct=not faults, attempted=got["attempted"], failed=got["failed"],
        end_to_end=got["metrics"], t_first_measured=t_first,
        counters={**got["metrics"], "serve_startup_s": serve_startup_s,
                  "calls": calls1 - calls0, "answered": got["answered"],
                  "queue_wait_count": wait1["count"] - wait0["count"],
                  "queue_wait_sum_ms": wait1["sum_ms"] - wait0["sum_ms"],
                  "backlog_mid": got.get("backlog_mid"),
                  "backlog_end": got.get("backlog_end"),
                  "lateness_p95_ms": got["lateness_p95_ms"],
                  "window_s": env.seconds, "dims": dims, "devices": 1},
        memory_peak_bytes=memory_peak, notes=notes)
