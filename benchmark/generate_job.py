"""A generating cell: ``serve.run`` of one replica whose deployment asks for
the generation engine (``serve.deployment(generation_slots=S)``), requests
over HTTP through ``serve.start_http_proxy()``, the router, the replica and
the engine, from a child process that never imports JAX
(``benchmark/generate_loadgen.py``).

A request is ``{"prompt": [...], "max_new_tokens": n}`` and its reply the
``n`` greedy tokens with their logits. The deployment's class is
``ray_tpu.models.generation.TransformerGenerator`` over the configuration's
program: the weights, ``S`` slots of state with room for ``cache_len``
positions, a prompt prefilled alone at its length bucket. Every shape is
compiled when the replica starts.

The cell's number, ``serve_tokens_per_s``, is **the prompt tokens plus the
generated tokens of the replies received inside the window, per second**: a
reply counts whole at the instant it arrives (there is no streaming), whenever
its request was sent.

A window the host froze in is measured again, as ``serve_job`` does it and
with its helpers (``longest_hold``, ``ATTEMPTS``).

After the kept window three seeded prompts (``reference.prompt_lengths``) are
sent *together* with ``reference.max_new_tokens`` (the traffic's longest
answer), and with them one more request of the plan for every other slot, as
long, so that the three prefill at three buckets and decode among occupied
slots for as many steps as the timed traffic's longest answer takes; the
replica keeps each one's recurrent state as its last step leaves it. Then the
deployment is shut down and its weights and state are freed; only then does the
adapter's streamed float32 reference run one full forward a prompt, over the
prompt followed by the served path's own tokens. Two numbers decide
``correct``: at each generated position the served logit must agree with the
reference's logit of that token, and that token lie within tolerance of the
reference's best (``serve_job._compare``'s two terms, at every position of
every answer: ``logit_atol``); and the served state of the Mamba layers must
agree with the reference's after the same tokens (``state_error``:
``state_rtol``).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, manifest, serve_job, traffic

KERNEL = serve_job.KERNEL
LOADGEN = "benchmark.generate_loadgen"

_LIVE: Dict[str, Any] = {}     # replicas are threads of this process


def _generator_class():
    """The deployment's class, made where the program has the engine (a
    program from before it fails at ``dims``, before this is asked for)."""
    from ray_tpu.models.generation import TransformerGenerator

    class Generator(TransformerGenerator):
        """The configuration's program as a slot model: seeded weights in
        the serving dtype, every shape warmed up."""

        def __init__(self, name: str, config: Dict[str, Any],
                     dims: Dict[str, Any], model: Dict[str, Any],
                     opts: Dict[str, Any], seed: int, on_tpu: bool):
            import jax

            import ray_tpu
            from ray_tpu.models import transformer

            cfg = manifest.adapter(config).program_config(
                dims, int(opts["cache_len"]), model)
            device = (ray_tpu.get_runtime_context().get_tpu_devices()[0]
                      if on_tpu else jax.devices()[0])
            dtype = cfg.dtype

            def init(key):
                return jax.tree.map(lambda p: p.astype(dtype),
                                    transformer.init_params(key, cfg))

            with jax.default_device(device):
                params = jax.jit(init)(harness.prng_key(seed))
            super().__init__(cfg, params, slots=int(opts["slots"]),
                             cache_len=int(opts["cache_len"]),
                             length_buckets=opts["length_buckets"],
                             device=device)
            # prompt -> (its place among the watched, steps to its last)
            self._watched: Dict[tuple, tuple] = {}
            self._left: Dict[int, List[int]] = {}    # slot -> [place, steps]
            self.kept: Dict[int, Any] = {}           # place -> its state
            self._slot_state = jax.jit(
                lambda ssm, slot: jax.lax.dynamic_index_in_dim(
                    ssm, slot, 1, keepdims=False))
            self.warm_up()
            self._keep(-1, 0)                        # compiled here, once
            self.shapes = self.compiled()
            self.kernel_in_program = None
            self.temp_bytes = 0
            self.inspect_s = 0.0
            if on_tpu:
                # is the scan's kernel in the longest prefill, and how much
                # the device holds for the largest program while it runs
                # (two programs compiled a second time, for their text and
                # their temporaries: ``inspect_s`` of the replica's start)
                t_inspect = time.monotonic()
                self.kernel_in_program, self.temp_bytes = self._inspect()
                self.inspect_s = time.monotonic() - t_inspect
            _LIVE[name] = self

        def watch(self, prompts: List[List[int]], n_new: int) -> None:
            """Keep each of these sequences' recurrent state ``[n_mamba,
            heads, head_dim, d_state]`` as the last of its ``n_new - 1`` steps
            leaves it (``self.kept[i]``): a slot that is free again computes
            on whatever it holds."""
            self.kept.clear()
            self._watched = {tuple(p): (i, n_new - 1)
                             for i, p in enumerate(prompts)}

        def _keep(self, place: int, slot: int) -> None:
            self.kept[place] = self._slot_state(self.state.ssm,
                                                self._put(np.int32(slot)))

        def admit(self, prompt, slot):
            out = super().admit(prompt, slot)
            if self._watched:
                place, steps = self._watched.pop(tuple(prompt), (None, 0))
                if place is not None and steps:
                    self._left[slot] = [place, steps]
                elif place is not None:
                    self._keep(place, slot)
            return out

        def step(self, active):
            handle = super().step(active)
            for slot, entry in list(self._left.items()):
                entry[1] -= 1
                if not entry[1]:
                    self._keep(entry[0], slot)
                    del self._left[slot]
            return handle

        def compiled(self) -> int:
            """Programs compiled so far (every shape of the three)."""
            return sum(f._cache_size() for f in (
                self._prefill, self._insert, self._decode_step))

        def _inspect(self):
            import jax.numpy as jnp
            longest = self.length_buckets[-1]
            prefill = self._prefill.lower(
                self.params, jnp.zeros((1, longest), jnp.int32),
                jnp.ones((1,), jnp.int32)).compile()
            step = self._decode_step.lower(
                self.params, self.tokens, self.state,
                jnp.zeros((self.slots,), bool)).compile()
            return (KERNEL in prefill.as_text(),
                    max(harness.temp_bytes(prefill), harness.temp_bytes(step)))

        def free(self) -> None:
            """Delete the weights and the slots' state from the device."""
            import jax
            held = (self.params, self.state, self.tokens, self.kept)
            self.params = self.state = self.tokens = None
            self.kept = {}
            for leaf in jax.tree.leaves(held):
                leaf.delete()

    return Generator


def request_plan(mix: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """What the load generator sends: ``traffic.request_plan``'s closed loop
    (the prompts' lengths, a cycle started at the seed's place) and beside
    each prompt its answer's length, the quantiles of ``answer_len`` arranged
    by ``answer_pattern_seed`` and rolled with the prompts."""
    plan = traffic.request_plan(mix, 0.0, seed)
    n, clients = int(mix["n_lengths"]), int(mix["clients"])
    answers = traffic.prompt_lengths(mix["answer_len"], n,
                                     int(mix["answer_pattern_seed"]),
                                     traffic.clients_arranged(mix))
    start = clients * traffic.start_index(seed, max(1, n // clients))
    return {**plan, "answers": np.roll(answers, -start).tolist()}


def reply_ok(r: Dict[str, Any], vocab_size: int) -> bool:
    tokens, logits = r.get("tokens"), r.get("logits")
    return (r.get("status") == 200 and isinstance(tokens, list)
            and isinstance(logits, list)
            and len(tokens) == len(logits) == r.get("n_new")
            and all(isinstance(t, int) and 0 <= t < vocab_size
                    for t in tokens)
            and all(isinstance(v, float) and np.isfinite(v) for v in logits))


def reduce_records(records: List[Dict[str, Any]], seconds: float,
                   vocab_size: int) -> Dict[str, Any]:
    """From the load generator's records to the cell's end-to-end number: the
    prompt tokens and the generated tokens of the replies received inside
    the window, over the window."""
    mine = [r for r in records if 0.0 <= r["done"] < seconds]
    good = [r for r in mine if reply_ok(r, vocab_size)]
    prompt = sum(r["len"] for r in good)
    new = sum(r["n_new"] for r in good)

    def failure(r):
        error = r.get("error") or (
            "malformed reply" if r.get("status") == 200 else "")
        return {**{k: r.get(k) for k in ("i", "len", "due", "sent", "done",
                                         "status")}, "error": str(error)}

    return {
        "attempted": len(mine), "failed": len(mine) - len(good),
        "answered": len(good), "prompt_tokens": prompt, "new_tokens": new,
        "malformed": sum(1 for r in records if r.get("status") == 200
                         and not reply_ok(r, vocab_size)),
        "metrics": {"serve_tokens_per_s": (prompt + new) / seconds},
        "last_reply_s": seconds,
        "failed_records": [failure(r) for r in mine
                           if not reply_ok(r, vocab_size)]}


def offer_load(url: str, plan: Dict[str, Any], seed: int, vocab_size: int,
               seconds: float, timeout_s: float, snapshot):
    """``serve_job.offer_load`` with this job's load generator: start its
    process, hold the window open under the window's span and return ``(t0,
    what the generator printed, (snapshot() at the window's start, at its
    end))``."""
    t0 = time.monotonic() + plan["preroll_s"] + serve_job.CHILD_START_S
    t_end = t0 + seconds
    child = subprocess.Popen(
        [sys.executable, "-m", LOADGEN],
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


def kept_window(env: harness.Env, plan: Dict[str, Any], vocab_size: int,
                offer):
    """``serve_job.kept_window`` for this job's records: ``offer()`` until a
    window did not freeze, ``serve_job.ATTEMPTS`` times at most."""
    again: List[str] = []
    t_first = None
    for attempt in range(1, serve_job.ATTEMPTS + 1):
        if env.trace and attempt > 1:
            shutil.rmtree(env.trace_dir, ignore_errors=True)
            os.makedirs(env.trace_dir)
        with harness.profiled(env):
            t0, load, snapshots = offer()
        t_first = t0 if t_first is None else t_first
        got = reduce_records(load["records"], env.seconds, vocab_size)
        hold = serve_job.longest_hold(load["holds"], -plan["preroll_s"],
                                      got["last_reply_s"])
        if hold is None:
            break
        again.append(
            f"FROZEN window {attempt} of {serve_job.ATTEMPTS}: the load "
            f"generator's own clock skipped {hold[1]:.3f} s (it woke at "
            f"{hold[0]:.3f} s of the window), so the host held every "
            f"process: {got['failed']} of {got['attempted']} failed there; "
            + ("measured again" if attempt < serve_job.ATTEMPTS else
               "every window froze: this one is reported as it stands"))
    return t_first, t0, load, got, snapshots, again


def sample_answers(url: str, prompts: List[List[int]], n_new: int,
                   timeout_s: float) -> List[Dict[str, Any]]:
    """Every prompt sent at once, one thread each and ``n_new`` tokens asked
    of each, so that they decode side by side."""
    replies: List[Any] = [None] * len(prompts)

    def call(i: int) -> None:
        try:
            replies[i] = serve_job._post(
                url, {"prompt": prompts[i], "max_new_tokens": n_new},
                timeout_s)
        except Exception as e:  # noqa: BLE001 - the comparison reports it
            replies[i] = {"error": repr(e)}

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return replies


def neighbours(plan: Dict[str, Any], seed: int, count: int, vocab_size: int
               ) -> List[List[int]]:
    """``count`` more prompts of the plan (the cycle's first lengths, token
    ids of their own: indices past the compared prompts'), to hold the other
    slots while the compared answers are made."""
    lengths = plan["lengths"]
    return [traffic.prompt_tokens(seed, 20_000_000 + i,
                                  lengths[i % len(lengths)], vocab_size)
            for i in range(count)]


def state_error(served, ref) -> float:
    """The served recurrent state of one sequence against the reference's,
    both ``[n_mamba, heads, head_dim, d_state]``: the norm of the difference
    over the norm of the reference's, all layers at once."""
    served, ref = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(served - ref) / np.linalg.norm(ref))


def compare(replies: List[Dict[str, Any]], states: List[Any],
            prompts: List[List[int]], n_new: int, adapter,
            dims: Dict[str, Any], seed: int, device) -> Dict[str, Any]:
    """Each answer against the reference over the prompt followed by the
    answer's own tokens: at generated position i the served logit against
    the reference's logit of that token, and that token against the
    reference's best there (``worst``); and ``states[i]``, the served
    recurrent state after the answer's last step, against the reference's
    after the same tokens (``state_worst``: ``state_error``). Every sequence
    is padded on the right to the longest (the reference is causal), so one
    program is compiled."""
    import jax

    longest = max(len(p) for p in prompts) + n_new
    with jax.default_device(device):
        key = jax.jit(lambda k: adapter.reference_params(k, dims, longest))(
            harness.prng_key(seed))
    ref_fn = jax.jit(lambda k, t, first: adapter.logits_and_state_from(
        k, t, first, n_new, dims))
    worst, state_worst, rows = 0.0, 0.0, []
    for prompt, reply, state in zip(prompts, replies, states):
        tokens = reply.get("tokens")
        if not (isinstance(tokens, list) and len(tokens) == n_new
                and state is not None):
            rows.append({"len": len(prompt), "error": str(reply)[:200],
                         "state_kept": state is not None})
            worst = state_worst = float("inf")
            continue
        row = np.zeros((longest,), np.int32)
        row[:len(prompt)] = prompt
        row[len(prompt):len(prompt) + n_new] = tokens
        # the logits that chose token i sit at the position before it
        ref, ref_state = ref_fn(key, jax.device_put(row, device),
                                np.int32(len(prompt) - 1))
        ref = np.asarray(ref)
        at = ref[np.arange(n_new), tokens]
        errs = np.maximum(np.abs(np.asarray(reply["logits"]) - at),
                          ref.max(axis=-1) - at)
        off = state_error(np.asarray(state), np.asarray(ref_state))
        worst = max(worst, float(errs.max()))
        state_worst = max(state_worst, off)
        rows.append({"len": len(prompt), "err": float(errs.max()),
                     "err_first": float(errs[0]), "err_last": float(errs[-1]),
                     "off_best": int(np.sum(ref.argmax(-1) != tokens)),
                     "state_err": off})
    return {"worst": worst, "state_worst": state_worst, "rows": rows}


def run(env: harness.Env) -> harness.Outcome:
    import ray_tpu
    from ray_tpu import serve

    cell = env.cell
    adapter = manifest.adapter(cell.config)
    dims = adapter.dims(cell.config, "generate", cell.chips)
    logit_atol = adapter.TOLERANCES["logit_atol"]
    state_rtol = adapter.TOLERANCES["state_rtol"]
    mix, opts = cell.traffic, cell.deploy["deployment"]
    timeout_s = float(mix["timeout_s"])
    slots = int(opts["slots"])
    if int(mix["clients"]) != slots:
        raise manifest.ManifestError(
            f"cell {cell.name}: {mix['clients']} callers for {slots} slots")
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    deployment = serve.deployment(
        name=cell.name, generation_slots=slots,
        max_concurrent_queries=max(100, 2 * slots),
        target_latency_ms=float(opts.get("target_latency_ms", 0.0)),
        ray_actor_options={"num_tpus": 1} if env.on_tpu else {})(
            _generator_class())
    t_serve = time.monotonic()
    serve.start()
    serve.run(deployment.bind(
        cell.name, cell.config, dims, cell.deploy.get("model", {}), opts,
        env.seed, env.on_tpu), name=cell.name, route_prefix=opts["route"])
    url = serve.start_http_proxy() + opts["route"]
    serve_startup_s = time.monotonic() - t_serve
    replica = _LIVE[cell.name]
    harness.say(f"serve.run + proxy in {serve_startup_s:.1f} s; "
                f"{replica.shapes} programs compiled at replica start, two "
                f"of them again for their text in {replica.inspect_s:.1f} s")

    plan = request_plan(mix, env.seed)

    def engine_counts() -> Dict[str, float]:
        info = ray_tpu.get(
            serve.api._get_controller().get_replica_handles.remote(cell.name))
        m = ray_tpu.get(info["handles"][0].get_metrics.remote())
        return {k: m[k] for k in m if k.startswith("generate_")}

    def snapshot():
        return replica.compiled(), engine_counts()

    def offer():
        return offer_load(url, plan, env.seed, dims["vocab_size"],
                          env.seconds, timeout_s, snapshot)

    t_first, t0, load, got, snapshots, again = kept_window(
        env, plan, dims["vocab_size"], offer)
    memory_peak = harness.memory_peak([replica.device], replica.temp_bytes)
    sample = cell.deploy["reference"]
    n_new = int(sample["max_new_tokens"])
    prompts = serve_job._sample_prompts(
        env.seed, list(sample["prompt_lengths"]), 1, dims["vocab_size"])
    replica.watch(prompts, n_new)
    replies = sample_answers(
        url, prompts + neighbours(plan, env.seed, slots - len(prompts),
                                  dims["vocab_size"]), n_new, timeout_s)
    replies, beside = replies[:len(prompts)], replies[len(prompts):]
    states = [np.asarray(replica.kept[i]) if i in replica.kept else None
              for i in range(len(prompts))]
    compiled_after = replica.compiled()
    serve.shutdown()
    device = replica.device
    _LIVE.pop(cell.name, None)
    replica.free()
    check = compare(replies, states, prompts, n_new, adapter, dims, env.seed,
                    device)

    (compiled0, counts0), (compiled1, counts1) = snapshots
    engine = {k: counts1[k] - counts0[k] for k in counts1
              if k not in ("generate_slots", "generate_slots_occupied")}
    faults = []
    if compiled1 != compiled0 or compiled_after != replica.shapes:
        faults.append(f"{compiled_after - replica.shapes} compilation(s) "
                      "after the replica's warm-up")
    if env.on_tpu and not replica.kernel_in_program:
        faults.append(f"no {KERNEL} in the compiled prefill")
    if got["malformed"]:
        faults.append(f"{got['malformed']} malformed replies")
    if got["failed"]:
        faults.append(f"{got['failed']} of {got['attempted']} requests "
                      "failed or were refused")
    if not got["answered"]:
        faults.append("no request was answered inside the window")
    unanswered = sum(1 for r in beside
                     if len(r.get("tokens") or ()) != n_new)
    if unanswered:
        faults.append(f"{unanswered} of the {len(beside)} requests beside "
                      "the compared ones failed")
    if not check["worst"] <= logit_atol:
        faults.append(f"served logits off the reference by "
                      f"{check['worst']:.4f} (atol {logit_atol})")
    if not check["state_worst"] <= state_rtol:
        faults.append(f"served recurrent state off the reference by "
                      f"{check['state_worst']:.4f} (rtol {state_rtol})")
    notes = [
        f"closed loop of {plan['clients']}: {got['attempted']} replies in "
        f"the window, {got['failed']} failed; {got['prompt_tokens']} prompt "
        f"tokens + {got['new_tokens']} generated tokens answered; the "
        f"generator's clock skipped {load['skip_max_s'] * 1e3:.1f} ms at most",
        f"the engine in the window: {engine}",
        f"reference (float32): worst logit error {check['worst']:.4f} over "
        f"{n_new} positions of each of {len(prompts)} answers to prompts of "
        f"{sample['prompt_lengths']} tokens (atol {logit_atol}), made among "
        f"{len(beside)} more; worst error of a sequence's recurrent state "
        f"after its last step {check['state_worst']:.4f} (rtol "
        f"{state_rtol}): {check['rows']}"]
    notes.extend(again)
    notes.extend(serve_job.failure_notes(got["failed_records"]))
    notes.extend(f"FAULT: {f}" for f in faults)
    return harness.Outcome(
        correct=not faults, attempted=got["attempted"], failed=got["failed"],
        end_to_end=got["metrics"], t_first_measured=t_first,
        counters={**got["metrics"], **engine,
                  "serve_startup_s": serve_startup_s,
                  "answered": got["answered"],
                  "prompt_tokens": got["prompt_tokens"],
                  "new_tokens": got["new_tokens"], "slots": slots,
                  "cache_len": int(opts["cache_len"]),
                  "window_s": env.seconds, "dims": dims, "devices": 1},
        memory_peak_bytes=memory_peak, notes=notes)
