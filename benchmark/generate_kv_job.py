"""A generating cell of a stack that keeps K and V alone (no recurrent
state): ``generate_job``'s deployment, load, window and reduction as they
are, and a comparison of its own.

``benchmark/generate_job.py`` decides ``correct`` by the logits and by the
Mamba layers' recurrent state, which a stack of attention layers does not
have (its ``DecodeState.ssm`` is empty: the state's error would be 0 / 0).
This job keeps, for the compared sequences, **the K rows of every attention
layer as the last step leaves them**: a global layer's rows ``[cache_len,
kv]`` and a window layer's ring ``[window, kv]``, in which position ``p``
lives in row ``p % window``. After the deployment is shut down and freed the
adapter's streamed float32 reference runs one forward a prompt over the
prompt and the answer's own tokens (``logits_and_keys_from``) and two numbers
decide ``correct``:

* *logits*: ``generate_job.compare``'s two terms at each generated position
  (the served logit against the reference's logit of that token, and that
  token against the reference's best there), **the lower quartile over an
  answer's positions**, the worst answer: ``logit_atol``;
* *cache*: each layer's served rows, a ring unrolled to the positions it
  holds, against the reference's k at those positions (rotated where the
  layer rotates), a row's difference over the row's norm (``cache_error``):
  **the lower quartile over the rows the prefill wrote**, the worst layer
  (``cache_rtol``); of the rows **that no narrow choice of a router has
  reached** (below), **the largest** of those the prefill wrote
  (``clear_rtol``) and **the upper quartile** of those the decode steps
  wrote (``clear_steps_rtol``); and of the rows the decode steps wrote into
  a ring, **the share that lie nearer to the reference's k at their own
  position than at the positions before and after**, the smallest layer's
  (``ring_placed_min``).

Why not the worst position and a layer's whole norm: the router's choice is
discrete. Program and reference round its input differently, so a few tokens
in a hundred of the first mixture and a third by the eighth choose another
sixth expert than the reference does, and such a token's row or logit reads
0.1 to 1 in a sound program (the worst position of a sound run read 0.6 to
1.8 and a layer's norm ratio 0.17 on the chip, as much as a window layer that
sees everything). **The reference says which tokens those can be**: it hands
out, a layer and a position, how narrowly its own router chose (the sixth
logit less the seventh over the spread of the token's 64: ``margins``), and a
row is *clear* where every router below it chose by ``clear_margin`` or more.
On the chip, over 10 seeds and 30 sequences, **none of the 43,955 clear rows
that a prefill wrote above layer 0 was off by 0.1** (the largest 0.080) while
1 to 35 in a hundred of a layer's others were: the rows that are off are the ones that chose
narrowly. So the clear rows of a prompt are held at their largest. A greedy
answer of seeded weights repeats itself: a token that chose narrowly comes
again and again, its rows flip together and move their clear neighbours
through the attention (one answer in thirty), so the steps' clear rows are
held at their upper quartile and every step's row is also asked where it
lies, which no drift changes. No generated position is clear through all
eight layers at that margin, so the logits keep their quartile. What this
cannot see: PERF.md section 7.

The adapter brings ``TOLERANCES["cache_rtol"]``, ``["clear_margin"]``,
``["clear_rtol"]``, ``["clear_steps_rtol"]``, ``["ring_placed_min"]`` and
``logits_and_keys_from`` (logits, every layer's k, every layer's margins), and
its dims name each layer ``window`` or ``global`` (``layer_types``) and the
``window``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import generate_job, harness, manifest, serve_job
from benchmark.generate_job import (KERNEL, _LIVE, kept_window, neighbours,
                                    offer_load, request_plan, sample_answers)

WINDOW = "window"       # ``layer_types``' value of a layer that keeps a ring


def _generator_class():
    """``generate_job``'s deployment class keeping a watched slot's K rows
    in the place of its recurrent state."""
    class Generator(generate_job._generator_class()):
        _slot_rows = None

        def _keep(self, place: int, slot: int) -> None:
            """``(global layers' rows [n, cache_len, kv], window layers'
            rings [n, window, kv], positions held)`` of ``slot`` now."""
            import jax
            if self._slot_rows is None:
                self._slot_rows = jax.jit(lambda state, slot: tuple(
                    jax.lax.dynamic_index_in_dim(a, slot, 1, keepdims=False)
                    for a in (state.k, state.ring_k)) + (
                        state.lengths[slot],))
            self.kept[place] = self._slot_rows(self.state,
                                               self._put(np.int32(slot)))

    return Generator


QUARTILE = 25.0     # the percentile the logits' and the rows' numbers read
STEPS_QUARTILE = 75.0   # ... and the clear rows' that the decode steps wrote
OFF = 0.1           # a row's error from which the notes count it as off


def cache_error(kept, ref_keys, margins, clear_margin: float,
                dims: Dict[str, Any], prompt_len: int) -> Dict[str, Any]:
    """One sequence's served K rows against the reference's k ``[L, S, kv]``.
    A layer is read over the positions it holds (a global layer all ``held``
    of them, a window layer the last ``window``, each found in row ``p %
    window`` of its ring); a row's error is the norm of its difference from
    the reference's row over that row's norm. ``margins`` ``[L, S]`` is how
    narrowly the reference's router chose at each layer and position
    (``smallthinker_reference.route``): a row is **clear** if every layer
    below it chose that token by ``clear_margin`` or more, so that no
    rounding of the router's input has swapped one of the token's experts on
    the way there (layer 0's rows are all clear: no mixture lies below them).
    On the chip no clear row of 30 sequences was off by ``OFF`` for a flip of
    its own (``witness``). Four numbers come of it:

    * ``clear``: **the largest error** over layer 0's rows and, above it,
      over the clear rows **that the prefill and its insert wrote** (``p <
      prompt_len``; a seeded prompt's tokens are drawn apart, so what their
      flipped neighbours add through the attention stays small), the worst
      layer's;
    * ``clear_steps``: over the clear rows **that the decode steps wrote**
      (``p >= prompt_len``, layers 1 and up, taken together), their **upper
      quartile**: a greedy answer of seeded weights repeats itself, so a
      token that chose narrowly comes again and again and what it adds to
      its clear neighbours through the attention is not small (one answer in
      thirty read 0.25 at the ninetieth percentile, 0.03 at the upper
      quartile);
    * ``worst``: over all the rows the prefill wrote, clear or not, the
      rows' **lower quartile**, the worst layer's (the tokens whose router
      chose another expert than the reference's somewhere below read 0.1 to
      1 and are no fault: a few in a hundred of layer 1's rows, a third of
      layer 7's);
    * ``placed``: over **the rows the decode steps wrote into a ring**, the
      share that lie nearer to the reference's k at their own position than
      to its k one position before and one after: the smallest layer's. A
      row that drifted is still its own position's (k is kept rotated: a
      neighbour's differs by a rotation that the drift does not undo), while
      a ring written one row off, or not as a ring, holds another position's
      row there.

    Also every layer's two quartiles for the notes (``layers``: the
    prefill's rows and the steps'), the whole cache's norm ratio a layer
    (``norms``), ``held`` and ``witness``: a layer's ``[share of its rows
    that are clear, share of the clear rows that are off by OFF or more,
    share of the others that are]``, which says whether the rows that are
    off are the ones whose router chose narrowly."""
    rows, ring, held = (np.asarray(a) for a in kept)
    held, window = int(held), ring.shape[1]
    ref_keys = np.asarray(ref_keys, np.float64)
    narrow = np.asarray(margins)[:, :held] < clear_margin
    recent = np.arange(max(0, held - window), held)
    layers, norms, placed, witness = [], [], [], []
    clear_worst, clear_steps = 0.0, []
    taken = {WINDOW: 0, "global": 0}
    for i, kind in enumerate(dims["layer_types"]):
        kind = WINDOW if kind == WINDOW else "global"
        at = recent if kind == WINDOW else np.arange(held)
        stack = (ring[taken[kind]][at % window] if kind == WINDOW
                 else rows[taken[kind]][:held]).astype(np.float64)
        taken[kind] += 1

        def off(shift):     # each row against the reference's k ``shift`` on
            return np.linalg.norm(stack - ref_keys[i][at + shift], axis=-1)

        mine = off(0)
        per_row = mine / np.linalg.norm(ref_keys[i][at], axis=-1)
        norms.append(float(np.linalg.norm(mine)
                           / np.linalg.norm(ref_keys[i][at])))
        stepped = at >= prompt_len
        layers.append([float(np.percentile(group, QUARTILE))
                       if group.size else None
                       for group in (per_row[~stepped], per_row[stepped])])
        clear = ~narrow[:i, at].any(axis=0)
        largest = per_row[clear & ~stepped] if i else per_row
        if largest.size:
            clear_worst = max(clear_worst, float(largest.max()))
        if i:
            clear_steps.append(per_row[clear & stepped])
        witness.append([round(float(np.mean(group)), 4) if group.size
                        else None for group in (
                            clear, per_row[clear] >= OFF,
                            per_row[~clear] >= OFF)])
        if kind == WINDOW and (stepped & (at >= 1)).any():
            own = mine < np.minimum(off(-1), off(1))
            placed.append(float(np.mean(own[stepped & (at >= 1)])))
    clear_steps = np.concatenate(clear_steps) if clear_steps else np.zeros(0)
    return {"worst": max(groups[0] for groups in layers
                         if groups[0] is not None),
            "clear": clear_worst,
            "clear_steps": float(np.percentile(clear_steps, STEPS_QUARTILE))
            if clear_steps.size else 0.0,
            "clear_steps_n": int(clear_steps.size),
            "placed": min(placed, default=1.0),
            "layers": layers, "norms": norms, "held": held,
            "witness": witness}


def logit_errors(served, ref, tokens) -> np.ndarray:
    """``generate_job.compare``'s two terms at each generated position: the
    served logit against the reference's logit of that token, and that token
    against the reference's best there."""
    at = ref[np.arange(len(tokens)), tokens]
    return np.maximum(np.abs(np.asarray(served) - at), ref.max(axis=-1) - at)


def compare(replies: List[Dict[str, Any]], kept: List[Any],
            prompts: List[List[int]], n_new: int, adapter,
            dims: Dict[str, Any], seed: int, device) -> Dict[str, Any]:
    """Each answer against the reference over the prompt followed by the
    answer's own tokens. ``logit_errors`` at each generated position; an
    answer's number is **the lower quartile over its positions** (``worst``:
    the largest of the answers'; the largest single position goes into the
    notes as ``err_max``): a token that a flipped choice has reached reads
    0.1 to 1 and is no fault. And ``cache_error`` of ``kept[i]``
    (``cache_worst``, ``clear_worst``, ``clear_steps_worst``,
    ``placed_worst``). Every sequence is
    padded on the right to the longest (the reference is causal; one position
    more, so that the last row has a neighbour), so one program is
    compiled."""
    import jax

    longest = max(len(p) for p in prompts) + n_new + 1
    with jax.default_device(device):
        key = jax.jit(lambda k: adapter.reference_params(k, dims, longest))(
            harness.prng_key(seed))
    ref_fn = jax.jit(lambda k, t, first: adapter.logits_and_keys_from(
        k, t, first, n_new, dims))
    worst = cache_worst = clear_worst = steps_worst = 0.0
    placed_worst, rows = 1.0, []
    for prompt, reply, held in zip(prompts, replies, kept):
        tokens = reply.get("tokens")
        if not (isinstance(tokens, list) and len(tokens) == n_new
                and held is not None):
            rows.append({"len": len(prompt), "error": str(reply)[:200],
                         "cache_kept": held is not None})
            worst = cache_worst = clear_worst = steps_worst = float("inf")
            placed_worst = 0.0
            continue
        row = np.zeros((longest,), np.int32)
        row[:len(prompt)] = prompt
        row[len(prompt):len(prompt) + n_new] = tokens
        # the logits that chose token i sit at the position before it
        ref, ref_keys, margins = ref_fn(key, jax.device_put(row, device),
                                        np.int32(len(prompt) - 1))
        ref = np.asarray(ref)
        errs = logit_errors(reply["logits"], ref, tokens)
        err = float(np.percentile(errs, QUARTILE))
        off = cache_error(held, ref_keys, margins,
                          adapter.TOLERANCES["clear_margin"], dims,
                          len(prompt))
        worst = max(worst, err)
        cache_worst = max(cache_worst, off["worst"])
        clear_worst = max(clear_worst, off["clear"])
        steps_worst = max(steps_worst, off["clear_steps"])
        placed_worst = min(placed_worst, off["placed"])
        rows.append({"len": len(prompt), "err": err,
                     "err_median": float(np.median(errs)),
                     "err_max": float(errs.max()),
                     "off_best": int(np.sum(ref.argmax(-1) != tokens)),
                     "held": off["held"], "placed": off["placed"],
                     "clear": off["clear"],
                     "clear_steps": [off["clear_steps"],
                                     off["clear_steps_n"]],
                     "witness": off["witness"],
                     "cache_err": [[e if e is None else round(e, 5)
                                    for e in groups]
                                   for groups in off["layers"]],
                     "cache_norm": [round(e, 4) for e in off["norms"]]})
    return {"worst": worst, "cache_worst": cache_worst,
            "clear_worst": clear_worst, "clear_steps_worst": steps_worst,
            "placed_worst": placed_worst,
            "rows": rows}


def run(env: harness.Env) -> harness.Outcome:
    """``generate_job.run`` with this module's generator and comparison."""
    import ray_tpu
    from ray_tpu import serve

    cell = env.cell
    adapter = manifest.adapter(cell.config)
    dims = adapter.dims(cell.config, "generate_kv", cell.chips)
    logit_atol = adapter.TOLERANCES["logit_atol"]
    cache_rtol = adapter.TOLERANCES["cache_rtol"]
    placed_min = adapter.TOLERANCES["ring_placed_min"]
    clear_rtol = adapter.TOLERANCES["clear_rtol"]
    steps_rtol = adapter.TOLERANCES["clear_steps_rtol"]
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
    kept = [tuple(np.asarray(a) for a in replica.kept[i])
            if i in replica.kept else None for i in range(len(prompts))]
    compiled_after = replica.compiled()
    serve.shutdown()
    device = replica.device
    _LIVE.pop(cell.name, None)
    replica.free()
    check = compare(replies, kept, prompts, n_new, adapter, dims, env.seed,
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
    if not check["cache_worst"] <= cache_rtol:
        faults.append(f"served K rows off the reference by "
                      f"{check['cache_worst']:.4f} (rtol {cache_rtol})")
    if not check["clear_worst"] <= clear_rtol:
        faults.append(f"a K row of the prefill's that no narrow choice has "
                      f"reached is off the reference by "
                      f"{check['clear_worst']:.4f} (rtol {clear_rtol})")
    if not check["clear_steps_worst"] <= steps_rtol:
        faults.append(f"the K rows of the steps' that no narrow choice has "
                      f"reached are off the reference by "
                      f"{check['clear_steps_worst']:.4f} (rtol {steps_rtol})")
    if not check["placed_worst"] >= placed_min:
        faults.append(f"only {check['placed_worst']:.3f} of the rows the "
                      f"steps wrote into a ring lie at their own position "
                      f"(at least {placed_min})")
    notes = [
        f"closed loop of {plan['clients']}: {got['attempted']} replies in "
        f"the window, {got['failed']} failed; {got['prompt_tokens']} prompt "
        f"tokens + {got['new_tokens']} generated tokens answered; the "
        f"generator's clock skipped {load['skip_max_s'] * 1e3:.1f} ms at most",
        f"the engine in the window: {engine}",
        f"reference (float32): logit error {check['worst']:.4f} (the lower "
        f"quartile over {n_new} positions, the worst of {len(prompts)} "
        f"answers to prompts of {sample['prompt_lengths']} tokens; atol "
        f"{logit_atol}), made among {len(beside)} more; error of a layer's K "
        f"rows after a sequence's last step {check['cache_worst']:.4f} (the "
        f"lower quartile over the prefill's rows, the worst layer; rtol "
        f"{cache_rtol}), of the rows no narrow choice has reached (every "
        f"router below chose by {adapter.TOLERANCES['clear_margin']} of its "
        f"logits' spread or more) {check['clear_worst']:.4f} (the largest "
        f"of layer 0's and of the prefill's, the worst layer; rtol "
        f"{clear_rtol}) and {check['clear_steps_worst']:.4f} (the upper "
        f"quartile of the steps', layers 1 and up together, the worst "
        f"answer; rtol {steps_rtol}); share of the rows the steps wrote "
        f"into a ring that lie at their own position "
        f"{check['placed_worst']:.4f} (the smallest layer's; at least "
        f"{placed_min}): {check['rows']}"]
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
