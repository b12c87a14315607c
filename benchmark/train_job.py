"""A training cell: ``JaxTrainer.fit`` -> ``session.get_mesh`` ->
``make_lm_train_step``, batches from a ``ray_tpu.data`` shard,
``session.report`` with the loss read back after every step.

The worker is a thread of this process (``ray_tpu.init()`` in-process), so
the loop itself marks the window and the profiler sees the device. After
the window the train state is dropped, and the program's loss and gradient
norm on a small seeded sample are compared with the plain float32
reference from the same seeded parameters.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, manifest, reference, traffic

KERNEL = "tpu_custom_call"
WARMUP_STEPS = 3      # the first compiles; two more settle the allocator

# The program computes in bfloat16 (eps 2^-8 = 3.9e-3) with a float32
# softmax and loss; the reference in float32 throughout. Rounding drifts the
# activations by about eps * sqrt(layers) and the loss is a mean over
# thousands of tokens, where most of that cancels; a gradient norm does not
# average it away. The program's RMSNorm epsilon is 1e-6 against the
# published 1e-5 of the reference: on unit-variance activations that is
# 5e-6 relative, far inside either limit. A step computed in 8-bit floats
# (eps 2^-4) would miss both by an order of magnitude.
LOSS_RTOL = 5e-3
GRAD_NORM_RTOL = 3e-2
# The loss of the run's first step, on other random tokens than the sample,
# against the reference's loss on the sample: both are means of per-token
# losses that spread by about 1 nat (unit-variance logits at the seeded
# initialisation), so their difference stays within a few of its standard
# errors, sqrt(1/n_sample + 1/n_step).
FIRST_LOSS_SIGMAS = 6.0


def _compare(cfg, dims, mesh, rules, seed: int, sample: Dict[str, int]
             ) -> Dict[str, float]:
    """The program's loss and gradient norm on a seeded sample against the
    reference's, from the run's own initial parameters."""
    import jax
    import optax
    from ray_tpu.models import transformer
    from ray_tpu.parallel import batch_sharding

    shardings = jax.tree.map(
        lambda axes: rules.sharding(mesh, axes),
        transformer.logical_axes(cfg),
        is_leaf=lambda axes: isinstance(axes, tuple))
    params = jax.jit(lambda k: transformer.init_params(k, cfg),
                     out_shardings=shardings)(harness.prng_key(seed))
    tokens = jax.device_put(
        traffic.rng(seed, 4).integers(
            0, dims["vocab_size"],
            (sample["sequences"], sample["seq_len"] + 1), dtype=np.int32),
        batch_sharding(mesh, rules, ndim=2))

    def program(p, t):
        value, grads = jax.value_and_grad(transformer.loss_fn)(
            p, t, cfg, mesh, rules)
        return value, optax.global_norm(grads)

    got = jax.device_get(jax.jit(program)(params, tokens))
    want = jax.device_get(jax.jit(
        lambda p, t: reference.loss_and_grad_norm(p, t, dims))(params, tokens))
    return {"loss": float(got[0]), "grad_norm": float(got[1]),
            "ref_loss": float(want[0]), "ref_grad_norm": float(want[1])}


def _loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``."""
    import jax
    from ray_tpu.observability import goodput
    from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh
    from ray_tpu.train import make_lm_train_step, session

    t_loop = time.monotonic()
    env: harness.Env = config["env"]
    dims, mix, opts = config["dims"], env.cell.traffic, env.cell.deploy
    batch, seq_len = int(mix["sequences_per_step"]), int(mix["seq_len"])
    mesh = session.get_mesh()
    if mesh is None:
        raise RuntimeError("session.get_mesh() returned None")
    chips = env.cell.chips
    if opts.get("mesh") or mesh.devices.size != chips:
        # the session's own mesh is always data=N over all it was given; a
        # cell that shards the state builds its layout over the same
        # devices, and a cell runs on as many devices as it asks for
        mesh = build_mesh(MeshConfig(**(opts.get("mesh") or {"data": chips})),
                          list(mesh.devices.flat)[:chips])
    devices = list(mesh.devices.flat)
    cfg = harness.transformer_config(dims, seq_len, opts.get("model", {}))
    rules = ShardingRules()
    init_fn, step_fn, shard_batch = make_lm_train_step(cfg, mesh, rules)
    state = init_fn(harness.prng_key(env.seed))
    shard = session.get_dataset_shard("train")

    def batches():
        while True:      # epochs over the shard
            yield from shard.iter_batches(batch_size=batch,
                                          batch_format="numpy",
                                          drop_last=True)

    stream = batches()
    losses: List[float] = []
    tokens = None

    def one_step(i: int) -> None:
        nonlocal state, tokens
        with harness.span("next(batch)"):
            rows = np.stack(next(stream)["tokens"]).astype(np.int32)
        with harness.span("step_fn"):
            tokens = shard_batch(rows)
            state, metrics = step_fn(state, tokens)
        with harness.span("loss_readback"):
            loss = float(metrics["loss"])
            grad_norm = float(metrics["grad_norm"])
        with harness.span("session.report"):
            session.report({"step": i, "loss": loss, "grad_norm": grad_norm})
        losses.append(loss)

    def ledger() -> Dict[str, Any]:
        jobs = goodput.snapshot()["jobs"]
        job = jobs.get(goodput.current_job(), {})
        return {"data_wait_s": job.get("cats", {}).get("data_wait", 0.0),
                "compiles": job.get("compile_count", 0)}

    t_first_step = time.monotonic()
    for i in range(WARMUP_STEPS):
        one_step(i)
    jitted = step_fn.__wrapped__        # under goodput.instrument_jit
    programs_before, before = jitted._cache_size(), ledger()
    steps = 0
    with harness.profiled(env):
        with harness.span(harness.WINDOW_SPAN):
            t0 = time.monotonic()
            while time.monotonic() - t0 < env.seconds:
                one_step(WARMUP_STEPS + steps)
                steps += 1
            t1 = time.monotonic()
    after = ledger()
    compiled_in_window = (jitted._cache_size() - programs_before
                          + after["compiles"] - before["compiles"])
    kernel_in_program, step_temp_bytes = None, 0
    if env.on_tpu:
        compiled = jitted.lower(state, tokens).compile()
        kernel_in_program = KERNEL in compiled.as_text()
        step_temp_bytes = harness.temp_bytes(compiled)
    memory_peak = harness.memory_peak(devices, step_temp_bytes)
    del state, tokens
    check = _compare(cfg, dims, mesh, rules, env.seed, opts["reference"])
    session.report({"summary": {
        "t_loop": t_loop, "t_first_step": t_first_step, "t0": t0, "t1": t1,
        "steps": steps, "tokens_per_step": batch * seq_len,
        "losses": losses, "compiled_in_window": compiled_in_window,
        "data_wait_s": after["data_wait_s"] - before["data_wait_s"],
        "memory_peak_bytes": memory_peak,
        "kernel_in_program": kernel_in_program,
        "mesh": dict(mesh.shape), "check": check}})


def run(env: harness.Env) -> harness.Outcome:
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.observability import goodput
    from ray_tpu.train import JaxTrainer

    cell = env.cell
    dims = manifest.model_dims(cell.config, "train", cell.chips)
    mix = cell.traffic
    goodput.enable()     # data_wait and the count of compilations
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    rows = traffic.token_rows(env.seed, int(mix["dataset_rows"]),
                              int(mix["seq_len"]), dims["vocab_size"])
    dataset = rd.from_items([{"tokens": row} for row in rows], parallelism=1)
    resources = {"TPU": cell.chips} if env.on_tpu else None
    trainer = JaxTrainer(
        _loop, train_loop_config={"env": env, "dims": dims},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=env.on_tpu,
                                     resources_per_worker=resources),
        run_config=RunConfig(name=cell.name),
        datasets={"train": dataset})
    t_fit = time.monotonic()
    result = trainer.fit()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit failed: {result.error!r}")
    summaries = [m["summary"] for m in result.metrics_history
                 if "summary" in m]
    if len(summaries) != 1:
        raise RuntimeError(f"{len(summaries)} summaries from one worker")
    s = summaries[0]
    reported = [m for m in result.metrics_history if "loss" in m]
    check, notes, faults = s["check"], [], []

    def close(a: float, b: float, rtol: float) -> bool:
        return abs(a - b) <= rtol * abs(b)

    if len(reported) != WARMUP_STEPS + s["steps"]:
        faults.append(f"session.report streamed {len(reported)} steps of "
                      f"{WARMUP_STEPS + s['steps']}")
    if not all(np.isfinite(s["losses"])):
        faults.append("a step's loss is not finite")
    if s["compiled_in_window"]:
        faults.append(f"{s['compiled_in_window']} compilation(s) inside "
                      "the window")
    if env.on_tpu and not s["kernel_in_program"]:
        faults.append(f"no {KERNEL} in the compiled step")
    if not close(check["loss"], check["ref_loss"], LOSS_RTOL):
        faults.append(f"loss {check['loss']} vs reference "
                      f"{check['ref_loss']} beyond rtol {LOSS_RTOL}")
    if not close(check["grad_norm"], check["ref_grad_norm"], GRAD_NORM_RTOL):
        faults.append(f"gradient norm {check['grad_norm']} vs reference "
                      f"{check['ref_grad_norm']} beyond rtol "
                      f"{GRAD_NORM_RTOL}")
    sample = cell.deploy["reference"]
    stderr = np.sqrt(1.0 / (sample["sequences"] * sample["seq_len"])
                     + 1.0 / s["tokens_per_step"])
    if abs(s["losses"][0] - check["ref_loss"]) > FIRST_LOSS_SIGMAS * stderr:
        faults.append(f"the first step's loss {s['losses'][0]} is not "
                      f"within {FIRST_LOSS_SIGMAS} standard errors of the "
                      f"reference's {check['ref_loss']}")
    elapsed = s["t1"] - s["t0"]
    rate = s["steps"] * s["tokens_per_step"] / elapsed
    notes.append(f"mesh {s['mesh']}: {s['steps']} steps of "
                 f"{s['tokens_per_step']} tokens in {elapsed:.3f} s; loss "
                 f"{s['losses'][0]:.4f} -> {s['losses'][-1]:.4f}")
    notes.append(f"reference (float32, {cell.deploy['reference']}): loss "
                 f"{check['loss']:.6f} vs {check['ref_loss']:.6f}, gradient "
                 f"norm {check['grad_norm']:.6f} vs "
                 f"{check['ref_grad_norm']:.6f}")
    notes.extend(f"FAULT: {f}" for f in faults)
    return harness.Outcome(
        correct=not faults, attempted=s["steps"], failed=0,
        end_to_end={"train_tokens_per_s": rate},
        t_first_measured=s["t0"],
        counters={"fit_startup_s": s["t_first_step"] - t_fit,
                  "steps": s["steps"], "window_s": elapsed,
                  "tokens_per_step": s["tokens_per_step"],
                  "data_wait_s": s["data_wait_s"], "dims": dims,
                  "seq_len": int(mix["seq_len"]),
                  "sequences_per_step": int(mix["sequences_per_step"]),
                  "devices": int(np.prod(list(s["mesh"].values())))},
        memory_peak_bytes=s["memory_peak_bytes"], notes=notes)
