"""Headline benchmark: ResNet-50 training throughput per chip, with MFU.

North-star image benchmark against the reference's GPU image-training
numbers (``doc/source/ray-air/benchmarks.rst:163-174``: torchvision
ResNet-18, 746.29 images/sec across 16 T4 workers = 46.64 images/sec/chip).
We run the *bigger* ResNet-50 (~2.4x the FLOPs of ResNet-18) and still
compare per-chip against that number, so ``vs_baseline`` is conservative.

Model FLOP utilization (``mfu_pct``) is computed from analytic FLOP
counts over the detected chip's peak bf16 throughput — the "is it
actually fast" number the reference never reports. (XLA's
``cost_analysis`` is NOT used: it counts a ``lax.scan`` body once
rather than per step, undercounting by the scan length.)

Extras carried in the same JSON line:
- ``transformer_tokens_per_sec`` (+ its MFU): decoder LM train step on the
  flagship transformer (the ``__graft_entry__`` model family).
- ``resnet18_images_per_sec``: continuity with rounds 1-3.

Synthetic data (the reference benchmark is also data-loader-free at this
granularity), bfloat16 compute, full fwd+bwd+optimizer step, steps chained
inside one jit scan so dispatch overhead is amortized.

Needs a TPU: with none, with a device kind that has no row in
``_PEAK_BF16``, or when any section raises, the run fails with a non-zero
exit code and prints no headline. Each section's result is also printed as
a JSON progress line when it is measured; the last stdout line is the
combined headline. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, or to ``.jax_cache`` beside this file.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

BASELINE_IMAGES_PER_SEC_PER_CHIP = 746.29 / 16  # T4, benchmarks.rst:171-174

MEASURE_STEPS = 20

# Peak dense bf16 FLOP/s per chip, keyed by ``device_kind`` as jax reports
# it (public specs; the jax-ml scaling-book hardware table).
_PEAK_BF16 = {
    "TPU v6 lite": 918e12,
    "TPU v5 lite": 197e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}


def _peak_bf16(kind: str) -> float:
    if kind not in _PEAK_BF16:
        raise RuntimeError(
            f"no peak bf16 FLOP/s known for device kind {kind!r} "
            f"(known: {', '.join(sorted(_PEAK_BF16))}); add its row to "
            "_PEAK_BF16 with its source")
    return _PEAK_BF16[kind]


def _timed_scan(step_fn, state, n_steps, min_measure_s: float = 0.5):
    """jit a lax.scan of ``n_steps`` steps; returns (state, elapsed_s).

    ``elapsed_s`` is the median per-invocation wall time over enough
    repetitions to accumulate ``min_measure_s`` of measured runtime. FLOP
    accounting is the CALLER's analytic formula: XLA's ``cost_analysis``
    counts a ``scan`` body once, not ``n_steps`` times, so it undercounts
    by the step count.
    """
    @jax.jit
    def run(state, xs):
        return jax.lax.scan(step_fn, state, xs)

    xs = jnp.arange(n_steps)
    state, out = run(state, xs)   # compile + warmup
    jax.block_until_ready(out)
    times = []
    total = 0.0
    while total < min_measure_s or len(times) < 2:
        t0 = time.perf_counter()
        state, out = run(state, xs)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        times.append(dt)
        total += dt
        if len(times) >= 20:
            break
    times.sort()
    return state, times[len(times) // 2]


def bench_resnet(cfg_name: str, batch: int):
    from ray_tpu.models import resnet
    cfg = getattr(resnet, cfg_name)(num_classes=1000)
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)
    images = jax.random.normal(jax.random.PRNGKey(1), (batch, 224, 224, 3),
                               dtype=jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)

    def one_step(state, _):
        params, opt_state = state
        loss, grads = jax.value_and_grad(resnet.loss_fn)(
            params, images, labels, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    _, elapsed = _timed_scan(one_step, (params, opt_state), MEASURE_STEPS)
    images_per_sec = batch * MEASURE_STEPS / elapsed
    # Analytic: ResNet-50 fwd ~= 4.09 GFLOP / image @224, ResNet-18
    # ~= 1.82; fwd+bwd ~= 3x fwd.
    per_image = {"resnet50": 4.09e9, "resnet18": 1.82e9}[cfg_name] * 3
    achieved = per_image * batch * MEASURE_STEPS / elapsed
    return images_per_sec, achieved


def bench_transformer():
    """Decoder-LM train step on the flagship transformer: tokens/sec."""
    from ray_tpu.models import transformer
    from ray_tpu.models.transformer import TransformerConfig

    batch, seq = 8, 1024
    cfg = TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=12, n_heads=16,
        max_seq_len=seq, dtype=jnp.bfloat16,
        use_flash=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    n_params = transformer.num_params(params)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)

    def one_step(state, _):
        params, opt_state = state
        loss, grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params=params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    steps = 10
    _, elapsed = _timed_scan(one_step, (params, opt_state), steps)
    tokens_per_sec = batch * seq * steps / elapsed
    flops = 6.0 * n_params * batch * seq * steps  # 2 fwd + 4 bwd
    achieved = flops / elapsed
    return tokens_per_sec, achieved, n_params


def bench_ppo():
    """End-to-end PPO throughput (sample + compiled learn), env-steps/sec.

    The RL analogue of the reference's tuned-example throughput tracking
    (``rllib/tuned_examples/ppo/``): in-repo CartPole over 8 vector envs,
    whole sgd schedule compiled as one XLA program (``rl/ppo.py``).

    Runs in a CPU-pinned SUBPROCESS: the RL design is CPU rollout actors
    feeding a compiled learner, and a child that needed the chip could
    not have it while this process holds it.
    """
    import subprocess
    code = r"""
import time
import jax
jax.config.update("jax_platforms", "cpu")
from ray_tpu.rl import PPO
algo = (PPO.get_default_config()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, num_envs_per_worker=8,
                  rollout_fragment_length=100)
        .training(train_batch_size=800, sgd_minibatch_size=256,
                  num_sgd_iter=8, lr=3e-4)
        .debugging(seed=0)
        .build())
algo.step()  # warmup: compiles the train program
t0 = time.perf_counter()
steps = 0
for _ in range(3):
    r = algo.step()
    steps += r.get("timesteps_this_iter", 0)
print("PPO_SPS", steps / (time.perf_counter() - t0))
algo.stop()
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    for line in proc.stdout.splitlines():
        if line.startswith("PPO_SPS"):
            return float(line.split()[1])
    raise RuntimeError(f"ppo bench failed: {proc.stderr[-300:]}")


def _emit(obj):
    """Progress line, flushed as soon as its section is measured."""
    print(json.dumps(obj), flush=True)


def main():
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        # A fixed path: it is part of the cache key. Set in the environment
        # too, so the PPO child shares it.
        cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        jax.config.update("jax_compilation_cache_dir", cache)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax found {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind})")
    kind = devices[0].device_kind
    peak = _peak_bf16(kind)

    def section(name, fn):
        t0 = time.perf_counter()
        value = fn()
        _emit({"metric": f"section_{name}", "unit": "progress", "value": "ok",
               "elapsed_s": round(time.perf_counter() - t0, 1)})
        return value

    ppo_sps = section("ppo", bench_ppo)
    r50_ips, r50_flops = section("resnet50",
                                 lambda: bench_resnet("resnet50", 128))
    lm_tps, lm_flops, lm_params = section("transformer", bench_transformer)
    r18_ips, _ = section("resnet18", lambda: bench_resnet("resnet18", 256))

    def mfu(achieved):
        return round(100.0 * achieved / peak, 2)

    _emit({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(r50_ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(r50_ips / BASELINE_IMAGES_PER_SEC_PER_CHIP, 2),
        "mfu_pct": mfu(r50_flops),
        "device_kind": kind,
        "device_count": len(devices),
        "peak_bf16_tflops": round(peak / 1e12, 1),
        "extras": {
            "resnet18_images_per_sec": round(r18_ips, 2),
            "transformer_tokens_per_sec": round(lm_tps, 2),
            "transformer_mfu_pct": mfu(lm_flops),
            "transformer_params_m": round(lm_params / 1e6, 1),
            "ppo_env_steps_per_sec": round(ppo_sps, 1),
        },
    })


if __name__ == "__main__":
    main()
