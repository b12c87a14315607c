"""chip_smoke.py on the CPU: its phases at a tiny size through the same
``JaxTrainer`` and ``serve`` calls (kernel interpreted, because the backend
is ``cpu``), its refusal to pass without a TPU, and the places this round
stopped from hiding a missing device."""

import logging
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models.transformer import TransformerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402
import chip_smoke  # noqa: E402

TINY = TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                         max_seq_len=128, dtype=jnp.bfloat16, use_flash=True)


@pytest.fixture
def runtime_with_tpus():
    """A runtime that advertises the 8 virtual CPU devices as TPU resource,
    so ``use_tpu=True`` and ``num_tpus=1`` place as they do on the chip."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=8)
    yield
    ray_tpu.shutdown()


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "alone-in-a-directory"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    path = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        path = shutil.copy(path, tmp_path)
    proc = _run_script(path, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip().splitlines()[-1].startswith('{"ok": false')


def test_bench_fails_without_a_tpu(tmp_path):
    proc = _run_script(os.path.join(ROOT, "bench.py"), cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "images_per_sec" not in proc.stdout


def test_bench_peak_lookup_raises_on_unknown_kind():
    assert bench._peak_bf16("TPU v5 lite") == 197e12
    for kind in ("TPU v5", "TPU v9", "cpu", ""):
        with pytest.raises(RuntimeError, match="no peak bf16"):
            bench._peak_bf16(kind)


@pytest.mark.parametrize("preset", [None, "/somewhere/else"])
def test_compile_cache_dir(preset):
    env = {} if preset is None else {"JAX_COMPILATION_CACHE_DIR": preset}
    got = chip_smoke.set_compile_cache(env)
    assert got == env["JAX_COMPILATION_CACHE_DIR"]
    assert got == (preset or os.path.join(ROOT, ".jax_cache"))


def test_phase_train_tiny(runtime_with_tpus):
    out = chip_smoke.phase_train(TINY, steps=5, seed=1)
    (summary,) = out["summaries"]
    assert len(summary["rows"]) == len(summary["ref_rows"]) == 5
    assert summary["compiles"] == 1
    assert out["device"]["platform"] == "cpu"
    # interpret mode: the kernel is not in the program, and only a chip
    # run may pass
    assert not summary["kernel_in_program"]
    with pytest.raises(chip_smoke.SmokeFailure, match="not a TPU"):
        chip_smoke.judge_on_chip(out)
    out["device"]["platform"] = "tpu"
    with pytest.raises(chip_smoke.SmokeFailure, match="not in the program"):
        chip_smoke.judge_on_chip(out)


def test_phase_multichip_tiny(runtime_with_tpus):
    out = chip_smoke.phase_multichip(TINY, n_chips=8, steps=2, seed=2)
    first, second = out["summaries"]
    assert first["mesh"]["data"] == 8 and first["batch_devices"] == 8
    assert second["mesh"]["data"] == 4 and second["mesh"]["tensor"] == 2
    assert second["param_shard_shape"][2] == TINY.n_heads // 2
    assert first["all_reduce_in_program"]


def test_phase_serve_tiny(runtime_with_tpus):
    out = chip_smoke.phase_serve(TINY, seed=3)
    assert out["traces"] <= len(chip_smoke.BUCKETS)
    assert set(out["batches"]) <= set(chip_smoke.BUCKETS)
    assert out["worst_logit_err"] <= chip_smoke.LOGIT_ATOL


def test_assign_devices_raises_when_granted_more_than_present(
        runtime_with_tpus):
    from ray_tpu._private.resources import TPU, ResourceSet
    runtime = ray_tpu._private.worker.global_worker().runtime
    assert runtime._assign_devices(ResourceSet({TPU: 2}), None) \
        == jax.devices()[:2]
    with pytest.raises(RuntimeError, match="granted 9 TPU but this process "
                                           "has 8"):
        runtime._assign_devices(ResourceSet({TPU: 9}), None)


def test_detect_num_tpus_warns_what_it_swallowed(monkeypatch, caplog):
    from ray_tpu._private import worker

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with caplog.at_level(logging.WARNING, logger="ray_tpu"):
        assert worker._detect_num_tpus() == 0
    assert "Unable to initialize backend 'tpu'" in caplog.text


def test_dryrun_multichip_fails_with_too_few_devices():
    import __graft_entry__
    with pytest.raises(RuntimeError, match="needs 16 devices; jax has 8"):
        __graft_entry__.dryrun_multichip(16)


def test_pipeline_with_ring_attention_is_refused(eight_device_mesh):
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train.step import make_lm_train_step
    mesh = build_mesh(MeshConfig(pipe=2, data=1, seq=2),
                      eight_device_mesh[:4])
    with pytest.raises(ValueError, match="cannot nest inside the pipeline"):
        make_lm_train_step(TINY, mesh)
